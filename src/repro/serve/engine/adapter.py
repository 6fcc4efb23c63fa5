"""Unified model adapter: one `ServableModel` protocol for every path.

The engine serves three execution paths through one interface:

  * the bf16 `repro.models.transformer.Model` — any decode-capable token-LM
    family (dense, MoE, pure SSM, hybrid),
  * the fake-quant model from `pipeline.build_quantized_model` (the same
    `Model` class with PTQ hooks installed — quantization error included,
    weights stored dequantized),
  * the packed-int4 `repro.serve.quantized.QuantizedDenseLM` (true integer
    arithmetic, optional int8/int4 KV cache; dense archs).

Paged state is not KV-shaped by fiat. Each adapter derives a `StateSpec`
from its config declaring which state *kinds* the model carries:

  * `kv` — sequence-length-proportional state (attention caches), stored
    in page pools and addressed through per-sequence block tables;
  * `register` — fixed-size per-sequence state (a Mamba2 layer's conv tail
    and SSD state), stored in slot pools and addressed by one register
    slot per sequence, allocated at admission.

`init_state(n_pages, page_size, n_slots)` builds the partitioned
`{"kv": ..., "register": ...}` pytree the engine owns (the page/slot axis
is the batch axis), and `forward_chunk(params, tokens, state, index,
block_table, seq_lengths, register_index)` runs one [B, S] chunk against
it: kv rows are scattered straight into their pages and attention walks
the table through `kernels.ops.paged_attention`; register leaves are
gathered by slot at entry and scattered back once per call — no gathered
slab exists anywhere in the step. Dense models are pure kv (the spec has
no register part and `register_index` stays None), pure SSMs are pure
register (no block table), hybrids mix both kinds in one state pytree,
and MoE needs no extra state kind at all — its routed FFN rides inside
the forward. With `block_table` and `register_index` both None the same
entry serves the model's native dense contiguous cache (the test oracle
and the legacy scheduler).

Genuinely unservable configs fail fast in `derive_state_spec` with a
capability error: encoder-only families have no autoregressive decode,
and frontend (audio/vision) models are not token LMs.

The adapter wraps that pair, normalises cache dtype handling, maps the
partitioned engine state onto the model's native cache structure, and
jits the step end to end, so `scheduler.ServeEngine` never branches on
which backend — or which architecture family — runs underneath.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.serve.quantized import QuantizedDenseLM

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class StateSpec:
    """Which paged-state kinds a served model's cache carries.

    `kv`: grows with sequence length; block-table-indexed page pools.
    `register`: fixed size per sequence; slot-indexed register pools.
    `register_leaves` names the per-layer register leaves (accounting and
    tests; the engine itself only needs the booleans).
    """
    kv: bool
    register: bool
    register_leaves: tuple[str, ...] = ()

    @property
    def prefix_shareable(self) -> bool:
        """Whether the prefix-sharing radix cache may serve this spec:
        kv pages are position-addressable (row i depends only on tokens
        ≤ i), so a cached prefix page is valid for any sequence with the
        same leading tokens. Register (SSM conv/SSD) state is a running
        summary whose value at a position depends on how it was chunked
        — never shareable — so any spec carrying register state opts the
        whole model out rather than serving half its layers stale."""
        return self.kv and not self.register


def derive_state_spec(cfg) -> StateSpec:
    """Per-family state spec — the capability check for servability.

    Raises ValueError for configs the paged engine genuinely cannot
    serve: encoder-only families (no autoregressive decode step exists)
    and frontend models (the engine schedules token streams, not
    audio-frame/vision-patch prefixes).
    """
    if cfg.family == "encoder":
        raise ValueError(
            f"{cfg.name}: encoder-only family has no autoregressive decode "
            "step — there is nothing for the serving engine to schedule")
    if cfg.frontend is not None:
        raise ValueError(
            f"{cfg.name}: paged serving engine serves token LMs only "
            f"(frontend={cfg.frontend!r} supplies non-token inputs)")
    if cfg.family in ("dense", "vlm", "moe"):
        return StateSpec(kv=True, register=False)
    if cfg.family == "ssm":
        return StateSpec(kv=False, register=True,
                         register_leaves=("conv", "state"))
    if cfg.family == "hybrid":
        return StateSpec(kv=True, register=True,
                         register_leaves=("conv", "state"))
    raise ValueError(f"{cfg.name}: family {cfg.family!r} has no state spec")


@runtime_checkable
class ServableModel(Protocol):
    """What the paged engine needs from an execution path."""

    cfg: Any
    params: Params

    @property
    def state_spec(self) -> StateSpec:
        """Which state kinds `init_state` builds (drives admission)."""
        ...

    def init_state(self, n_pages: int, page_size: int,
                   n_slots: int) -> Params:
        """Partitioned `{"kv": ..., "register": ...}` paged state: kv
        leaves [n_layers, n_pages, ...] in the page format of
        `kernels.paged_attention`, register leaves [n_layers, n_slots,
        ...]. Either part may be empty per the spec."""
        ...

    def init_cache(self, batch: int, max_len: int) -> Params:
        """The model's native dense contiguous cache (test oracle /
        legacy scheduler path)."""
        ...

    def forward_chunk(self, params: Params, tokens: jnp.ndarray,
                      cache: Params, index: jnp.ndarray,
                      block_table: jnp.ndarray | None = None,
                      seq_lengths: jnp.ndarray | None = None,
                      register_index: jnp.ndarray | None = None):
        """[B, S] tokens at fill position(s) `index` → ([B, S, V] logits,
        updated cache). In paged mode (`block_table` [B, P] and/or
        `register_index` [B] present) `cache` is the engine's partitioned
        state; `seq_lengths` [B] (true context lengths, 0 for padded
        rows) drive the paged kernel's ragged early-exit and mask padded
        prefill-chunk tails out of the SSM state recurrence. `params` is
        passed explicitly (usually `adapter.params`) so the engine's
        fused jits trace the weights as arguments, not as per-executable
        constants."""
        ...


class _AdapterBase:
    name: str
    # quality probes (serve.telemetry.quality) are instrumented on the
    # integer path; adapters that cannot run them advertise it so the
    # engine rejects a probed configuration at construction, not mid-run
    supports_quality_probes: bool = False

    def __init__(self, cfg, params: Params):
        # capability check: raises for encoder/frontend configs
        self.spec = derive_state_spec(cfg)
        self.cfg = cfg
        self.params = params

    @property
    def state_spec(self) -> StateSpec:
        return self.spec

    # -- partitioned engine state ↔ the model's native cache structure --

    def _merge(self, state: Params) -> Params:
        fam = self.cfg.family
        if fam == "ssm":
            return state["register"]
        if fam == "hybrid":
            return {"ssm": state["register"]["ssm"],
                    "shared": state["kv"]["shared"]}
        return state["kv"]

    def _split(self, caches: Params) -> Params:
        fam = self.cfg.family
        if fam == "ssm":
            return {"kv": {}, "register": caches}
        if fam == "hybrid":
            return {"kv": {"shared": caches["shared"]},
                    "register": {"ssm": caches["ssm"]}}
        return {"kv": caches, "register": {}}


class DenseModelAdapter(_AdapterBase):
    """bf16 or fake-quant `Model` of any servable family (the PTQ hooks
    ride along transparently)."""

    def __init__(self, model, params: Params, *, name: str = "bf16",
                 cache_dtype=jnp.float32):
        super().__init__(model.cfg, params)
        self.model = model
        self.name = name
        self.cache_dtype = cache_dtype
        self._forward = jax.jit(model.forward_chunk)

    def init_state(self, n_pages: int, page_size: int,
                   n_slots: int) -> Params:
        return self.model.init_paged_state(n_pages, page_size, n_slots,
                                           dtype=self.cache_dtype)

    def init_cache(self, batch: int, max_len: int) -> Params:
        return self.model.init_cache(batch, max_len, dtype=self.cache_dtype)

    def forward_chunk(self, params, tokens, cache, index, block_table=None,
                      seq_lengths=None, register_index=None, *,
                      probe=False):
        if probe:
            raise ValueError("quality probes are instrumented on the "
                             "integer path only (QuantizedDenseLM)")
        paged = block_table is not None or register_index is not None
        caches = self._merge(cache) if paged else cache
        logits, new = self._forward(params, tokens, caches,
                                    jnp.asarray(index, jnp.int32),
                                    block_table, seq_lengths, register_index)
        return logits, (self._split(new) if paged else new)


class IntegerModelAdapter(_AdapterBase):
    """Packed-int4 `QuantizedDenseLM` (params = packed weights). Dense
    archs only, so its state is pure kv."""

    supports_quality_probes = True

    def __init__(self, qlm: QuantizedDenseLM, packed_params: Params):
        super().__init__(qlm.cfg, packed_params)
        self.qlm = qlm
        self.name = f"int4_kv{qlm.kv_bits or 'bf16'}"

    def init_state(self, n_pages: int, page_size: int,
                   n_slots: int) -> Params:
        return {"kv": self.qlm.init_cache(n_pages, page_size),
                "register": {}}

    def init_cache(self, batch: int, max_len: int) -> Params:
        return self.qlm.init_cache(batch, max_len)

    def forward_chunk(self, params, tokens, cache, index, block_table=None,
                      seq_lengths=None, register_index=None, *,
                      probe=False):
        if register_index is not None:
            raise ValueError("integer path serves kv-only state")
        paged = block_table is not None
        caches = self._merge(cache) if paged else cache
        # QuantizedDenseLM jits internally (per kernels-enabled state)
        out = self.qlm.forward_chunk(params, tokens, caches, index,
                                     block_table, seq_lengths, probe=probe)
        if probe:
            logits, new, stats = out
            return logits, (self._split(new) if paged else new), stats
        logits, new = out
        return logits, (self._split(new) if paged else new)


def as_servable(model, params: Params, **kw) -> ServableModel:
    """Wrap any supported execution path in its engine adapter."""
    if isinstance(model, QuantizedDenseLM):
        return IntegerModelAdapter(model, params)
    return DenseModelAdapter(model, params, **kw)
