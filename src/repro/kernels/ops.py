"""jit'd public entry points for the kernels, with backend dispatch.

This module is the *only* kernel API the serving path uses: on TPU the
Pallas kernels compile to Mosaic; everywhere else (this CPU container,
debugging) they run in interpret mode or fall back to the jnp references.
`use_kernels(False)` forces the reference path (used by the dry-run, where
the XLA-level graph is what the roofline reads).

Entry points accept serving-path shapes directly: activations may carry
leading batch/seq dims ([..., K] codes with [..., 1] per-token asymmetric
scale/zero), and the packed-weight layout produced by `pack_int4_weights`
is the one `serve.quantized` stores per layer (vmapped under `lax.scan`).
The dispatch decision is made at trace time, so a `use_kernels(...)` scope
wrapped around a `jax.jit` trace bakes the chosen path into the compiled
function.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from . import ref as _ref
from .block_hadamard import _column_tile, rotation_operand
from .block_hadamard import block_hadamard as _bh_kernel
from .hadamard_quant import hadamard_quant as _hq_kernel
from .int4_matmul import int4_matmul as _i4_kernel
from .paged_attention import paged_attention as _pa_kernel

__all__ = [
    "use_kernels",
    "kernels_enabled",
    "block_hadamard",
    "hadamard_quant",
    "quantize_act",
    "int4_matmul",
    "pack_int4_weights",
    "infer_int4_scales",
    "paged_attention",
    "dispatch_counts",
    "reset_dispatch_counts",
]

_STATE = {"enabled": True}

# -- dispatch telemetry -------------------------------------------------
# Per-(entry point, path) call tallies, kept as plain module state so the
# kernels layer stays free of any serve/telemetry import; the engine
# mirrors them into its MetricsRegistry at snapshot time as
# `kernels.dispatch.<entry>.<kernels|ref>` counters. These count
# *Python-level* calls: entry points are usually invoked inside a jit
# trace, so a tally ticks once per trace (or once per eager call), and
# the path tag records which backend that trace baked in — honest
# per-dispatch wall time lives in the scheduler's trace spans, where the
# caller can block_until_ready around a whole fused dispatch.
_DISPATCH: dict[tuple[str, str], int] = {}


def _record_dispatch(entry: str):
    key = (entry, "kernels" if _STATE["enabled"] else "ref")
    _DISPATCH[key] = _DISPATCH.get(key, 0) + 1


def dispatch_counts() -> dict[tuple[str, str], int]:
    """Snapshot of the per-entry-point call tallies (copy)."""
    return dict(_DISPATCH)


def reset_dispatch_counts():
    _DISPATCH.clear()


def kernels_enabled() -> bool:
    return _STATE["enabled"]


@contextlib.contextmanager
def use_kernels(enabled: bool):
    prev = _STATE["enabled"]
    _STATE["enabled"] = enabled
    try:
        yield
    finally:
        _STATE["enabled"] = prev


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def block_hadamard(x: jnp.ndarray, b: int) -> jnp.ndarray:
    """Online block rotation X·(I ⊗ H_b); Pallas on TPU, interpret elsewhere."""
    _record_dispatch("block_hadamard")
    if not kernels_enabled():
        return _ref.block_hadamard_ref(x, b)
    return _bh_kernel(x, b, interpret=not _on_tpu())


def _rotate_mm(x: jnp.ndarray, b: int) -> jnp.ndarray:
    """X·(I ⊗ H_b) as a dot against the block-diagonal rotation operand —
    the same arithmetic the TPU kernel performs (DESIGN.md §3), in plain
    XLA ops. Used by the reference serving path so `use_kernels(False)`
    is bit-compatible with the interpret-mode kernel (the butterfly FWHT
    in `ref.py` stays the *independent* oracle for the kernel tests)."""
    d = x.shape[-1]
    td = _column_tile(b, d)
    h = rotation_operand(b, td, dtype=jnp.float32)
    lead = x.shape[:-1]
    xs = x.astype(jnp.float32).reshape(-1, d // td, td)
    y = jax.lax.dot_general(xs, h,
                            dimension_numbers=(((2,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    return y.reshape(*lead, d)


def hadamard_quant(x: jnp.ndarray, b: int, *, bits: int = 4):
    """Fused rotate+quantize → (codes, scale, zero); x may be [..., D]."""
    _record_dispatch("hadamard_quant")
    if not kernels_enabled():
        return _ref.quantize_act_int_ref(_rotate_mm(x, b), bits)
    return _hq_kernel(x, b, bits=bits, interpret=not _on_tpu())


def quantize_act(x: jnp.ndarray, bits: int = 4):
    """Per-token asymmetric activation quantization → (codes, scale, zero).

    Kernel path reuses the fused rotate+quantize kernel with block size 1
    (identity rotation), so the row min/max walk stays in VMEM; reference
    path is the jnp oracle.
    """
    _record_dispatch("quantize_act")
    if not kernels_enabled():
        return _ref.quantize_act_int_ref(x, bits)
    return _hq_kernel(x, 1, bits=bits, interpret=not _on_tpu())


def int4_matmul(act_codes, act_scale, act_zero, w_packed, w_scale,
                **kw) -> jnp.ndarray:
    """True-integer W4A4 GEMM; activations may carry leading dims.

    act_codes [..., K] int8 with per-token asymmetric act_scale/act_zero
    [..., 1]; w_packed [K/2, N] uint8 nibbles, w_scale [N] (or [1, N]) per
    output channel. Returns [..., N] float32.
    """
    _record_dispatch("int4_matmul")
    lead = act_codes.shape[:-1]
    k = act_codes.shape[-1]
    qa = act_codes.reshape(-1, k)
    sa = act_scale.reshape(-1, 1)
    za = act_zero.reshape(-1, 1)
    if not kernels_enabled():
        out = _ref.int4_matmul_ref(qa, sa, za, w_packed, w_scale)
    else:
        out = _i4_kernel(qa, sa, za, w_packed, w_scale,
                         interpret=not _on_tpu(), **kw)
    return out.reshape(*lead, out.shape[-1])


def paged_attention(q: jnp.ndarray, kv: dict, block_tables: jnp.ndarray,
                    q_positions: jnp.ndarray,
                    seq_lengths: jnp.ndarray | None = None,
                    layer: jnp.ndarray | int | None = None, *,
                    kv_heads: int,
                    rope_theta: float | None = None,
                    kv_bits: int | None = None,
                    kv_group: int | None = None,
                    q_block: int | None = None,
                    kv_splits: int | None = None,
                    head_block: int | None = None) -> jnp.ndarray:
    """Block-table-native causal attention over layer `layer` of a KV
    page pool.

    q [B, S, H, Dh] (already rotated); kv the whole pool in the
    tile-dense page format of `kernels.paged_attention` — "kv_rows"
    [L, n_pages, T·KH, 2·Dh] (float post-RoPE K|V, or int8/int4 codes
    with "kv_scale_zero" [L, n_pages, 4, T·KH] and `kv_bits`/`kv_group`
    set: dequant and the pre-RoPE K rotation happen inside the walk) of
    `kv_heads` KV heads; `layer` a traced scalar (None → 0) read by the
    page index maps, so a layer loop can carry the pool and hand it over
    whole; block_tables [B, P] int32, q_positions [B, S], seq_lengths [B]
    optional true context lengths — the ragged early-exit skips every
    table column past ceil(len/page_size) (0 skips a padded row's walk
    entirely). `q_block`/`kv_splits`/`head_block` tile the flash-decoding
    grid (`resolve_tiling` defaults); both paths resolve them
    identically, so the split/combine reduction order matches. Pallas on
    TPU, interpret elsewhere, the bit-identical jnp page walk under
    `use_kernels(False)`. Returns [B, S, H, Dh] f32.
    """
    _record_dispatch("paged_attention")
    kw = dict(kv_heads=kv_heads, rope_theta=rope_theta, kv_bits=kv_bits,
              kv_group=kv_group, q_block=q_block, kv_splits=kv_splits,
              head_block=head_block)
    if not kernels_enabled():
        return _ref.paged_attention_ref(q, kv, block_tables, q_positions,
                                        seq_lengths, layer, **kw)
    return _pa_kernel(q, kv, block_tables, q_positions, seq_lengths, layer,
                      interpret=not _on_tpu(), **kw)


def infer_int4_scales(w: jnp.ndarray) -> jnp.ndarray:
    """Recover per-output-channel symmetric int4 scales from a [K, N] weight.

    PTQ hands the serving packer weights that are *already rounded* to a
    symmetric int4 grid k·s (k ∈ [-7, 7]), but the scale s itself is not
    stored in the PTQ result. `absmax/7` only recovers s when some channel
    code hits ±7 — GPTQ/Qronos error diffusion can leave a channel's max
    code below 7, in which case absmax/7 silently re-grids the channel and
    the integer path drifts from fake-quant. Searching s ∈ {absmax/m,
    m = 7..1} for the minimum round-trip error recovers the exact grid for
    every on-grid channel (m = max |code|) and degrades to absmax/7 for
    channels that were never on a grid.
    """
    wf = w.astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(wf), axis=0), 1e-12)      # [N]
    ms = jnp.arange(7, 0, -1, dtype=jnp.float32)                    # prefer 7
    cands = absmax[None, :] / ms[:, None]                           # [7, N]

    def roundtrip_err(s):
        q = jnp.clip(jnp.round(wf / s[None]), -7, 7) * s[None]
        return jnp.sum(jnp.square(q - wf), axis=0)

    errs = jax.vmap(roundtrip_err)(cands)                           # [7, N]
    best = jnp.argmin(errs, axis=0)                                 # first min
    return jnp.take_along_axis(cands, best[None], axis=0)[0]


def pack_int4_weights(w: jnp.ndarray, scale: jnp.ndarray | None = None):
    """Quantize a [K, N] float weight symmetrically to int4 and pack.

    The one shared packer for the serving path and the kernel benchmarks —
    vmap it over a leading layer axis to pack a whole `lax.scan` stack.
    `scale` is per output channel ([N] or [1, N], e.g. from
    `int_weight_scales_mse`); when None the grid is recovered from the
    weights via `infer_int4_scales`. Returns {"packed": uint8 [K/2, N],
    "scale": float32 [N]}.
    """
    wf = w.astype(jnp.float32)
    if scale is None:
        scale = infer_int4_scales(wf)
    scale = scale.reshape(-1).astype(jnp.float32)
    codes = jnp.clip(jnp.round(wf / scale[None]), -7, 7).astype(jnp.int8)
    return {"packed": _ref.int4_pack(codes), "scale": scale}
