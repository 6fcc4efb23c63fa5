"""The unified model: dense / MoE / SSM / hybrid / encoder / VLM families.

Parameters are plain dict pytrees with per-layer leaves stacked on axis 0 so
the layer stack is a `lax.scan` (compact HLO — essential for compiling 62-layer
models in the dry-run). The PTQ pipeline walks the same tree to merge
permutations/rotations and swap in quantized projections.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.distributed.context import shard_act
from repro.kernels.paged_attention import zero_pages
from . import layers as L
from . import moe as M
from . import ssm as S
from .config import ArchConfig, ShapeCell

Params = dict[str, Any]

FRONTEND_DIMS = {"audio_frames": 512, "vision_patches": 1024}


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


class Model:
    """Functional model wrapper for one ArchConfig."""

    def __init__(self, cfg: ArchConfig, *, quant_hooks=None,
                 remat_policy: str = "nothing",
                 moe_dense_oracle: bool = False):
        self.cfg = cfg.validate()
        self.pdt = _dtype(cfg.param_dtype)
        self.cdt = _dtype(cfg.compute_dtype)
        # quant_hooks: {"down_proj_fn": fn(h, w)->out, "act_in_fn": fn(x)->x}
        self.quant_hooks = quant_hooks or {}
        # moe_dense_oracle: route MoE FFNs through the evaluate-all-experts
        # oracle (per-token exact, chunking-invariant) instead of the
        # capacity-bounded gather dispatch — parity tests only, where
        # chunk-length-dependent capacity drops would break chunked-prefill
        # ≡ whole-prompt comparisons
        self.moe_dense_oracle = moe_dense_oracle
        # remat_policy: "nothing" saves only layer boundaries (min memory,
        # max recompute — the backward re-runs the layer INCLUDING its
        # ZeRO-3 weight all-gathers); "dots" saves matmul outputs, which
        # keeps the recompute (and crucially the re-gathers) out of the
        # backward at ~2 GiB/device of extra activations (§Perf, cell A).
        self.remat_policy = remat_policy
        if cfg.n_heads:
            self.attn_spec = L.AttnSpec(
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, causal=cfg.causal,
                rope_theta=cfg.rope_theta, qkv_bias=cfg.qkv_bias)

    # ------------------------------------------------------------------
    # Init
    # ------------------------------------------------------------------

    def _init_block(self, key) -> Params:
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        if cfg.family == "ssm":
            return {
                "norm": L.init_norm(cfg.d_model, cfg.norm, self.pdt),
                "ssm": S.init_ssm(ks[0], cfg.d_model, expand=cfg.ssm_expand,
                                  head_dim=cfg.ssm_head_dim,
                                  state=cfg.ssm_state,
                                  conv_width=cfg.ssm_conv_width,
                                  dtype=self.pdt),
            }
        if cfg.family == "hybrid":
            return {
                "norm": L.init_norm(cfg.d_model, cfg.norm, self.pdt),
                "ssm": S.init_ssm(ks[0], cfg.d_model, expand=cfg.ssm_expand,
                                  head_dim=cfg.ssm_head_dim,
                                  state=cfg.ssm_state,
                                  conv_width=cfg.ssm_conv_width,
                                  dtype=self.pdt),
            }
        blk = {
            "attn_norm": L.init_norm(cfg.d_model, cfg.norm, self.pdt),
            "attn": L.init_attention(ks[0], cfg.d_model, self.attn_spec,
                                     self.pdt),
            "ffn_norm": L.init_norm(cfg.d_model, cfg.norm, self.pdt),
        }
        if cfg.uses_moe:
            blk["moe"] = M.init_moe(ks[1], cfg.d_model, cfg.n_experts,
                                    cfg.moe_d_ff, cfg.n_shared_experts,
                                    cfg.act, self.pdt)
        else:
            blk["ffn"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act,
                                    self.pdt)
        return blk

    def _shared_attn_block(self, key) -> Params:
        """Hybrid (Zamba2): one shared attention+FFN block."""
        cfg = self.cfg
        ks = jax.random.split(key, 2)
        return {
            "attn_norm": L.init_norm(cfg.d_model, cfg.norm, self.pdt),
            "attn": L.init_attention(ks[0], cfg.d_model, self.attn_spec,
                                     self.pdt),
            "ffn_norm": L.init_norm(cfg.d_model, cfg.norm, self.pdt),
            "ffn": L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act,
                              self.pdt),
        }

    def init(self, key) -> Params:
        cfg = self.cfg
        k_emb, k_layers, k_head, k_shared, k_fe = jax.random.split(key, 5)
        p: Params = {}
        if cfg.frontend != "audio_frames":
            p["embed"] = (jax.random.normal(k_emb, (cfg.vocab, cfg.d_model))
                          * 0.02).astype(self.pdt)
        if cfg.frontend is not None:
            fdim = FRONTEND_DIMS[cfg.frontend]
            p["frontend_proj"] = (jax.random.normal(k_fe, (fdim, cfg.d_model))
                                  * (fdim ** -0.5)).astype(self.pdt)
        layer_keys = jax.random.split(k_layers, cfg.n_layers)
        blocks = [self._init_block(k) for k in layer_keys]
        p["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
        if cfg.family == "hybrid":
            p["shared_attn"] = self._shared_attn_block(k_shared)
        p["final_norm"] = L.init_norm(cfg.d_model, cfg.norm, self.pdt)
        p["lm_head"] = (jax.random.normal(k_head, (cfg.d_model, cfg.vocab))
                        * (cfg.d_model ** -0.5)).astype(self.pdt)
        return p

    def init_abstract(self) -> Params:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # ------------------------------------------------------------------
    # Blocks
    # ------------------------------------------------------------------

    def _apply_block(self, x, blk: Params, cache, cache_index, *,
                     positions=None, block_table=None, seq_lengths=None,
                     register_index=None, valid_len=None):
        cfg = self.cfg
        hooks = self.quant_hooks
        new_cache = None
        if cfg.family in ("ssm", "hybrid"):
            h = L.apply_norm(x, blk["norm"], cfg.norm)
            h, new_cache = S.ssm_block(
                h, blk["ssm"], head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
                chunk=cfg.ssm_chunk, cache=cache, cache_index=cache_index,
                register_index=register_index, valid_len=valid_len,
                act_in=hooks.get("act_in"),
                out_proj_fn=hooks.get("ssm_out_proj_fn"))
            return x + h, new_cache

        h = L.apply_norm(x, blk["attn_norm"], cfg.norm)
        h, attn_cache = L.attention(h, blk["attn"], self.attn_spec,
                                    positions=positions, cache=cache,
                                    cache_index=cache_index,
                                    block_table=block_table,
                                    seq_lengths=seq_lengths,
                                    act_in=hooks.get("act_in"))
        x = x + h
        h = L.apply_norm(x, blk["ffn_norm"], cfg.norm)
        if cfg.uses_moe:
            if self.moe_dense_oracle:
                h = M.moe_ffn_dense_oracle(
                    h, blk["moe"], n_experts=cfg.n_experts, top_k=cfg.top_k,
                    act=cfg.act, down_proj_fn=hooks.get("moe_down_proj_fn"),
                    act_in=hooks.get("act_in"),
                    shared_down_proj_fn=hooks.get("down_proj_fn"))
            else:
                h = M.moe_ffn(h, blk["moe"], n_experts=cfg.n_experts,
                              top_k=cfg.top_k,
                              capacity_factor=cfg.capacity_factor,
                              act=cfg.act,
                              down_proj_fn=hooks.get("moe_down_proj_fn"),
                              act_in=hooks.get("act_in"),
                              shared_down_proj_fn=hooks.get("down_proj_fn"))
        else:
            h = L.mlp(h, blk["ffn"], cfg.act,
                      down_proj_fn=hooks.get("down_proj_fn"),
                      act_in=hooks.get("act_in"))
        return x + h, attn_cache

    def _apply_shared(self, x, shared: Params, cache, cache_index, *,
                      block_table=None, seq_lengths=None):
        cfg = self.cfg
        hooks = self.quant_hooks
        h = L.apply_norm(x, shared["attn_norm"], cfg.norm)
        h, attn_cache = L.attention(h, shared["attn"], self.attn_spec,
                                    cache=cache, cache_index=cache_index,
                                    block_table=block_table,
                                    seq_lengths=seq_lengths,
                                    act_in=hooks.get("act_in"))
        x = x + h
        h = L.apply_norm(x, shared["ffn_norm"], cfg.norm)
        h = L.mlp(h, shared["ffn"], cfg.act,
                  down_proj_fn=hooks.get("down_proj_fn"),
                  act_in=hooks.get("act_in"))
        return x + h, attn_cache

    def _run_layers_unrolled(self, params, x):
        """Python-loop execution (no scan) — used by PTQ calibration so the
        capture hook can record per-layer activations via side effects."""
        cfg = self.cfg
        lp = params["layers"]

        def layer_slice(i):
            return jax.tree.map(lambda a: a[i], lp)

        if cfg.family == "hybrid":
            n_groups, period, _ = self._hybrid_groups()
            for i in range(cfg.n_layers):
                x, _ = self._apply_block(x, layer_slice(i), None, None)
                if (i + 1) % period == 0 and (i + 1) // period <= n_groups:
                    x, _ = self._apply_shared(x, params["shared_attn"],
                                              None, None)
            return x
        for i in range(cfg.n_layers):
            x, _ = self._apply_block(x, layer_slice(i), None, None)
        return x

    def _hybrid_groups(self) -> tuple[int, int, int]:
        """(n_groups, period, tail): L = n_groups·period + tail; the shared
        attention block runs after each full group."""
        cfg = self.cfg
        period = cfg.hybrid_period or cfg.n_layers
        n_groups = cfg.n_layers // period
        tail = cfg.n_layers - n_groups * period
        return n_groups, period, tail

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

    def _embed_inputs(self, params: Params, batch: Params):
        cfg = self.cfg
        if cfg.frontend == "audio_frames":
            x = batch["frames"].astype(self.cdt) @ params["frontend_proj"]
        elif cfg.frontend == "vision_patches":
            pe = batch["patches"].astype(self.cdt) @ params["frontend_proj"]
            te = jnp.take(params["embed"], batch["tokens"], axis=0)
            x = jnp.concatenate([pe, te.astype(self.cdt)], axis=1)
        else:
            x = jnp.take(params["embed"], batch["tokens"], axis=0)
        x = x.astype(self.cdt)
        return shard_act(x, ("batch", "seq", "embed"))

    def _run_layers(self, params, x, *, caches=None, cache_index=None,
                    block_table=None, seq_lengths=None, register_index=None,
                    valid_len=None, remat: bool = False):
        cfg = self.cfg

        def body(carry, inp):
            blk, cache = inp
            y, new_cache = self._apply_block(carry, blk, cache, cache_index,
                                             block_table=block_table,
                                             seq_lengths=seq_lengths,
                                             register_index=register_index,
                                             valid_len=valid_len)
            return y, new_cache

        if remat:
            policy = {
                "nothing": jax.checkpoint_policies.nothing_saveable,
                "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            }[self.remat_policy]
            body = jax.checkpoint(body, policy=policy)

        if cfg.family == "hybrid":
            n_groups, period, tail = self._hybrid_groups()
            lp = params["layers"]
            main = jax.tree.map(
                lambda a: a[: n_groups * period].reshape(
                    n_groups, period, *a.shape[1:]), lp)
            tail_p = jax.tree.map(lambda a: a[n_groups * period:], lp)
            c_main = c_tail = c_shared = None
            if caches is not None:
                c_main = jax.tree.map(
                    lambda a: a[: n_groups * period].reshape(
                        n_groups, period, *a.shape[1:]), caches["ssm"])
                c_tail = jax.tree.map(lambda a: a[n_groups * period:],
                                      caches["ssm"])
                c_shared = caches["shared"]

            def group_body(carry, inp):
                gp, gcache, shared_cache = inp
                y, new_c = jax.lax.scan(body, carry, (gp, gcache))
                y, new_sc = self._apply_shared(y, params["shared_attn"],
                                               shared_cache, cache_index,
                                               block_table=block_table,
                                               seq_lengths=seq_lengths)
                return y, (new_c, new_sc)

            if caches is None:
                def group_body_nc(carry, gp):
                    y, _ = jax.lax.scan(lambda c, b: body(c, (b, None)),
                                        carry, gp)
                    y, _ = self._apply_shared(y, params["shared_attn"], None,
                                              None)
                    return y, None
                x, _ = jax.lax.scan(group_body_nc, x, main)
                if tail:
                    x, _ = jax.lax.scan(lambda c, b: body(c, (b, None)), x,
                                        tail_p)
                return x, None
            else:
                x, (nc_main, nc_shared) = jax.lax.scan(
                    group_body, x, (main, c_main, c_shared))
                nc_main = jax.tree.map(
                    lambda a: a.reshape(n_groups * period, *a.shape[2:]),
                    nc_main)
                nc_tail = None
                if tail:
                    x, nc_tail = jax.lax.scan(body, x, (tail_p, c_tail))
                    nc_main = jax.tree.map(
                        lambda a, t: jnp.concatenate([a, t], 0),
                        nc_main, nc_tail)
                return x, {"ssm": nc_main, "shared": nc_shared}

        if caches is None:
            x, _ = jax.lax.scan(lambda c, b: body(c, (b, None)), x,
                                params["layers"])
            return x, None
        x, new_caches = jax.lax.scan(body, x, (params["layers"], caches))
        return x, new_caches

    def forward(self, params: Params, batch: Params, *,
                remat: bool = False, unroll: bool = False) -> jnp.ndarray:
        """Full-sequence forward → logits [B, S, vocab]. `unroll=True` runs
        the layer stack as a Python loop (PTQ calibration capture)."""
        x = self._embed_inputs(params, batch)
        if unroll:
            x = self._run_layers_unrolled(params, x)
        else:
            x, _ = self._run_layers(params, x, remat=remat)
        x = L.apply_norm(x, params["final_norm"], self.cfg.norm)
        logits = x @ params["lm_head"].astype(self.cdt)
        return shard_act(logits, ("batch", "seq", "vocab"))

    def loss_fn(self, params: Params, batch: Params, *,
                remat: bool = False):
        """Mean next-token (or frame-label) cross-entropy + z-loss."""
        cfg = self.cfg
        logits = self.forward(params, batch, remat=remat).astype(jnp.float32)
        labels = batch["labels"]
        if cfg.frontend == "vision_patches":
            # labels cover text positions only (after the patch prefix)
            logits = logits[:, -labels.shape[1]:]
        mask = (labels >= 0).astype(jnp.float32)
        labels = jnp.maximum(labels, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mask
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        loss = jnp.sum(nll) / denom
        zloss = 1e-4 * jnp.sum((lse * mask) ** 2) / denom
        return loss + zloss, {"nll": loss, "zloss": zloss,
                              "tokens": jnp.sum(mask)}

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------

    def init_cache(self, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Params:
        cfg = self.cfg
        if cfg.family == "ssm":
            one = S.init_ssm_cache(batch, cfg.d_model, expand=cfg.ssm_expand,
                                   head_dim=cfg.ssm_head_dim,
                                   state=cfg.ssm_state,
                                   conv_width=cfg.ssm_conv_width, dtype=dtype)
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.n_layers, *a.shape)), one)
        if cfg.family == "hybrid":
            one = S.init_ssm_cache(batch, cfg.d_model, expand=cfg.ssm_expand,
                                   head_dim=cfg.ssm_head_dim,
                                   state=cfg.ssm_state,
                                   conv_width=cfg.ssm_conv_width, dtype=dtype)
            ssm_c = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.n_layers, *a.shape)), one)
            n_groups, _, _ = self._hybrid_groups()
            ac = L.init_attention_cache(batch, max_len, self.attn_spec, dtype)
            shared_c = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_groups, *a.shape)), ac)
            return {"ssm": ssm_c, "shared": shared_c}
        one = L.init_attention_cache(batch, max_len, self.attn_spec, dtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers, *a.shape)), one)

    def init_paged_state(self, n_pages: int, page_size: int, n_slots: int,
                         dtype=jnp.bfloat16) -> Params:
        """Engine-owned partitioned state `{"kv": ..., "register": ...}`.

        kv leaves are page pools ([n_layers/n_groups, n_pages, ...] in the
        page format of `kernels.paged_attention`, block-table-indexed);
        register leaves are slot pools ([n_layers, n_slots, ...], one
        fixed slot per admitted sequence).
        Dense/MoE state is pure kv, pure SSM is pure register, hybrid
        mixes both kinds.
        """
        cfg = self.cfg

        def stack(one, n):
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n, *a.shape)), one)

        def ssm_slots():
            return stack(S.init_ssm_cache(
                n_slots, cfg.d_model, expand=cfg.ssm_expand,
                head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
                conv_width=cfg.ssm_conv_width, dtype=dtype), cfg.n_layers)

        def pages():
            return zero_pages((n_pages, page_size, cfg.n_kv_heads,
                               cfg.head_dim), dtype)

        if cfg.family == "ssm":
            return {"kv": {}, "register": ssm_slots()}
        if cfg.family == "hybrid":
            n_groups, _, _ = self._hybrid_groups()
            return {"kv": {"shared": stack(pages(), n_groups)},
                    "register": {"ssm": ssm_slots()}}
        return {"kv": stack(pages(), cfg.n_layers), "register": {}}

    def prefill(self, params: Params, batch: Params, caches: Params):
        """Process the prompt, fill caches, return last-position logits."""
        x = self._embed_inputs(params, batch)
        x, new_caches = self._run_layers(params, x, caches=caches,
                                         cache_index=jnp.asarray(0, jnp.int32))
        x = L.apply_norm(x[:, -1:], params["final_norm"], self.cfg.norm)
        logits = x @ params["lm_head"].astype(self.cdt)
        return logits[:, 0], new_caches

    def forward_chunk(self, params: Params, tokens: jnp.ndarray,
                      caches: Params, index: jnp.ndarray,
                      block_table: jnp.ndarray | None = None,
                      seq_lengths: jnp.ndarray | None = None,
                      register_index: jnp.ndarray | None = None):
        """Token chunk [B, S] at fill position `index` → per-position
        logits [B, S, V] + updated caches.

        The serving-engine entry point: S == 1 with a vector index is a
        per-slot continuous-batching decode step; S > 1 with a scalar
        index is one chunk of an incremental (chunked) prefill, causal
        within the chunk and attending to everything already cached. With
        `block_table` [B, P], kv leaves of `caches` are the engine's page
        pool (`init_paged_state`) and attention runs
        block-table-native — new rows are written straight into their
        pages and the paged-attention kernel walks the table;
        `seq_lengths` [B] (the true per-sequence context lengths, 0 for
        padded batch rows) feed the kernel's ragged early-exit. With
        `register_index` [B], SSM leaves of `caches` are register slot
        pools ([n_layers, n_slots, ...]) gathered/scattered by slot, and
        `seq_lengths` additionally bound each prefill row's live tokens so
        right-padded chunk tails stay out of the carried state.
        """
        x = jnp.take(params["embed"], tokens, axis=0).astype(self.cdt)
        x = shard_act(x, ("batch", "seq", "embed"))
        valid_len = None
        if register_index is not None and seq_lengths is not None \
                and tokens.shape[1] > 1:
            # prefill chunk: index is the scalar fill position, so the
            # row's live tokens in THIS chunk end at seq_lengths - index
            valid_len = seq_lengths - jnp.asarray(index, jnp.int32)
        x, new_caches = self._run_layers(params, x, caches=caches,
                                         cache_index=index,
                                         block_table=block_table,
                                         seq_lengths=seq_lengths,
                                         register_index=register_index,
                                         valid_len=valid_len)
        x = L.apply_norm(x, params["final_norm"], self.cfg.norm)
        logits = x @ params["lm_head"].astype(self.cdt)
        return logits, new_caches

    def decode_step(self, params: Params, tokens: jnp.ndarray,
                    caches: Params, index: jnp.ndarray):
        """One decode step. tokens: [B, 1]; index: scalar int32 fill pos."""
        logits, new_caches = self.forward_chunk(params, tokens, caches, index)
        return logits[:, 0], new_caches


def build_model(cfg: ArchConfig, **kw) -> Model:
    return Model(cfg, **kw)
