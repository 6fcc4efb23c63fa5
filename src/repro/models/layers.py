"""Shared neural-net layers: norms, RoPE, GQA attention (+KV cache, chunked
flash-style long-context path), SwiGLU/GELU feed-forward.

Everything is a pure function over explicit param dicts (stacked-over-layers
leaves scan cleanly, and the PTQ pipeline can walk/merge weights directly).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.context import mesh_axis_size, shard_act
from repro.kernels import ops as kops
from repro.kernels.paged_attention import pack_pages, paged_write_rows

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32)).astype(x.dtype)


def layernorm(x: jnp.ndarray, gamma: jnp.ndarray, beta: jnp.ndarray,
              eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * gamma.astype(jnp.float32)
            + beta.astype(jnp.float32)).astype(x.dtype)


def apply_norm(x, p: Params, kind: str):
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """RoPE inverse frequencies — the model-side twin of
    `kernels.paged_attention.rope_frequencies`.

    The two CANNOT be one function: the kernel needs a host-side numpy
    literal (a trace-invariant constant, or its operand and the
    reference's embedded constant would round `pow` differently and break
    the kernel-vs-reference bit-for-bit contract), while the model's
    traced computation constant-folds through XLA, whose `pow` rounds up
    to 2 ulp away from numpy's. Swapping the model onto the numpy literal
    shifts every rotation by those ulps — enough to flip activation-quant
    rounding ties downstream. `tests/test_kernels.py::
    test_rope_frequency_literals_agree` pins the twins together (≤ 2 ulp
    elementwise over the config sweep) so they cannot silently drift.
    """
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: [B, S, H, Dh]; positions: [B, S] (absolute)."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta)                      # [Dh/2]
    ang = positions[..., None].astype(jnp.float32) * freqs   # [B, S, Dh/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    causal: bool = True
    rope_theta: float = 10_000.0
    qkv_bias: bool = False
    chunk_q: int = 2048    # flash-style chunking thresholds
    chunk_kv: int = 2048


def init_attention(key, d_model: int, spec: AttnSpec, dtype) -> Params:
    ks = jax.random.split(key, 4)
    h, kv, dh = spec.n_heads, spec.n_kv_heads, spec.head_dim
    sc = 1.0 / math.sqrt(d_model)
    p = {
        "wq": (jax.random.normal(ks[0], (d_model, h * dh)) * sc).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d_model, kv * dh)) * sc).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d_model, kv * dh)) * sc).astype(dtype),
        "wo": (jax.random.normal(ks[3], (h * dh, d_model))
               * (1.0 / math.sqrt(h * dh))).astype(dtype),
    }
    if spec.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), dtype)
        p["bk"] = jnp.zeros((kv * dh,), dtype)
        p["bv"] = jnp.zeros((kv * dh,), dtype)
    return p


def _dense_attention(q, k, v, *, causal, q_offset=0):
    """Reference attention: q [B,Sq,H,Dh], k/v [B,Sk,KH,Dh] (H = KH·G)."""
    b, sq, h, dh = q.shape
    sk, kh = k.shape[1], k.shape[2]
    g = h // kh
    qg = q.reshape(b, sq, kh, g, dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(dh)
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(sk)
        mask = kpos[None, :] <= qpos[:, None]
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, dh).astype(q.dtype)


def _chunked_attention(q, k, v, *, causal, chunk_q, chunk_kv, q_offset=0):
    """Flash-style online-softmax attention scanning KV chunks.

    Compiled memory is O(chunk_q · chunk_kv) per head instead of O(S²) —
    required for the 32k-prefill and 500k cells to fit HBM.
    """
    b, sq, h, dh = q.shape
    sk, kh = k.shape[1], k.shape[2]
    g = h // kh
    nq = -(-sq // chunk_q)
    pad_q = nq * chunk_q - sq
    nk = -(-sk // chunk_kv)
    pad_k = nk * chunk_kv - sk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v

    qp = qp.reshape(b, nq, chunk_q, kh, g, dh).astype(jnp.float32)
    kp = kp.reshape(b, nk, chunk_kv, kh, dh).astype(jnp.float32)
    vp = vp.reshape(b, nk, chunk_kv, kh, dh).astype(jnp.float32)
    scale = 1.0 / math.sqrt(dh)

    def per_qchunk(qi, qc):
        # qc: [b, chunk_q, kh, g, dh]
        def kv_step(carry, inputs):
            m, l, acc = carry
            ki, kc, vc = inputs
            logits = jnp.einsum("bqkgd,bskd->bkgqs", qc, kc) * scale
            qpos = qi * chunk_q + jnp.arange(chunk_q) + q_offset
            kpos = ki * chunk_kv + jnp.arange(chunk_kv)
            valid = kpos[None, :] < sk
            if causal:
                valid = valid & (kpos[None, :] <= qpos[:, None])
            logits = jnp.where(valid[None, None, None], logits, -1e30)
            m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vc)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kh, g, chunk_q), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, kh, g, chunk_q), jnp.float32)
        a0 = jnp.zeros((b, kh, g, chunk_q, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kp, 1, 0), jnp.moveaxis(vp, 1, 0)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.einsum("bkgqd->bqkgd", out)

    outs = jax.lax.map(lambda args: per_qchunk(*args),
                       (jnp.arange(nq), jnp.moveaxis(qp, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, nq * chunk_q, h, dh)
    if pad_q:
        out = out[:, :sq]
    return out.astype(q.dtype)


def attention(x: jnp.ndarray, p: Params, spec: AttnSpec, *,
              positions: jnp.ndarray | None = None,
              cache: Params | None = None,
              cache_index: jnp.ndarray | None = None,
              block_table: jnp.ndarray | None = None,
              seq_lengths: jnp.ndarray | None = None,
              act_in=None):
    """GQA attention. Returns (out, new_cache).

    cache = {"k": [B, S_max, KH, Dh], "v": ...} for decode; `cache_index`
    is the current fill position (scalar int32, or [B] per-slot vector).
    With `block_table` [B, P] the cache is instead one layer's page pool
    ({"kv_rows": [n_pages, T·KH, 2·Dh]}, the page format of
    `kernels.paged_attention`) and attention is block-table-native:
    the new rows are scattered straight into their pages and the kernel
    walks the table (`kernels.ops.paged_attention`), no gathered slab.
    `seq_lengths` [B] (paged path only) are the true per-sequence context
    lengths the scheduler dispatches — the kernel's ragged early-exit
    skips every page column past ceil(len/page_size); without them the
    kernel derives the bound from the query positions. `act_in(x, tag)`
    is the PTQ hook applied to every projection input (quantize or
    capture).
    """
    b, s, d = x.shape
    h, kv, dh = spec.n_heads, spec.n_kv_heads, spec.head_dim
    if act_in is not None:
        x = act_in(x, "qkv")
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if spec.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, s, kv, dh)
    v = v.reshape(b, s, kv, dh)
    q = shard_act(q, ("batch", "seq", "heads", "head_dim"))
    k = shard_act(k, ("batch", "seq", "kv_heads", "head_dim"))

    # cache_index may be a scalar (lockstep batch) or [B] (per-slot fill
    # positions, as used by the continuous-batching scheduler).
    per_slot = cache_index is not None and jnp.ndim(cache_index) == 1

    if positions is None:
        if cache_index is None:
            base = jnp.zeros((b, 1), jnp.int32)
        elif per_slot:
            base = cache_index[:, None]
        else:
            base = jnp.broadcast_to(cache_index, (b,))[:, None]
        positions = jnp.arange(s)[None, :] + base
    q = apply_rope(q, positions, spec.rope_theta)
    k = apply_rope(k, positions, spec.rope_theta)

    new_cache = None
    if cache is not None and block_table is not None:
        # block-table-native path: write the new rows straight into their
        # pages, then attend by walking the table — no gathered slab. K is
        # stored post-RoPE, so the kernel applies no rotation.
        new_cache = paged_write_rows(
            cache, pack_pages(k[:, :, None], v[:, :, None]), block_table,
            positions)
        out = kops.paged_attention(
            q, jax.tree.map(lambda a: a[None], new_cache), block_table,
            positions, seq_lengths, kv_heads=kv).astype(x.dtype)
    elif cache is not None:
        if per_slot:
            if s != 1:
                raise ValueError("per-slot cache_index requires q_len == 1")
            rows = jnp.arange(b)
            ck = cache["k"].at[rows, cache_index].set(
                k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[rows, cache_index].set(
                v[:, 0].astype(cache["v"].dtype))
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, cache_index, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, cache_index, 0, 0))
        new_cache = {"k": ck, "v": cv}
        k_all, v_all = ck, cv
        s_k = ck.shape[1]
        if s == 1:
            # decode: mask positions beyond the (per-row) fill point
            msize = mesh_axis_size("model")
            seq_sharded_cache = (msize > 1 and kv % msize != 0
                                 and s_k % msize == 0)
            cache_axes = ("batch", "kv_cache_seq", None, None) \
                if seq_sharded_cache else \
                ("batch", None, "kv_heads", None)
            k_all = shard_act(k_all, cache_axes)
            v_all = shard_act(v_all, cache_axes)
            kpos = jnp.arange(s_k)
            if per_slot:
                valid = kpos[None, :] <= cache_index[:, None]   # [B, S_k]
            else:
                valid = jnp.broadcast_to(kpos <= cache_index, (b, s_k))
            g = h // kv
            qg = q.reshape(b, 1, kv, g, dh)
            logits = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                                k_all.astype(jnp.float32)) / math.sqrt(dh)
            logits = jnp.where(valid[:, None, None, None, :], logits, -1e30)
            if seq_sharded_cache:
                # keep the kv_len axis sharded through the softmax so SPMD
                # emits partial-softmax all-reduces (flash-decoding) instead
                # of gathering the whole cache
                logits = shard_act(
                    logits, ("batch", None, None, None, "kv_cache_seq"))
            probs = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bkgqs,bskd->bqkgd", probs,
                             v_all.astype(jnp.float32))
            out = out.reshape(b, 1, h, dh).astype(x.dtype)
        elif s <= spec.chunk_q and s_k <= spec.chunk_kv:
            # short chunked-prefill block (the serving engine's common
            # case): the flash path would pad q/kv to the 2048-wide chunk
            # tiles, turning a 4-token chunk into a 2048² attention — the
            # dense path with a query offset is exact and ~chunk²/s·s_k
            # cheaper. Stale cache rows beyond the fill point sit at
            # positions > every query position, so the causal mask hides
            # them just as the flash path's validity mask does.
            out = _dense_attention(q, k_all, v_all, causal=spec.causal,
                                   q_offset=cache_index)
        else:
            out = _chunked_attention(q, k_all, v_all, causal=spec.causal,
                                     chunk_q=spec.chunk_q,
                                     chunk_kv=spec.chunk_kv,
                                     q_offset=cache_index)
    else:
        if s > spec.chunk_q:
            out = _chunked_attention(q, k, v, causal=spec.causal,
                                     chunk_q=spec.chunk_q,
                                     chunk_kv=spec.chunk_kv)
        else:
            out = _dense_attention(q, k, v, causal=spec.causal)

    out = out.reshape(b, s, h * dh)
    if act_in is not None:
        out = act_in(out, "wo")
    out = out @ p["wo"]
    return shard_act(out, ("batch", "seq", "embed")), new_cache


def init_attention_cache(batch: int, max_len: int, spec: AttnSpec,
                         dtype=jnp.bfloat16) -> Params:
    kv, dh = spec.n_kv_heads, spec.head_dim
    return {"k": jnp.zeros((batch, max_len, kv, dh), dtype),
            "v": jnp.zeros((batch, max_len, kv, dh), dtype)}


# ---------------------------------------------------------------------------
# Feed-forward
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, act: str, dtype) -> Params:
    ks = jax.random.split(key, 3)
    sc_in = 1.0 / math.sqrt(d_model)
    sc_out = 1.0 / math.sqrt(d_ff)
    p = {
        "w_up": (jax.random.normal(ks[1], (d_model, d_ff)) * sc_in).astype(dtype),
        "w_down": (jax.random.normal(ks[2], (d_ff, d_model)) * sc_out).astype(dtype),
    }
    if act == "silu":  # SwiGLU
        p["w_gate"] = (jax.random.normal(ks[0], (d_model, d_ff)) * sc_in).astype(dtype)
    return p


def mlp(x: jnp.ndarray, p: Params, act: str,
        down_proj_fn=None, act_in=None) -> jnp.ndarray:
    """SwiGLU (act="silu") or plain GELU MLP. `down_proj_fn(h, w_down)`
    overrides the final projection — the PTQ hook where the online block
    rotation + quantized GEMM is injected (Figure 7, R̃₃)."""
    if act_in is not None:
        x = act_in(x, "ffn")
    if act == "silu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = jax.nn.gelu(x @ p["w_up"])
    h = shard_act(h, ("batch", "seq", "mlp"))
    if down_proj_fn is not None:
        out = down_proj_fn(h, p["w_down"])
    else:
        out = h @ p["w_down"]
    return shard_act(out, ("batch", "seq", "embed"))


def init_norm(d_model: int, kind: str, dtype) -> Params:
    p = {"scale": jnp.ones((d_model,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d_model,), dtype)
    return p
