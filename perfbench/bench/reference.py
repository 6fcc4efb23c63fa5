"""Plain float32 reference of the served model, and its lower-precision
control. Imports nothing of the program and takes nothing it made: the
weights are rebuilt from the seed by `weights.py`, one layer at a time.

The model, written out (per layer, x the residual stream):

    h = rmsnorm(x)                          (eps from the configuration)
    q, k, v = Q4(h) @ Wq, Q4(h) @ Wk, Q4(h) @ Wv
    k, v -> kv_bits (8) per (position, head): s = (max-min)/255,
            z = round(min/s), code = clip(round(t/s) - z, 0, 255),
            t' = s (code + z)
    q, k' rotated by RoPE (half split, theta from the configuration)
    a = softmax(q k'^T / sqrt(dh), causal) v'      (GQA: head groups)
    x = x + Q4(a) @ Wo
    h = rmsnorm(x)
    u = silu(Q4(h) @ Wg) * (Q4(h) @ Wu)
    x = x + Q4(u (I ⊗ H16/4)) @ Wd          (Sylvester H16, normalized)
    logits = rmsnorm(x) @ Whead

Q4 is per-token asymmetric int4: s = (max-min)/15, z = round(min/s),
code = clip(round(t/s) - z, 0, 15), t' = s (code + z); W = codes * scale
per output channel. Every product runs in float32 at HIGHEST precision.

`mode="fp8"` is the control: the same forward with every tensor the
configuration keeps in bfloat16 (embeddings, residual stream, norm
outputs, projection and attention outputs, logits) rounded through
float8_e4m3fn, the next precision below bfloat16.

Streams are processed in blocks of `block` rows against a KV buffer of the
cell's longest stream, so one program serves every stream length.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .weights import Dims, head_weights, layer_codes

HI = jax.lax.Precision.HIGHEST


def _round(t, mode):
    if mode == "fp8":
        return t.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return t


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _q_asym(t, bits, floor):
    levels = 2 ** bits - 1
    mn = jnp.min(t, axis=-1, keepdims=True)
    mx = jnp.max(t, axis=-1, keepdims=True)
    s = jnp.maximum((mx - mn) / levels, floor)
    z = jnp.round(mn / s)
    code = jnp.clip(jnp.round(t / s) - z, 0, levels)
    return s, z, code


def _qlin(t, w):
    """Q4(t) @ (codes * scale), exact integer products in float32."""
    codes, scale = w
    s, z, code = _q_asym(t, 4, jnp.finfo(jnp.float32).tiny)
    acc = jnp.dot(code + z, codes.astype(jnp.float32), precision=HI)
    return s * acc * scale[None, :]


def _kv(t, bits):
    s, z, code = _q_asym(t, bits, 1e-6)
    return s * (code + z)


def _rope(t, pos, theta):
    dh = t.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh))
    ang = pos[:, None, None].astype(jnp.float32) * jnp.asarray(freqs)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = jnp.split(t, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _sylvester(n):
    h = np.ones((1, 1), np.float32)
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return h / math.sqrt(n)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _layer_block(x, kbuf, vbuf, pos0, w, *, dims: Dims, mode: str):
    """One layer over rows [pos0, pos0 + B) of a stream whose earlier rows
    already sit in the layer's KV buffers [Tmax, KH, dh]."""
    r = functools.partial(_round, mode=mode)
    b = x.shape[0]
    H, KH, dh = dims.n_heads, dims.n_kv_heads, dims.head_dim
    pos = pos0 + jnp.arange(b)
    h = r(_rms(x, dims.rms_eps))
    q = r(_qlin(h, w["wq"])).reshape(b, H, dh)
    k = _kv(r(_qlin(h, w["wk"])).reshape(b, KH, dh), dims.kv_bits)
    v = _kv(r(_qlin(h, w["wv"])).reshape(b, KH, dh), dims.kv_bits)
    q = r(_rope(q, pos, dims.rope_theta))
    k = _rope(k, pos, dims.rope_theta)
    kbuf = jax.lax.dynamic_update_slice(kbuf, k, (pos0, 0, 0))
    vbuf = jax.lax.dynamic_update_slice(vbuf, v, (pos0, 0, 0))
    g = H // KH
    qg = q.reshape(b, KH, g, dh)
    logits = jnp.einsum("bkgd,tkd->kgbt", qg, kbuf, precision=HI) \
        / math.sqrt(dh)
    keep = jnp.arange(kbuf.shape[0])[None, :] <= pos[:, None]
    logits = jnp.where(keep[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    a = jnp.einsum("kgbt,tkd->bkgd", p, vbuf, precision=HI)
    a = r(a.reshape(b, H * dh))
    x = r(x + r(_qlin(a, w["wo"])))
    h = r(_rms(x, dims.rms_eps))
    u = r(jax.nn.silu(r(_qlin(h, w["w_gate"]))) * r(_qlin(h, w["w_up"])))
    bs = dims.block_size
    rot = jnp.einsum("bnc,cd->bnd", u.reshape(b, -1, bs),
                     jnp.asarray(_sylvester(bs)), precision=HI)
    x = r(x + r(_qlin(rot.reshape(b, -1), w["w_down"])))
    return x, kbuf, vbuf


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _logits(x, head, *, dims: Dims, mode: str):
    h = _round(_rms(x, dims.rms_eps), mode)
    return _round(jnp.dot(h, head.astype(jnp.float32), precision=HI), mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _embed(tokens, embed, *, dims: Dims, mode: str):
    return _round(jnp.take(embed, tokens, axis=0).astype(jnp.float32), mode)


_layer_weights = jax.jit(layer_codes, static_argnames=("dims",))


def forward(key, dims: Dims, streams: list[list[int]], rows: list[list[int]],
            *, mode: str = "f32", block: int = 256,
            max_len: int | None = None) -> list[np.ndarray]:
    """Logits [len(rows[i]), V] at the positions `rows[i]` of each token
    stream, computed layer by layer over blocks of `block` rows."""
    t_max = max_len or max(len(s) for s in streams)
    t_max = -(-t_max // block) * block
    embed, head = head_weights(key, dims)
    xs = []
    for s in streams:
        n = -(-len(s) // block) * block
        toks = jnp.asarray(list(s) + [0] * (n - len(s)), jnp.int32)
        xs.append(_embed(toks, embed, dims=dims, mode=mode))
    del embed
    zeros = jnp.zeros((t_max, dims.n_kv_heads, dims.head_dim), jnp.float32)
    for layer in range(dims.n_layers):
        w = _layer_weights(key, dims=dims, layer=layer)
        for i, x in enumerate(xs):
            kbuf, vbuf, outs = zeros, zeros, []
            for j in range(x.shape[0] // block):
                y, kbuf, vbuf = _layer_block(
                    x[j * block:(j + 1) * block], kbuf, vbuf,
                    jnp.asarray(j * block, jnp.int32), w, dims=dims,
                    mode=mode)
                outs.append(y)
            xs[i] = jnp.concatenate(outs, axis=0)
        del w
    out = []
    for x, r in zip(xs, rows):
        sel = x[jnp.asarray(r, jnp.int32)]
        out.append(np.asarray(_logits(sel, head, dims=dims, mode=mode)))
    return out
