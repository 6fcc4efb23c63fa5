"""The arithmetic of the plain float32 references, shared by the model
modules (`perfbench/models/`). Imports nothing of the program and takes
nothing it made: a module rebuilds its weights from the seed, one layer at
a time, and writes its layers from these pieces.

Q4 is per-token asymmetric int4: s = (max-min)/15, z = round(min/s),
code = clip(round(t/s) - z, 0, 15), t' = s (code + z); W = codes * scale
per output channel. KV rows are quantized the same way at `kv_bits` per
(position, head). Every product runs in float32 at HIGHEST precision.

`mode="fp8"` is the control: every tensor the configuration keeps in
bfloat16 rounded through float8_e4m3fn, the next precision below
bfloat16 (`round_to`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def round_to(t, mode):
    if mode == "fp8":
        return t.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return t


def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _q_asym(t, bits, floor):
    levels = 2 ** bits - 1
    mn = jnp.min(t, axis=-1, keepdims=True)
    mx = jnp.max(t, axis=-1, keepdims=True)
    s = jnp.maximum((mx - mn) / levels, floor)
    z = jnp.round(mn / s)
    code = jnp.clip(jnp.round(t / s) - z, 0, levels)
    return s, z, code


def qlin(t, w):
    """Q4(t) @ (codes * scale), exact integer products in float32."""
    codes, scale = w
    s, z, code = _q_asym(t, 4, jnp.finfo(jnp.float32).tiny)
    acc = jnp.dot(code + z, codes.astype(jnp.float32), precision=HI)
    return s * acc * scale[None, :]


def kv(t, bits):
    s, z, code = _q_asym(t, bits, 1e-6)
    return s * (code + z)


def rope(t, pos, theta):
    """RoPE over the last axis, half split."""
    dh = t.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh))
    ang = pos[:, None, None].astype(jnp.float32) * jnp.asarray(freqs)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = jnp.split(t, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def sylvester(n):
    """The normalized n-point Sylvester Hadamard matrix."""
    h = np.ones((1, 1), np.float32)
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return h / math.sqrt(n)
