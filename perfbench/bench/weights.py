"""Seeded random weights, made on the device in the form they are served
in: the scheme every model module (`perfbench/models/`) builds its
projections from.

A projection is int4: its codes come from random bytes, two nibbles a
byte, each nibble a two's-complement code in [-8, 7] with -8 mapped to 0,
so the codes are symmetric on [-7, 7] like a symmetric int4 grid. Row 2k
of a [K, N] projection is the low nibble of byte row k, row 2k+1 the high
nibble. Each output channel has a float32 scale. There is no calibration
and no float master copy: the values are random, the shapes and the work
are the model's. A module rebuilds the very same numbers one layer at a
time for its reference, from the seed alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# E[q^2] of the code distribution above: (2 * (1 + 4 + ... + 49)) / 16
CODE_MEAN_SQ = 17.5


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number (the seed may exceed 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def proj_key(key, layer, i):
    """The key of projection `i` of layer `layer`."""
    return jax.random.fold_in(jax.random.fold_in(key, layer), i)


def _nibble_code(n):
    """4-bit two's complement → int8 code, with -8 mapped to 0."""
    c = jnp.where(n >= 8, n - 16, n)
    return jnp.where(c == -8, 0, c)


def proj_bytes(key, k: int, n: int):
    """Packed [K/2, N] uint8 (nibble -8 already mapped to 0), the low and
    high nibbles' codes [K/2, N], and the per-channel scale multiplier in
    [0.75, 1.25)."""
    kb, ks = jax.random.split(key)
    raw = jax.random.bits(kb, (k // 2, n), jnp.uint8).astype(jnp.int32)
    lo = _nibble_code(raw & 0xF)
    hi = _nibble_code(raw >> 4)
    packed = ((lo & 0xF) | ((hi & 0xF) << 4)).astype(jnp.uint8)
    mult = jax.random.uniform(ks, (n,), jnp.float32, 0.75, 1.25)
    return packed, lo, hi, mult
