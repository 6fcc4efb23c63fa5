"""Pallas TPU kernel: W4A4 integer GEMM with in-kernel nibble unpack.

out = x̂ @ ŵ where x̂ = s_a·(q_a + z_a) (per-token asymmetric int4 codes from
`hadamard_quant`) and ŵ = s_w·q_w (symmetric int4, packed two rows per byte,
per-output-channel scale).

    out = s_a · s_w · (q_a @ q_w  +  z_a · colsum(q_w))

The integer product q_a @ q_w accumulates in int32 on the MXU (int8×int8
dot), the correction term uses precomputed int32 column sums, and the float
epilogue applies both scales — i.e. true integer arithmetic, not fake-quant.

Grid (M/TM, N/TN, K/TK) with a VMEM accumulator scratch; K is the innermost
(fastest) grid axis so the accumulator tile stays resident across the K walk.
Weights stay packed in HBM (half the bytes of int8) and are unpacked in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["int4_matmul"]

_INT8_ROWS = 32   # sublane tile of an int8 block on the TPU


def _divisor_tile(dim: int, pref: int, *, multiple: int = 1) -> int:
    """Largest divisor of `dim` that is ≤ `pref` and a multiple of
    `multiple` — serving dims (head counts × head_dim, FFN widths) are not
    always multiples of the preferred MXU tile."""
    for t in range(min(pref, dim), multiple - 1, -1):
        if dim % t == 0 and t % multiple == 0:
            return t
    raise ValueError(f"no tile ≤ {pref} (multiple of {multiple}) "
                     f"divides {dim}")


def _tile_or_pad(dim: int, pref: int, *, multiple: int = 1) -> tuple[int, int]:
    """(tile, padded_dim) for an awkward dimension.

    Prefers an exact divisor tile (padded_dim == dim). A dimension whose
    only divisors ≤ `pref` are tiny — e.g. 2·prime projection widths,
    where the best "tile" is 1 or 2 — would either hard-fail or crawl, so
    it falls back to the preferred tile with zero padding: padded rows of
    the activation/weight operands contribute exactly zero to every real
    output element (0-codes × 0-weights, and zero weight columns add
    nothing to the colsum correction), and the pad is sliced off the
    result.
    """
    try:
        t = _divisor_tile(dim, pref, multiple=multiple)
        if t >= min(pref, 8, dim):
            return t, dim
    except ValueError:
        pass
    t = max(multiple, min(pref, -(-dim // multiple) * multiple))
    t -= t % multiple
    return t, dim + (-dim) % t


def _kernel(qe_ref, qo_ref, wp_ref, sa_ref, za_ref, sw_ref, colsum_ref,
            o_ref, acc_ref, *, n_k):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Packed byte j of a column holds weight rows 2j (low nibble) and 2j+1
    # (high nibble). Rather than interleave them back into [TK, TN], the
    # product splits over the two nibble planes against the even/odd
    # activation columns: q_a @ q_w = q_a[:, 0::2] @ lo + q_a[:, 1::2] @ hi.
    # The nibbles are sign-extended with int32 shifts (Mosaic legalizes no
    # 8-bit shift) and narrowed back to int8 for the MXU's int8 path.
    w = wp_ref[...].astype(jnp.int32)             # [TK/2, TN]
    lo = ((w << 28) >> 28).astype(jnp.int8)
    hi = ((w << 24) >> 28).astype(jnp.int8)
    dims = (((1,), (0,)), ((), ()))
    acc_ref[...] += (
        jax.lax.dot_general(qe_ref[...], lo, dims,
                            preferred_element_type=jnp.int32)
        + jax.lax.dot_general(qo_ref[...], hi, dims,
                              preferred_element_type=jnp.int32))

    @pl.when(k_idx == n_k - 1)
    def _epilogue():
        sa = sa_ref[...]                           # [TM, 1]
        za = za_ref[...]                           # [TM, 1]
        sw = sw_ref[...]                           # [1, TN]
        cs = colsum_ref[...].astype(jnp.float32)   # [1, TN]
        acc = acc_ref[...].astype(jnp.float32)
        o_ref[...] = ((sa * sw) * (acc + za * cs)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "tm", "tn", "tk",
                                             "interpret"))
def int4_matmul(act_codes: jnp.ndarray, act_scale: jnp.ndarray,
                act_zero: jnp.ndarray, w_packed: jnp.ndarray,
                w_scale: jnp.ndarray, *, out_dtype=jnp.float32,
                tm: int = 128, tn: int = 128, tk: int = 256,
                interpret: bool = True) -> jnp.ndarray:
    """act_codes [M, K] int8 (asym, [0, 15]); act_scale/zero [M, 1] f32;
    w_packed [K/2, N] uint8; w_scale [N] or [1, N] f32 → [M, N] out_dtype."""
    m, k = act_codes.shape
    k2, n = w_packed.shape
    if 2 * k2 != k:
        raise ValueError(f"packed K mismatch: acts K={k}, weights K={2 * k2}")
    w_scale = w_scale.reshape(1, n).astype(jnp.float32)

    # int8 blocks tile natively at 32 rows; decode's few rows pad up to it
    tm = min(tm, -(-m // _INT8_ROWS) * _INT8_ROWS)
    tn, np_ = _tile_or_pad(n, tn)
    tk, kp = _tile_or_pad(k, tk, multiple=2)
    if np_ > n:
        # zero weight columns (and unit scales, so the 0·0 epilogue stays
        # finite); their outputs are sliced off below
        w_packed = jnp.pad(w_packed, ((0, 0), (0, np_ - n)))
        w_scale = jnp.pad(w_scale, ((0, 0), (0, np_ - n)),
                          constant_values=1)
    if kp > k:
        # zero activation codes against zero weight rows: 0·0 adds nothing
        # to the integer product, and zero rows leave the colsum
        # correction unchanged
        act_codes = jnp.pad(act_codes, ((0, 0), (0, kp - k)))
        w_packed = jnp.pad(w_packed, ((0, (kp - k) // 2), (0, 0)))

    # Precompute per-channel weight-code column sums (int32) for the
    # asymmetric-activation correction term (after padding — zero rows
    # are exact no-ops).
    lo = (w_packed & 0xF).astype(jnp.int32)
    hi = ((w_packed >> 4) & 0xF).astype(jnp.int32)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    colsum = (jnp.sum(lo, axis=0) + jnp.sum(hi, axis=0)).reshape(1, np_)

    pad_m = (-m) % tm
    if pad_m:
        act_codes = jnp.pad(act_codes, ((0, pad_m), (0, 0)))
        act_scale = jnp.pad(act_scale, ((0, pad_m), (0, 0)), constant_values=1)
        act_zero = jnp.pad(act_zero, ((0, pad_m), (0, 0)))
    mp = act_codes.shape[0]
    n_k = kp // tk
    # even/odd activation columns meet the low/high nibble planes
    act_even, act_odd = act_codes[:, 0::2], act_codes[:, 1::2]
    w_packed = jax.lax.bitcast_convert_type(w_packed, jnp.int8)

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        grid=(mp // tm, np_ // tn, n_k),
        in_specs=[
            pl.BlockSpec((tm, tk // 2), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((tm, tk // 2), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((tk // 2, tn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((tm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((tm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, tn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, tn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.int32)],
        interpret=interpret,
    )(act_even, act_odd, w_packed, act_scale, act_zero, w_scale, colsum)

    if pad_m or np_ > n:
        out = out[:m, :n]
    return out
