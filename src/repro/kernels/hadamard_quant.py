"""Pallas TPU kernel: fused block-Hadamard rotation + dynamic per-token
asymmetric integer quantization (the R̃₃ → Q_A path of Figure 7).

Fusing saves one full HBM round-trip of the rotated activation: unfused, the
rotation writes [M, D] bf16 to HBM and the quantizer reads it back; fused,
the rotated tile never leaves VMEM and only int codes + 2 floats per token
are written (a ~4× reduction in bytes moved for bf16 inputs at 4 bits).

Per-token quantization needs full-row min/max, so the grid tiles rows only
and each instance holds one [TM, D] strip (D ≤ 19200 f32 ≈ 75 KB/row — a
TM=64 strip is < 5 MiB of VMEM). The rotation applies H per column slab via
a dot against the block-diagonal operand, reusing `rotation_operand`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .block_hadamard import rotation_operand, _column_tile

__all__ = ["hadamard_quant"]


def _kernel(x_ref, h_ref, codes_ref, scale_ref, zero_ref, *, bits, n_slabs):
    x = x_ref[...].astype(jnp.float32)          # [TM, D]
    h = h_ref[...]                               # [TD, TD] block-diag operand
    tm, d = x.shape
    td = h.shape[0]
    # Rotate slab-by-slab (static unroll keeps everything MXU matmuls).
    xs = x.reshape(tm, n_slabs, td)
    # f32 at full precision (the chip's default may take one bf16 pass),
    # the same arithmetic as the reference's `ops._rotate_mm`
    y = jax.lax.dot_general(
        xs, h, dimension_numbers=(((2,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                            # [TM, n_slabs, TD]
    y = y.reshape(tm, d)
    mn = jnp.min(y, axis=-1, keepdims=True)
    mx = jnp.max(y, axis=-1, keepdims=True)
    s = jnp.maximum((mx - mn) / (2 ** bits - 1), jnp.finfo(jnp.float32).tiny)
    z = jnp.round(mn / s)
    codes = jnp.clip(jnp.round(y / s) - z, 0, 2 ** bits - 1)
    codes_ref[...] = codes.astype(jnp.int8)
    scale_ref[...] = s
    zero_ref[...] = z


@functools.partial(jax.jit, static_argnames=("b", "bits", "row_tile", "interpret"))
def hadamard_quant(x: jnp.ndarray, b: int, *, bits: int = 4,
                   row_tile: int = 64, interpret: bool = True):
    """Rotate by (I ⊗ H_b) and quantize per token.

    Returns (codes int8 [..., D] in [0, 2^bits−1], scale f32 [..., 1],
    zero f32 [..., 1]) with dequant x̂ = scale·(codes + zero).
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    if d % b:
        raise ValueError(f"feature dim {d} not divisible by block size {b}")
    m = int(np.prod(orig_shape[:-1])) if len(orig_shape) > 1 else 1
    x2 = x.reshape(m, d)

    td = _column_tile(b, d)
    n_slabs = d // td
    tm = min(row_tile, max(8, m))
    pad_m = (-m) % tm
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)), constant_values=1.0)
    mp = x2.shape[0]

    h_op = rotation_operand(b, td, dtype=jnp.float32)

    kern = functools.partial(_kernel, bits=bits, n_slabs=n_slabs)
    codes, scale, zero = pl.pallas_call(
        kern,
        out_shape=(
            jax.ShapeDtypeStruct((mp, d), jnp.int8),
            jax.ShapeDtypeStruct((mp, 1), jnp.float32),
            jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        ),
        grid=(mp // tm,),
        in_specs=[
            pl.BlockSpec((tm, d), lambda i: (i, 0)),
            pl.BlockSpec((td, td), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((tm, d), lambda i: (i, 0)),
            pl.BlockSpec((tm, 1), lambda i: (i, 0)),
            pl.BlockSpec((tm, 1), lambda i: (i, 0)),
        ),
        interpret=interpret,
    )(x2, h_op)

    if pad_m:
        codes, scale, zero = codes[:m], scale[:m], zero[:m]
    lead = orig_shape[:-1]
    return (codes.reshape(*lead, d), scale.reshape(*lead, 1),
            zero.reshape(*lead, 1))
