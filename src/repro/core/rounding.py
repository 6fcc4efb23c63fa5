"""Stage-2 rounding algorithms: RTN, GPTQ (OPTQ), Qronos.

Conventions
-----------
Layers compute ``y = x @ W`` with ``W: [d_in, d_out]``. The Hessian is
``H = XᵀX : [d_in, d_in]`` accumulated over calibration tokens. GPTQ/Qronos
quantize the d_in rows of W sequentially, diffusing the rounding error into
not-yet-quantized rows via the upper Cholesky factor of H⁻¹ (exact OPTQ
recursion). Per Appendix B we

  * damp GPTQ with λ = damp_frac · mean(diag H) (1%),
  * damp Qronos with λ = 1e-3 · σ₁(H) (largest singular value),
  * quantize rows in descending order of diag(H) ("act order"),
  * compute weight scales from the original full-precision W (per output
    channel for INT/FP4; per 32-row group for MXFP4) before the loop.

Qronos ("correct the past by shaping the future", Zhang et al. 2026): when
the layer inputs themselves are quantized (X̃ ≠ X), first re-fit the weights
against the quantized inputs — W ← (X̃ᵀX̃ + λI)⁻¹ X̃ᵀX · W — which corrects the
error already committed upstream; then run the GPTQ recursion with H = X̃ᵀX̃.
With X̃ = X the re-fit is the identity and Qronos reduces to GPTQ exactly.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from .quantizers import (QuantSpec, fp4_quantize, fp4_weight_scales_mse,
                         int_quantize, int_weight_scales_mse)

__all__ = [
    "hessian_from_activations",
    "cross_from_activations",
    "row_scales",
    "rtn",
    "gptq",
    "qronos",
]


def hessian_from_activations(x: jnp.ndarray) -> jnp.ndarray:
    """H = XᵀX in float32; x is [tokens, d_in] (flatten batch/seq first)."""
    x = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    return x.T @ x


def cross_from_activations(x_q: jnp.ndarray, x_fp: jnp.ndarray) -> jnp.ndarray:
    """C = X̃ᵀX in float32 for the Qronos re-fit."""
    x_q = x_q.reshape(-1, x_q.shape[-1]).astype(jnp.float32)
    x_fp = x_fp.reshape(-1, x_fp.shape[-1]).astype(jnp.float32)
    return x_q.T @ x_fp


def row_scales(w: jnp.ndarray, spec: QuantSpec) -> jnp.ndarray:
    """Scale for each row of W (broadcastable to W): INT/FP4 → per output
    channel [1, d_out]; MXFP4 → per (32-row group × output channel) [d_in, d_out]
    with power-of-2 shared scales (static-group approximation: scales fixed
    from the original W before error diffusion)."""
    if spec.fmt in ("int4", "int8"):
        bits = 4 if spec.fmt == "int4" else 8
        return int_weight_scales_mse(w, bits, axis=0, n_grid=spec.scale_grid)
    if spec.fmt == "fp4":
        return fp4_weight_scales_mse(w, axis=0, n_grid=spec.scale_grid)
    if spec.fmt == "mxfp4":
        d_in, d_out = w.shape
        g = spec.mx_group
        if d_in % g:
            raise ValueError(f"d_in={d_in} not divisible by MX group {g}")
        wg = w.reshape(d_in // g, g, d_out)
        absmax = jnp.maximum(jnp.max(jnp.abs(wg), axis=1, keepdims=True),
                             jnp.finfo(jnp.float32).tiny)
        e = jnp.floor(jnp.log2(absmax)) - 2.0  # fp4 emax = 2
        s = jnp.broadcast_to(2.0 ** e, wg.shape).reshape(d_in, d_out)
        return s
    raise ValueError(spec.fmt)


def _quantize_rows(w: jnp.ndarray, s: jnp.ndarray, spec: QuantSpec) -> jnp.ndarray:
    """Fake-quantize rows of w given (broadcastable) scales s."""
    if spec.fmt in ("int4", "int8"):
        bits = 4 if spec.fmt == "int4" else 8
        return int_quantize(w, s, 0.0, bits, signed=True)
    if spec.fmt in ("fp4", "mxfp4"):
        return fp4_quantize(w, s)
    raise ValueError(spec.fmt)


@functools.partial(jax.jit, static_argnames=("spec",))
def rtn(w: jnp.ndarray, spec: QuantSpec) -> jnp.ndarray:
    """Round-to-nearest with the Appendix-B scale policy. jit'd so the
    scale search's per-candidate errors fuse into its reduction: run op
    by op they are an [n_grid, d_in, d_out] f32 array (5 GiB for one
    2048 × 8192 projection)."""
    if not spec.enabled:
        return w
    s = row_scales(w.astype(jnp.float32), spec)
    return _quantize_rows(w.astype(jnp.float32), s, spec).astype(w.dtype)


def _upper_cholesky_inv(h: jnp.ndarray) -> jnp.ndarray:
    """Upper Cholesky factor U of H⁻¹ (H⁻¹ = UᵀU), via H = LLᵀ."""
    hinv = jnp.linalg.inv(h)
    # Symmetrize for numerical safety before factorization.
    hinv = 0.5 * (hinv + hinv.T)
    L = jnp.linalg.cholesky(hinv)
    return L.T


@functools.partial(jax.jit, static_argnames=("spec", "act_order"))
def gptq(w: jnp.ndarray, h: jnp.ndarray, spec: QuantSpec,
         *, damp_frac: float = 0.01, act_order: bool = True,
         damp_sigma: float | None = None) -> jnp.ndarray:
    """GPTQ/OPTQ error-correcting rounding.

    w: [d_in, d_out], h: [d_in, d_in] = XᵀX. Returns fake-quantized W whose
    rows were rounded sequentially with error diffusion. `damp_sigma`
    overrides the damping to λ = damp_sigma·σ₁(H) (used by Qronos).
    """
    if not spec.enabled:
        return w
    w = w.astype(jnp.float32)
    h = h.astype(jnp.float32)
    d = w.shape[0]

    # Dead input channels: H_ii == 0 ⇒ pin to 1 (their weights don't matter).
    diag = jnp.diagonal(h)
    dead = diag <= 0.0
    h = h + jnp.diag(jnp.where(dead, 1.0, 0.0))

    if damp_sigma is not None:
        lam = damp_sigma * _sigma_max(h)
    else:
        lam = damp_frac * jnp.mean(jnp.diagonal(h))
    h = h + lam * jnp.eye(d, dtype=jnp.float32)

    if act_order:
        order = jnp.argsort(-jnp.diagonal(h), stable=True)
        w = w[order]
        h = h[order][:, order]

    scales = row_scales(w, spec)
    scales = jnp.broadcast_to(scales, w.shape)
    u = _upper_cholesky_inv(h)

    idx = jnp.arange(d)

    def step(carry, i):
        wc = carry
        wi = wc[i]
        qi = _quantize_rows(wi, scales[i], spec)
        err = (wi - qi) / u[i, i]
        mask = (idx > i).astype(jnp.float32)
        wc = wc - (mask * u[i])[:, None] * err[None, :]
        wc = wc.at[i].set(qi)
        return wc, None

    w, _ = jax.lax.scan(step, w, jnp.arange(d))

    if act_order:
        inv = jnp.argsort(order)
        w = w[inv]
    return w


def _sigma_max(h: jnp.ndarray, iters: int = 32) -> jnp.ndarray:
    """Largest singular value of symmetric PSD h via power iteration."""
    v = jnp.ones((h.shape[0],), jnp.float32) / jnp.sqrt(h.shape[0])

    def body(_, v):
        v = h @ v
        return v / jnp.maximum(jnp.linalg.norm(v), 1e-30)

    v = jax.lax.fori_loop(0, iters, body, v)
    return jnp.linalg.norm(h @ v)


@functools.partial(jax.jit, static_argnames=("spec", "act_order"))
def qronos(w: jnp.ndarray, h_q: jnp.ndarray, spec: QuantSpec,
           *, c_qx: jnp.ndarray | None = None, alpha: float = 1e-3,
           act_order: bool = True) -> jnp.ndarray:
    """Qronos rounding: past-correcting re-fit + GPTQ recursion.

    h_q = X̃ᵀX̃ (quantized inputs), c_qx = X̃ᵀX (quantized × full-precision).
    When c_qx is None the re-fit is skipped (X̃ = X) and this is GPTQ with
    Qronos' σ₁-based damping.
    """
    if not spec.enabled:
        return w
    w = w.astype(jnp.float32)
    h_q = h_q.astype(jnp.float32)
    if c_qx is not None:
        lam = alpha * _sigma_max(h_q)
        a = h_q + lam * jnp.eye(h_q.shape[0], dtype=jnp.float32)
        # Shape the future: remaining (all) weights re-fit against X̃.
        w = jax.scipy.linalg.solve(a, c_qx.astype(jnp.float32) @ w,
                                   assume_a="pos")
    return gptq(w, h_q, spec, act_order=act_order, damp_sigma=alpha)
