"""Pallas TPU kernel: flash-decoding block-table-native paged causal attention.

The kernel walks each sequence's block table in place — no gathered KV
slab — on the grid

    grid = (batch, kv_head_block, q_block, kv_split, page_column)

  * **KV-head and query-block axes** — each `(head_block, q_block)` tile
    owns its own `m/l/acc` VMEM scratch (the four outer axes are marked
    `parallel` for Mosaic; the page axis stays `arbitrary` since the
    online softmax is a carried reduction). By default one tile holds
    every KV head: a page is fetched whole, and the tile's query rows
    meet the page's rows of all heads in one 2-D matmul whose
    cross-head entries are masked (see "Shared arithmetic").
  * **Split-K page partitions** — the page axis is cut into `kv_splits`
    independent partial walks. Each split emits flash-decoding partials
    `(m, l, acc)`; a second LSE-combine kernel merges them with the
    standard log-sum-exp reweighting. Decode (S == 1) gets context-length
    parallelism this way: a 32-page context becomes `kv_splits` concurrent
    8-page walks plus one tiny combine.
  * **Ragged early-exit** — per-sequence used-page counts are
    scalar-prefetched alongside the block table, and every instance
    `pl.when`-skips columns past its sequence's last live page. The pages
    walked per decode step drop from `batch · n_cols` to
    `Σ_b ceil(len_b / page_size)` — a real work reduction for ragged
    batches (exact by construction: a fully-masked page leaves `m/l/acc`
    bitwise unchanged, so skipping it is a bit-for-bit no-op).
  * **Pipelined page fetch** — K/V pages (and the scale/zero pages of the
    integer formats) ride BlockSpecs whose index map reads the
    scalar-prefetched block table, so Pallas double-buffers them: the
    copy of the next column overlaps the update of the current one. A
    column past the used count maps to the last live page again, and an
    unchanged block index issues no copy, so dead columns cost no DMA.

Three KV page formats are served by the same walk:

  * float pages (bf16/f32) holding post-RoPE K — the bf16 and fake-quant
    engine backends;
  * int8/int4 code pages with per-(position, head) asymmetric
    scale/zero pages riding along — dequantized in VMEM, and (because the
    integer cache stores K pre-RoPE) rotated in-kernel with the absolute
    position of each page row.

Every arithmetic step lives in a small jnp helper shared with
`kernels.ref.paged_attention_ref`, which replays the *identical*
split/combine reduction order (same per-split column walk, same skip
select, same LSE combine) on a gathered view — that is what keeps the
dispatch-vs-reference comparison bit-for-bit in interpret mode for every
`(q_block, kv_splits, head_block)` configuration, the contract
`hadamard_quant`/`int4_matmul` already meet.

Padding is handled by the causal mask plus the early-exit: pad block-table
entries point at the scratch page and sit past the used-page count, so they
are skipped outright; rows of the last live page beyond the fill point are
hidden by `kpos <= qpos` exactly as a sequence's own not-yet-written rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention", "paged_attention_reference", "resolve_tiling",
           "used_page_counts", "rope_frequencies"]

MASK_VALUE = -1e30
# f32 dots at full precision on the chip too (XLA's and Mosaic's default
# for an f32 matmul may drop to one bf16 pass), so the kernel and the
# XLA reference compute the same arithmetic on every backend
_F32 = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Shared arithmetic (kernel body AND the bit-for-bit jnp reference)
# ---------------------------------------------------------------------------
#
# One grid instance owns a (q_block, head_block) tile. Its queries are laid
# out as R = q_block · head_block · G rows (row r = s·hbg + j·G + g: query
# position s, KV head j of the block, group member g) and one page of the
# block's KV heads as C = page_size · head_block rows (row c = t·hb + j).
# Every step is then a plain 2-D matmul: logits [R, C] = q · kᵀ with the
# rows of other KV heads masked out, which keeps the page in the pool's
# own [T, KH, Dh] layout (no in-kernel transpose) at the price of
# head_block× redundant MXU work on the masked entries.

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """Twin of `models.layers.rope_frequencies` — duplicated on purpose,
    and pinned to it by `tests/test_kernels.py::
    test_rope_frequency_literals_agree` (≤ 2 ulp) so it cannot drift.

    Computed host-side in numpy so the kernel operand and the reference's
    traced constant embed the *identical* literal — `pow` rounds a ulp
    differently between XLA's eager dispatch and constant folding, which
    would break the kernel-vs-reference bit-for-bit contract. That same
    rounding gap is why the model keeps its own traced-jnp twin: swapping
    it onto this literal shifts every rotation by the ulp difference."""
    freqs = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                             / np.float32(head_dim)))
    return jnp.asarray(freqs, jnp.float32)


def dequant_page(codes: jnp.ndarray, scale: jnp.ndarray, zero: jnp.ndarray,
                 *, bits: int) -> jnp.ndarray:
    """Asymmetric per-(row, head) dequant of one KV page → [T·KH, Dh] f32.

    codes [T, KH, Dh] int8 (stored offset by 2^(bits-1)), scale/zero
    [T, KH, 1] — the exact arithmetic of `QuantizedDenseLM._cache_read`.
    """
    off = 2 ** (bits - 1)
    t, kh, dh = codes.shape
    c = codes.astype(jnp.float32).reshape(t * kh, dh) + off
    return scale.reshape(t * kh, 1) * (c + zero.reshape(t * kh, 1))


def rope_page(k: jnp.ndarray, kpos: jnp.ndarray,
              freqs: jnp.ndarray) -> jnp.ndarray:
    """Apply RoPE at absolute positions `kpos` [C, 1] to the page rows
    k [C, Dh] (f32) — `models.layers.apply_rope` arithmetic; freqs
    [1, Dh/2]."""
    ang = kpos.astype(jnp.float32) * freqs                  # [C, Dh/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(k, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def page_update(m, l, acc, q, k, v, valid, scale):
    """One online-softmax step over a single KV page.

    q [R, Dh] f32, k/v [C, Dh] f32, valid [R, C] (same KV head and causal);
    m/l [R, 1], acc [R, Dh]. Every row's trajectory is independent, so
    tiling does not change a single bit. Masked entries contribute exactly
    zero (exp underflows), so scratch-padded table columns and the other
    heads' rows are free no-ops.
    """
    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 precision=_F32,
                                 preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid, logits, MASK_VALUE)
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def walk_step(carry, q, qpos, col, k, v, aux, *, scale, bits, freqs, g):
    """Advance one tile's `(m, l, acc)` by table column `col`.

    q [R, Dh] f32 and qpos [R, 1] int32 are the tile's query rows; k/v
    [T, hb, Dh] one page of the tile's KV heads (float, or codes with
    `aux` = (k_scale, k_zero, v_scale, v_zero) [T, hb, 1] pages); `freqs`
    [1, Dh/2] rotates dequantized (pre-RoPE) K, None for float pages.
    """
    t, hb, dh = k.shape
    c = t * hb
    if bits is not None:
        ks, kz, vs, vz = aux
        k = dequant_page(k, ks, kz, bits=bits)
        v = dequant_page(v, vs, vz, bits=bits)
    else:
        k = k.astype(jnp.float32).reshape(c, dh)
        v = v.astype(jnp.float32).reshape(c, dh)
    if freqs is not None:
        kpos = col * t + jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) // hb
        k = rope_page(k, kpos, freqs)
    rows = q.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, c), 0)
    cc = jax.lax.broadcasted_iota(jnp.int32, (rows, c), 1)
    valid = jnp.logical_and((r % (hb * g)) // g == cc % hb,
                            col * t + cc // hb <= qpos)
    return page_update(*carry, q, k, v, valid, scale)


def finalize(l, acc):
    """acc/l → [R, Dh] f32 (a single page walk degenerates to the plain
    softmax: one m/l pass ≡ exp(x−max)/Σ)."""
    return acc / jnp.maximum(l, 1e-30)


def combine_partials(m, l, acc):
    """LSE-merge `kv_splits` flash-decoding partials into the output tile.

    m/l [KS, R, 1], acc [KS, R, Dh] → [R, Dh] f32. Splits that saw no
    live page carry m = -inf; their weight is forced to exactly zero so
    empty partitions are bit-for-bit no-ops (matching the in-walk skip).
    """
    mx = jnp.max(m, axis=0)                                  # [R, 1]
    w = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - mx[None]))
    l_tot = jnp.sum(l * w, axis=0)                           # [R, 1]
    acc_tot = jnp.sum(acc * w, axis=0)                       # [R, Dh]
    return acc_tot / jnp.maximum(l_tot, 1e-30)


# ---------------------------------------------------------------------------
# Tiling resolution (shared by the kernel dispatch and the reference)
# ---------------------------------------------------------------------------

def _largest_divisor(n: int, cap: int) -> int:
    for t in range(min(cap, n), 0, -1):
        if n % t == 0:
            return t
    return 1


# Decode split-K defaults: FIXED-WIDTH partitions (4 table columns per
# split, up to 8 splits). The resolver pins the split WIDTH and derives
# the split count from it — never `width = ceil(n_cols / kv_splits)`,
# which would move partition boundaries whenever the table widens. Fixed
# boundaries keep scratch-column widening bit-exact: widening only
# appends splits past every used-page count, whose partials carry
# m = -inf and thus exactly zero combine weight. Past
# `SPLIT_PAGE_COLS · MAX_KV_SPLITS` columns the cap forces wider splits,
# so boundaries do shift at table-width doublings there — a ulp-level
# effect covered by the engine's tolerance contract, not the bitwise one.
SPLIT_PAGE_COLS = 4
MAX_KV_SPLITS = 8


def resolve_tiling(s: int, kh: int, n_cols: int,
                   q_block: int | None = None,
                   kv_splits: int | None = None,
                   head_block: int | None = None
                   ) -> tuple[int, int, int, int]:
    """Shape-driven defaults for the grid axes — resolved identically on
    the kernel and reference paths so a `(q_block, kv_splits, head_block)`
    request means the same reduction order on both. Returns
    `(q_block, kv_splits, head_block, split_cols)` where `split_cols` is
    the page-column width of every split partition (the table is padded
    to `kv_splits · split_cols` scratch columns).

      * q_block: ≤ 8 query rows per instance (decode S=1 → 1, an 8-token
        prefill chunk → one block, a 32-token chunk → 4 blocks).
      * head_block: every KV head in one instance. A page is fetched
        whole: the TPU's DMA cannot cut a sub-tile slice out of the head
        axis of a [T, KH, Dh] page, so a smaller block only runs in
        interpret mode (where the tests sweep it).
      * kv_splits: decode steps (S == 1) partition the page walk into
        fixed `SPLIT_PAGE_COLS`-wide splits, up to `MAX_KV_SPLITS`
        (context-length parallelism for the latency-critical path);
        prefill keeps one walk per (head, q-block) instance, which is
        already wide. An explicit `kv_splits` request gets equal-width
        `ceil(n_cols / kv_splits)` partitions instead.
    """
    if q_block is None:
        q_block = _largest_divisor(s, min(s, 8))
    if head_block is None:
        head_block = kh
    if kv_splits is None:
        if s == 1 and n_cols > SPLIT_PAGE_COLS:
            # width first, count second: boundaries at fixed multiples of
            # split_cols stay put when the table widens (see above)
            split_cols = max(SPLIT_PAGE_COLS, -(-n_cols // MAX_KV_SPLITS))
            kv_splits = -(-n_cols // split_cols)
        else:
            kv_splits, split_cols = 1, n_cols
    else:
        kv_splits = max(1, min(kv_splits, n_cols))
        split_cols = -(-n_cols // kv_splits)
    if s % q_block:
        raise ValueError(f"q_block {q_block} does not divide q_len {s}")
    if kh % head_block:
        raise ValueError(f"head_block {head_block} does not divide "
                         f"n_kv_heads {kh}")
    return q_block, kv_splits, head_block, split_cols


def used_page_counts(q_positions: jnp.ndarray,
                     seq_lengths: jnp.ndarray | None,
                     page_size: int, n_cols: int) -> jnp.ndarray:
    """[B] number of live table columns per sequence: ceil(len/page_size).

    `seq_lengths` comes from the scheduler (true per-sequence context
    lengths; 0 for padded batch rows → the whole walk is skipped). Without
    it the count is derived from the query positions — the causal mask
    hides every page past `max(qpos)+1` anyway, so trimming them is exact.
    """
    if seq_lengths is None:
        lens = jnp.max(q_positions, axis=1) + 1
    else:
        lens = seq_lengths
    return jnp.clip(-(-lens // page_size), 0, n_cols).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

_AUX = ("k_scale", "k_zero", "v_scale", "v_zero")


def _kernel(bt_ref, used_ref, *refs, g, scale, bits, rope, ncp, splits):
    quant = bits is not None
    q_ref, qpos_ref, k_ref, v_ref, *rest = refs
    if quant:
        ks_ref, kz_ref, vs_ref, vz_ref, *rest = rest
    if rope:
        fr_ref, *rest = rest
    if splits == 1:
        o_ref, m_ref, l_ref, acc_ref = rest
    else:
        mp_ref, lp_ref, ap_ref, m_ref, l_ref, acc_ref = rest

    b = pl.program_id(0)
    ks = pl.program_id(3)
    p = pl.program_id(4)
    col = ks * ncp + p
    q_block, hbg, dh = q_ref.shape[1:]
    rows = q_block * hbg

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(col < used_ref[b])
    def _update():
        q = q_ref[0].astype(jnp.float32).reshape(rows, dh)
        qpos = qpos_ref[0].reshape(rows, 1)
        aux = ((ks_ref[0], kz_ref[0], vs_ref[0], vz_ref[0]) if quant
               else None)
        m, l, acc = walk_step(
            (m_ref[...], l_ref[...], acc_ref[...]), q, qpos, col,
            k_ref[0], v_ref[0], aux, scale=scale, bits=bits,
            freqs=fr_ref[...] if rope else None, g=g)
        m_ref[...] = m
        l_ref[...] = l
        acc_ref[...] = acc

    @pl.when(p == ncp - 1)
    def _epilogue():
        if splits == 1:
            o_ref[0] = finalize(l_ref[...], acc_ref[...]).reshape(
                q_block, hbg, dh).astype(o_ref.dtype)
        else:
            mp_ref[0, 0] = m_ref[...].reshape(q_block, hbg, 1)
            lp_ref[0, 0] = l_ref[...].reshape(q_block, hbg, 1)
            ap_ref[0, 0] = acc_ref[...].reshape(q_block, hbg, dh)


def _combine_kernel(mp_ref, lp_ref, ap_ref, o_ref):
    ks, q_block, hbg, dh = ap_ref.shape[1:]
    rows = q_block * hbg
    out = combine_partials(mp_ref[0].reshape(ks, rows, 1),
                           lp_ref[0].reshape(ks, rows, 1),
                           ap_ref[0].reshape(ks, rows, dh))
    o_ref[0] = out.reshape(q_block, hbg, dh).astype(o_ref.dtype)


def _query_rows(q_positions: jnp.ndarray, h: int) -> jnp.ndarray:
    """[B, S] query positions → [B, S, H, 1], one per tile row."""
    b, s = q_positions.shape
    return jnp.broadcast_to(q_positions.astype(jnp.int32)[:, :, None, None],
                            (b, s, h, 1))


@functools.partial(jax.jit, static_argnames=(
    "rope_theta", "kv_bits", "kv_group", "q_block", "kv_splits",
    "head_block", "interpret"))
def paged_attention(q: jnp.ndarray, kv: dict, block_tables: jnp.ndarray,
                    q_positions: jnp.ndarray,
                    seq_lengths: jnp.ndarray | None = None, *,
                    rope_theta: float | None = None,
                    kv_bits: int | None = None,
                    kv_group: int | None = None,
                    q_block: int | None = None,
                    kv_splits: int | None = None,
                    head_block: int | None = None,
                    interpret: bool = True) -> jnp.ndarray:
    """Causal attention of `q` against one layer's KV page pool.

    q [B, S, H, Dh] (queries already rotated); kv {"k", "v"} pages
    [n_pages, T, KH, Dh] (+ "{k,v}_{scale,zero}" [n_pages, T, KH, 1]
    when `kv_bits` is set — one scale/zero pair per (position, head), so
    `kv_group` must equal Dh); block_tables [B, P] int32 (pad = scratch);
    q_positions [B, S] int32 absolute positions; seq_lengths [B] optional
    true context lengths (pages past ceil(len/T) are skipped — 0 skips the
    row's whole walk). `rope_theta` rotates the dequantized K pages
    in-kernel (integer caches store K pre-RoPE). `q_block`/`kv_splits`/
    `head_block` pick the grid tiling (`resolve_tiling` defaults).
    Returns [B, S, H, Dh] float32.
    """
    b, s, h, dh = q.shape
    t, kh = kv["k"].shape[1], kv["k"].shape[2]
    g = h // kh
    n_cols = block_tables.shape[1]
    quant = kv_bits is not None
    if quant and kv_group != dh:
        raise ValueError(f"kv_group {kv_group} != head_dim {dh}: pages "
                         "carry one scale/zero pair per (position, head)")
    q_block, kv_splits, head_block, ncp = resolve_tiling(
        s, kh, n_cols, q_block, kv_splits, head_block)
    n_hb, n_qb = kh // head_block, s // q_block
    hbg = head_block * g
    rows = q_block * hbg

    # partition the page axis into kv_splits × ncp-column walks; the grid
    # needs equal widths, so the table is padded with scratch columns
    # (past every used count — never walked)
    pad_cols = kv_splits * ncp - n_cols
    if pad_cols:
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad_cols)))
    used = used_page_counts(q_positions, seq_lengths, t, n_cols)

    # float pages hold post-RoPE K; integer pages hold it pre-RoPE
    rope = quant and rope_theta is not None
    kern = functools.partial(
        _kernel, g=g, scale=1.0 / math.sqrt(dh), bits=kv_bits, rope=rope,
        ncp=ncp, splits=kv_splits)

    def page_spec(last):
        # Pallas double-buffers these fetches. Columns past the used count
        # re-name the last live page, so the pipeline issues no copy for
        # them (the block index does not change) and the walk skips them.
        def index(bb, hh, qq, ss, pp, bt, u):
            live = jnp.minimum(ss * ncp + pp, jnp.maximum(u[bb] - 1, 0))
            return (bt[bb, live], 0, hh, 0)
        return pl.BlockSpec((1, t, head_block, last), index)

    in_specs = [
        pl.BlockSpec((1, q_block, hbg, dh),
                     lambda bb, hh, qq, ss, pp, bt, u: (bb, qq, hh, 0)),
        pl.BlockSpec((1, q_block, hbg, 1),
                     lambda bb, hh, qq, ss, pp, bt, u: (bb, qq, hh, 0)),
        page_spec(dh), page_spec(dh),
    ]
    operands = [q, _query_rows(q_positions, h), kv["k"], kv["v"]]
    if quant:
        in_specs += [page_spec(1)] * 4
        operands += [kv[name] for name in _AUX]
    if rope:
        in_specs.append(pl.BlockSpec(
            (1, dh // 2), lambda bb, hh, qq, ss, pp, bt, u: (0, 0)))
        operands.append(rope_frequencies(dh, rope_theta)[None])

    if kv_splits == 1:
        out_shape = jax.ShapeDtypeStruct((b, s, h, dh), jnp.float32)
        out_specs = pl.BlockSpec(
            (1, q_block, hbg, dh),
            lambda bb, hh, qq, ss, pp, bt, u: (bb, qq, hh, 0))
    else:
        out_shape = (
            jax.ShapeDtypeStruct((b, kv_splits, s, h, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kv_splits, s, h, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kv_splits, s, h, dh), jnp.float32),
        )

        def part_spec(last):
            return pl.BlockSpec((1, 1, q_block, hbg, last),
                                lambda bb, hh, qq, ss, pp, bt, u:
                                (bb, ss, qq, hh, 0))
        out_specs = (part_spec(1), part_spec(1), part_spec(dh))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_hb, n_qb, kv_splits, ncp),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, dh), jnp.float32),
        ],
    )
    result = pl.pallas_call(
        kern,
        out_shape=out_shape,
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), used, *operands)
    if kv_splits == 1:
        return result

    def split_spec(last):
        return pl.BlockSpec((1, kv_splits, q_block, hbg, last),
                            lambda bb, hh, qq: (bb, 0, qq, hh, 0))
    return pl.pallas_call(
        _combine_kernel,
        out_shape=jax.ShapeDtypeStruct((b, s, h, dh), jnp.float32),
        grid=(b, n_hb, n_qb),
        in_specs=[split_spec(1), split_spec(1), split_spec(dh)],
        out_specs=pl.BlockSpec((1, q_block, hbg, dh),
                               lambda bb, hh, qq: (bb, qq, hh, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(*result)


# ---------------------------------------------------------------------------
# jnp reference (re-exported as `kernels.ref.paged_attention_ref`)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "rope_theta", "kv_bits", "kv_group", "q_block", "kv_splits",
    "head_block"))
def paged_attention_reference(q: jnp.ndarray, kv: dict,
                              block_tables: jnp.ndarray,
                              q_positions: jnp.ndarray,
                              seq_lengths: jnp.ndarray | None = None, *,
                              rope_theta: float | None = None,
                              kv_bits: int | None = None,
                              kv_group: int | None = None,
                              q_block: int | None = None,
                              kv_splits: int | None = None,
                              head_block: int | None = None) -> jnp.ndarray:
    """Plain-XLA mirror of the kernel: the identical split/combine
    reduction order — per-split column walks as `lax.scan`s with the same
    used-page skip, the same LSE combine, same helpers, same op order —
    replayed PER `(head_block, q_block)` TILE, vmapped over sequences.
    Tiling the element-independent head/query axes cannot change the math,
    but it does change the operand shapes XLA hands its dot kernels, and
    different gemm strategies round the d-contraction a ulp apart; walking
    each tile at exactly the kernel instance's shapes is what keeps the
    contract bit-for-bit for every `(q_block, kv_splits, head_block)`.
    jit'd like the kernel entry (an eagerly dispatched combine chain
    rounds a ulp away from the compiled one).
    """
    b, s, h, dh = q.shape
    t, kh = kv["k"].shape[1], kv["k"].shape[2]
    g = h // kh
    n_cols = block_tables.shape[1]
    quant = kv_bits is not None
    if quant and kv_group != dh:
        raise ValueError(f"kv_group {kv_group} != head_dim {dh}: pages "
                         "carry one scale/zero pair per (position, head)")
    scale = 1.0 / math.sqrt(dh)
    q_block, kv_splits, head_block, ncp = resolve_tiling(
        s, kh, n_cols, q_block, kv_splits, head_block)
    hbg = head_block * g
    rows = q_block * hbg
    pad_cols = kv_splits * ncp - n_cols
    if pad_cols:
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad_cols)))
    used = used_page_counts(q_positions, seq_lengths, t, n_cols)
    freqs = (rope_frequencies(dh, rope_theta)[None]
             if quant and rope_theta is not None else None)

    def one_sequence(qb, qposb, btb, used_b):
        def one_tile(q_tile, qpos_tile, h0):
            # q_tile [q_block, hbg, dh] — one grid instance
            q2 = q_tile.astype(jnp.float32).reshape(rows, dh)
            qp = qpos_tile.reshape(rows, 1)
            hsl = slice(h0, h0 + head_block)

            def step(carry, inp):
                col, page = inp
                aux = (tuple(kv[name][page][:, hsl] for name in _AUX)
                       if quant else None)
                new = walk_step(carry, q2, qp, col, kv["k"][page][:, hsl],
                                kv["v"][page][:, hsl], aux, scale=scale,
                                bits=kv_bits, freqs=freqs, g=g)
                # the kernel skips dead columns outright; selecting the
                # old carry replays that skip exactly
                keep = col < used_b
                carry = jax.tree.map(
                    lambda n, o: jnp.where(keep, n, o), new, carry)
                return carry, None

            def split_walk(split):
                cols = jnp.arange(split * ncp, (split + 1) * ncp,
                                  dtype=jnp.int32)
                init = (jnp.full((rows, 1), -jnp.inf, jnp.float32),
                        jnp.zeros((rows, 1), jnp.float32),
                        jnp.zeros((rows, dh), jnp.float32))
                (m, l, acc), _ = jax.lax.scan(
                    step, init,
                    (cols, jax.lax.dynamic_slice_in_dim(btb, split * ncp,
                                                        ncp)))
                return m, l, acc

            if kv_splits == 1:
                m, l, acc = split_walk(0)
                return finalize(l, acc).reshape(q_block, hbg, dh)
            parts = [split_walk(i) for i in range(kv_splits)]
            return tuple(jnp.stack([x.reshape(q_block, hbg, -1)
                                    for x in xs])
                         for xs in zip(*parts))  # 3 × [KS, q_block, hbg, ·]

        tiles = [[one_tile(qb[qi * q_block:(qi + 1) * q_block,
                              hb * hbg:(hb + 1) * hbg],
                           qposb[qi * q_block:(qi + 1) * q_block,
                                 hb * hbg:(hb + 1) * hbg],
                           hb * head_block)
                  for hb in range(kh // head_block)]
                 for qi in range(s // q_block)]
        if kv_splits == 1:
            return jnp.concatenate(
                [jnp.concatenate(row, axis=1) for row in tiles], axis=0)
        # per-tile partial stacks [n_q_tiles, n_h_tiles, KS, q_block, hbg, ·]
        return jax.tree.map(lambda *xs: jnp.stack(xs).reshape(
            s // q_block, kh // head_block, *xs[0].shape),
            *[tile for row in tiles for tile in row])

    out = jax.vmap(one_sequence)(q, _query_rows(q_positions, h),
                                 block_tables, used)
    if kv_splits == 1:
        return out

    # The combine runs OUTSIDE the vmapped walk, per (sequence, tile),
    # behind an optimization barrier: the kernel path's partials are
    # materialized pallas outputs (a hard fusion boundary), and without
    # the same boundary here XLA fuses the combine's multiply-adds into
    # the walk's producers as FMAs — a ulp apart from the kernel's
    # combine. Same shapes + same isolation ⇒ same lowering, bit for bit.
    m_p, l_p, acc_p = (jax.lax.optimization_barrier(x) for x in out)
    out_rows = []
    for bi in range(b):
        qrows = []
        for qi in range(s // q_block):
            tiles = [combine_partials(
                m_p[bi, qi, hb].reshape(kv_splits, rows, 1),
                l_p[bi, qi, hb].reshape(kv_splits, rows, 1),
                acc_p[bi, qi, hb].reshape(kv_splits, rows, dh)
            ).reshape(q_block, hbg, dh) for hb in range(kh // head_block)]
            qrows.append(jnp.concatenate(tiles, axis=1))
        out_rows.append(jnp.concatenate(qrows, axis=0))
    return jnp.stack(out_rows)
