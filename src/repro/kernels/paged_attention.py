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
  * **Pipelined page fetch** — the kernel takes every layer's pool
    (`[n_layers, n_pages, ...]` leaves) and the layer to read as a third
    scalar-prefetch operand, so a model's layer loop can carry the pool
    and hand it over whole: nothing slices a layer out of it. A page is
    ONE block of K|V rows (plus one block of its scale/zero rows for the
    integer formats) whose index map `(layer, block_table[b, col], 0, 0)`
    reads the scalar-prefetched table, so Pallas double-buffers them: the
    copy of the next column overlaps the update of the current one. A
    column past the used count maps to the last live page again, and an
    unchanged block index issues no copy, so dead columns cost no DMA.

**The page format** (`pack_pages` / `unpack_pages` / `zero_pages`, and
`paged_write_rows`, which writes new positions in place). Each pool leaf's two
minor dims are the page's rows and lanes, sized so they fill whole TPU
tiles — (32, 128) for int8, (16, 128) for bf16, (8, 128) for f32 — and
XLA stores the pool row-major, the layout the Pallas operand takes, with
no relayout anywhere in a step:

  * `kv_rows` `[..., n_pages, T·KH, 2·Dh]`: row `t·KH + j` holds position
    t of KV head j, K in lanes `[0, Dh)` and V in `[Dh, 2·Dh)` — float
    K/V, or int8 codes (int4 codes stored in int8) offset by 2^(bits-1).
    A 16-row page of 8 heads of 64 is one (128, 128) int8 block: 16 KiB,
    one DMA, no padding.
  * `kv_scale_zero` `[..., n_pages, 4, T·KH]` f32 (integer formats only):
    rows `k_scale, k_zero, v_scale, v_zero`, lane `t·KH + j` — one
    asymmetric pair per (position, head), in the rows' order.

The rule is tile-dense whenever 2·Dh is a multiple of 128 and T·KH one of
the dtype's sublane tile, which every served head size (64, 128) meets at
16-row pages with 2 or more KV heads (the scale/zero block of 4 rows
fills half an f32 tile: 2 KiB of 18 KiB a page).

Three KV page formats are served by the same walk:

  * float pages (bf16/f32) holding post-RoPE K — the bf16 and fake-quant
    engine backends;
  * int8/int4 code pages with per-(position, head) asymmetric
    scale/zero rows riding along — dequantized in VMEM, and (because the
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
           "used_page_counts", "rope_frequencies", "pack_pages",
           "unpack_pages", "zero_pages", "paged_write_rows", "ROWS",
           "SCALE_ZERO", "AUX"]

MASK_VALUE = -1e30
# f32 dots at full precision on the chip too (XLA's and Mosaic's default
# for an f32 matmul may drop to one bf16 pass), so the kernel and the
# XLA reference compute the same arithmetic on every backend
_F32 = jax.lax.Precision.HIGHEST

# pool leaf names, and the row order of the scale/zero leaf
ROWS, SCALE_ZERO = "kv_rows", "kv_scale_zero"
AUX = ("k_scale", "k_zero", "v_scale", "v_zero")


# ---------------------------------------------------------------------------
# The page format
# ---------------------------------------------------------------------------

def pack_pages(k: jnp.ndarray, v: jnp.ndarray,
               aux: tuple | None = None) -> dict:
    """Logical pages → pool leaves (module docstring, "The page format").

    k, v [..., T, KH, Dh]; aux = (k_scale, k_zero, v_scale, v_zero), each
    [..., T, KH], for the integer formats. Returns {"kv_rows": [...,
    T·KH, 2·Dh]} (+ "kv_scale_zero" [..., 4, T·KH]). With T = 1 it packs
    single rows, the unit a page write scatters."""
    *lead, t, kh, dh = k.shape
    out = {ROWS: jnp.concatenate([k, v.astype(k.dtype)], axis=-1).reshape(
        *lead, t * kh, 2 * dh)}
    if aux is not None:
        out[SCALE_ZERO] = jnp.stack(aux, axis=-3).astype(
            jnp.float32).reshape(*lead, len(AUX), t * kh)
    return out


def unpack_pages(pool: dict, kv_heads: int):
    """Pool leaves → logical pages: (k, v [..., T, KH, Dh], aux) with aux
    the four [..., T, KH] scale/zero arrays of `AUX`, or None for float
    pages. The inverse of `pack_pages`."""
    rows = pool[ROWS]
    *lead, c, w = rows.shape
    t, dh = c // kv_heads, w // 2
    kv = rows.reshape(*lead, t, kv_heads, w)
    aux = None
    if SCALE_ZERO in pool:
        sz = pool[SCALE_ZERO].reshape(*lead, len(AUX), t, kv_heads)
        aux = tuple(sz[..., i, :, :] for i in range(len(AUX)))
    return kv[..., :dh], kv[..., dh:], aux


def zero_pages(shape: tuple, dtype, *, scale_zero: bool = False) -> dict:
    """Zeroed pool leaves for logical pages of `shape` [..., T, KH, Dh]:
    "kv_rows" in `dtype`, and the f32 "kv_scale_zero" leaf of the integer
    formats when `scale_zero`."""
    *lead, t, kh, dh = shape
    out = {ROWS: jnp.zeros((*lead, t * kh, 2 * dh), dtype)}
    if scale_zero:
        out[SCALE_ZERO] = jnp.zeros((*lead, len(AUX), t * kh), jnp.float32)
    return out


def paged_write_rows(pool: dict, rows: dict, block_table: jnp.ndarray,
                     positions: jnp.ndarray) -> dict:
    """Write new KV rows straight into their pages, in place.

    pool: pool leaves in the page format ("kv_rows" [..., n_pages, T·KH,
    2·Dh], and "kv_scale_zero" [..., n_pages, 4, T·KH] for integer
    pages); rows: the same leaves for the new positions, [B, S, KH, 2·Dh]
    and [B, S, 4, KH] (`pack_pages` of one position); block_table [B, P]
    int32 page ids; positions [B, S] absolute positions. Position p of
    row b lands in page block_table[b, p // T] at rows (p % T)·KH + j
    (lanes, for the scale/zero leaf). Page ids index the leaves' leading
    axes taken as one, so a layer's pages in an [L, n_pages, ...] pool
    are `layer·n_pages + id` and the write lands at [layer, page, slot]
    without slicing a layer out. One loop of in-place updates, one per
    (b, s), writes every leaf. Padded rows target the scratch page (or
    not-yet-valid in-page slots that are overwritten before the causal
    mask ever exposes them), so duplicate writes are harmless.
    """
    flat = {k: a.reshape(-1, *a.shape[-2:]) for k, a in pool.items()}
    kh = rows[ROWS].shape[-2]
    t = flat[ROWS].shape[-2] // kh
    pages = jnp.take_along_axis(block_table, positions // t,
                                axis=1).reshape(-1).astype(jnp.int32)
    starts = ((positions % t) * kh).reshape(-1).astype(jnp.int32)
    new = {k: v.reshape(-1, 1, *v.shape[2:]).astype(pool[k].dtype)
           for k, v in rows.items()}

    def write(i, flat):
        zero = jnp.zeros((), jnp.int32)
        at = {ROWS: (pages[i], starts[i], zero),
              SCALE_ZERO: (pages[i], zero, starts[i])}
        return {k: jax.lax.dynamic_update_slice(a, new[k][i], at[k])
                for k, a in flat.items()}

    flat = jax.lax.fori_loop(0, pages.shape[0], write, flat)
    return {k: flat[k].reshape(pool[k].shape) for k in pool}


# ---------------------------------------------------------------------------
# Shared arithmetic (kernel body AND the bit-for-bit jnp reference)
# ---------------------------------------------------------------------------
#
# One grid instance owns a (q_block, head_block) tile. Its queries are laid
# out as R = q_block · head_block · G rows (row r = s·hbg + j·G + g: query
# position s, KV head j of the block, group member g) and one page of the
# block's KV heads as C = page_size · head_block rows (row c = t·hb + j,
# the pool's own row order). Every step is then a plain 2-D matmul:
# logits [R, C] = q · kᵀ with the rows of other KV heads masked out, which
# keeps the page in the pool's layout (no in-kernel transpose of K) at
# the price of head_block× redundant MXU work on the masked entries.

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


def dequant_rows(codes: jnp.ndarray, scale: jnp.ndarray, zero: jnp.ndarray,
                 *, bits: int) -> jnp.ndarray:
    """Asymmetric per-(position, head) dequant of page rows → [C, Dh] f32.

    codes [C, Dh] (int8 codes stored offset by 2^(bits-1), as f32),
    scale/zero [C, 1] — the exact arithmetic of
    `QuantizedDenseLM._cache_read`.
    """
    off = 2 ** (bits - 1)
    return scale * ((codes + off) + zero)


def head_rows(page: jnp.ndarray, sz: jnp.ndarray | None, h0, *, kv_heads: int,
              head_block: int):
    """The rows of KV heads [h0, h0 + head_block) of one page: page
    [T·KH, W] → [T·hb, W], sz [4, T·KH] → [4, T·hb]. A tile of every head
    takes the page as it is; a smaller head block (interpret mode only:
    the TPU's DMA cannot cut a head out of a page's sublanes) slices it."""
    if head_block == kv_heads:
        return page, sz
    t = page.shape[0] // kv_heads
    page = jax.lax.dynamic_slice_in_dim(
        page.reshape(t, kv_heads, -1), h0, head_block, axis=1)
    page = page.reshape(t * head_block, -1)
    if sz is not None:
        sz = jax.lax.dynamic_slice_in_dim(
            sz.reshape(len(AUX), t, kv_heads), h0, head_block, axis=2)
        sz = sz.reshape(len(AUX), t * head_block)
    return page, sz


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


def walk_step(carry, q, qpos, col, page, sz, h0, *, scale, bits, freqs, g,
              kv_heads, head_block):
    """Advance one tile's `(m, l, acc)` by table column `col`.

    q [R, Dh] f32 and qpos [R, 1] int32 are the tile's query rows; page
    [T·KH, 2·Dh] one page's K|V rows (float, or codes with `sz` its
    [4, T·KH] scale/zero rows), of which the tile reads KV heads
    [h0, h0 + head_block); `freqs` [1, Dh/2] rotates dequantized
    (pre-RoPE) K, None for float pages.
    """
    page, sz = head_rows(page, sz, h0, kv_heads=kv_heads,
                         head_block=head_block)
    hb = head_block
    c, w = page.shape
    t, dh = c // hb, w // 2
    x = page.astype(jnp.float32)
    k, v = x[:, :dh], x[:, dh:]
    if bits is not None:
        cols = sz.T                                           # [C, 4]
        k = dequant_rows(k, cols[:, 0:1], cols[:, 1:2], bits=bits)
        v = dequant_rows(v, cols[:, 2:3], cols[:, 3:4], bits=bits)
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

def _kernel(bt_ref, used_ref, layer_ref, *refs, g, scale, bits, rope, ncp,
            splits, kv_heads, head_block):
    del layer_ref    # read by the index maps only
    quant = bits is not None
    q_ref, qpos_ref, kv_ref, *rest = refs
    if quant:
        sz_ref, *rest = rest
    if rope:
        fr_ref, *rest = rest
    if splits == 1:
        o_ref, m_ref, l_ref, acc_ref = rest
    else:
        mp_ref, lp_ref, ap_ref, m_ref, l_ref, acc_ref = rest

    b = pl.program_id(0)
    h0 = pl.program_id(1) * head_block
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
        m, l, acc = walk_step(
            (m_ref[...], l_ref[...], acc_ref[...]), q, qpos, col,
            kv_ref[0, 0], sz_ref[0, 0] if quant else None, h0,
            scale=scale, bits=bits,
            freqs=fr_ref[...] if rope else None, g=g, kv_heads=kv_heads,
            head_block=head_block)
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


def _page_geometry(q, kv, kv_heads, kv_bits, kv_group):
    """(page_size, group size) of a pool, after checking it against q."""
    dh = q.shape[3]
    c, w = kv[ROWS].shape[-2:]
    if w != 2 * dh or c % kv_heads:
        raise ValueError(f"pool rows [{c}, {w}] are not pages of "
                         f"{kv_heads} KV heads of K|V rows of {dh}")
    if kv_bits is not None and kv_group != dh:
        raise ValueError(f"kv_group {kv_group} != head_dim {dh}: pages "
                         "carry one scale/zero pair per (position, head)")
    return c // kv_heads, q.shape[2] // kv_heads


def _layer_operand(layer) -> jnp.ndarray:
    """The layer index as the kernel's [1] int32 scalar-prefetch operand
    (None reads layer 0: a pool with one layer)."""
    return jnp.reshape(jnp.asarray(0 if layer is None else layer,
                                   jnp.int32), (1,))


@functools.partial(jax.jit, static_argnames=(
    "kv_heads", "rope_theta", "kv_bits", "kv_group", "q_block",
    "kv_splits", "head_block", "interpret"))
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
                    head_block: int | None = None,
                    interpret: bool = True) -> jnp.ndarray:
    """Causal attention of `q` against layer `layer` of a KV page pool.

    q [B, S, H, Dh] (queries already rotated); kv the pool in the page
    format of the module docstring: "kv_rows" [L, n_pages, T·KH, 2·Dh]
    (+ "kv_scale_zero" [L, n_pages, 4, T·KH] when `kv_bits` is set — one
    scale/zero pair per (position, head), so `kv_group` must equal Dh),
    of `kv_heads` KV heads; `layer` a traced scalar (None → 0) that the
    page index maps read; block_tables [B, P] int32 (pad = scratch);
    q_positions [B, S] int32 absolute positions; seq_lengths [B] optional
    true context lengths (pages past ceil(len/T) are skipped — 0 skips the
    row's whole walk). `rope_theta` rotates the dequantized K pages
    in-kernel (integer caches store K pre-RoPE). `q_block`/`kv_splits`/
    `head_block` pick the grid tiling (`resolve_tiling` defaults).
    Returns [B, S, H, Dh] float32.
    """
    b, s, h, dh = q.shape
    kh = kv_heads
    t, g = _page_geometry(q, kv, kh, kv_bits, kv_group)
    n_cols = block_tables.shape[1]
    quant = kv_bits is not None
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
        ncp=ncp, splits=kv_splits, kv_heads=kh, head_block=head_block)

    def page_spec(leaf):
        # Pallas double-buffers these fetches. Columns past the used count
        # re-name the last live page, so the pipeline issues no copy for
        # them (the block index does not change) and the walk skips them.
        def index(bb, hh, qq, ss, pp, bt, u, lyr):
            live = jnp.minimum(ss * ncp + pp, jnp.maximum(u[bb] - 1, 0))
            return (lyr[0], bt[bb, live], 0, 0)
        return pl.BlockSpec((1, 1) + leaf.shape[-2:], index)

    def tile_map(bb, hh, qq, *_):
        return (bb, qq, hh, 0)

    in_specs = [pl.BlockSpec((1, q_block, hbg, dh), tile_map),
                pl.BlockSpec((1, q_block, hbg, 1), tile_map),
                page_spec(kv[ROWS])]
    operands = [q, _query_rows(q_positions, h), kv[ROWS]]
    if quant:
        in_specs.append(page_spec(kv[SCALE_ZERO]))
        operands.append(kv[SCALE_ZERO])
    if rope:
        in_specs.append(pl.BlockSpec((1, dh // 2), lambda *_: (0, 0)))
        operands.append(rope_frequencies(dh, rope_theta)[None])

    if kv_splits == 1:
        out_shape = jax.ShapeDtypeStruct((b, s, h, dh), jnp.float32)
        out_specs = pl.BlockSpec((1, q_block, hbg, dh), tile_map)
    else:
        out_shape = (
            jax.ShapeDtypeStruct((b, kv_splits, s, h, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kv_splits, s, h, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kv_splits, s, h, dh), jnp.float32),
        )

        def part_spec(last):
            return pl.BlockSpec((1, 1, q_block, hbg, last),
                                lambda bb, hh, qq, ss, *_:
                                (bb, ss, qq, hh, 0))
        out_specs = (part_spec(1), part_spec(1), part_spec(dh))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
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
    )(block_tables.astype(jnp.int32), used, _layer_operand(layer),
      *operands)
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
    "kv_heads", "rope_theta", "kv_bits", "kv_group", "q_block",
    "kv_splits", "head_block"))
def paged_attention_reference(q: jnp.ndarray, kv: dict,
                              block_tables: jnp.ndarray,
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
    kh = kv_heads
    t, g = _page_geometry(q, kv, kh, kv_bits, kv_group)
    n_cols = block_tables.shape[1]
    quant = kv_bits is not None
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
    lyr = _layer_operand(layer)[0]

    def one_sequence(qb, qposb, btb, used_b):
        def one_tile(q_tile, qpos_tile, h0):
            # q_tile [q_block, hbg, dh] — one grid instance
            q2 = q_tile.astype(jnp.float32).reshape(rows, dh)
            qp = qpos_tile.reshape(rows, 1)

            def step(carry, inp):
                col, page = inp
                sz = kv[SCALE_ZERO][lyr, page] if quant else None
                new = walk_step(carry, q2, qp, col, kv[ROWS][lyr, page], sz,
                                h0, scale=scale, bits=kv_bits, freqs=freqs,
                                g=g, kv_heads=kh, head_block=head_block)
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
