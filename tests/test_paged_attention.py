"""Paged-attention kernel vs the gather+dense oracle.

The kernel is flash-decoding shaped — grid `(batch, kv_head_block,
q_block, kv_split, page_column)`, split-K partials merged by an LSE
combine kernel, ragged early-exit on scalar-prefetched used-page counts —
while the *independent* oracle gathers the pages into a contiguous slab
(`pages.gather_pages` arithmetic) and runs plain-softmax causal attention,
the exact data path the kernel replaced. Swept over page sizes, ragged
per-sequence lengths, GQA group sizes, `(q_block, kv_splits, head_block)`
tilings, and all three KV page formats (bf16-style float pages with
post-RoPE K, int8/int4 code pages with per-(position, head) scale/zero
and pre-RoPE K rotated after dequant). The split/combine reduction order
and the early-exit are additionally pinned bitwise: dispatch-vs-reference
for a non-trivial split config, and trimmed-pad-column no-ops.
"""
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels.paged_attention import AUX, pack_pages
from repro.models import layers as L

B, S_CHUNK, KH, G, DH = 3, 4, 2, 2, 32
H = KH * G


def _make_pool(rng, fmt, n_pages, t, kh=KH):
    """Logical pages {"k", "v" [n_pages, t, kh, DH]} (+ the four
    scale/zero arrays [n_pages, t, kh, 1] of the integer formats) — what
    the oracle reads; `_pool` packs them into the kernel's page format."""
    shape = (n_pages, t, kh, DH)
    if fmt == "float":
        return {"k": jnp.asarray(rng.standard_normal(shape), jnp.float32),
                "v": jnp.asarray(rng.standard_normal(shape), jnp.float32)}
    bits = {"int8": 8, "int4": 4}[fmt]
    off, levels = 2 ** (bits - 1), 2 ** bits - 1

    def codes():
        return jnp.asarray(
            rng.integers(0, levels + 1, shape) - off, jnp.int8)

    def aux(lo, hi):
        return jnp.asarray(rng.uniform(lo, hi, (n_pages, t, kh, 1)),
                           jnp.float32)

    return {"k": codes(), "v": codes(),
            "k_scale": aux(0.02, 0.2), "v_scale": aux(0.02, 0.2),
            "k_zero": jnp.round(aux(-12.0, 2.0)),
            "v_zero": jnp.round(aux(-12.0, 2.0))}


def _pool(*layers):
    """Logical pages of each layer → one pool in the page format
    ([n_layers, n_pages, ...] leaves), through `pack_pages`."""
    def stack(name):
        return jnp.stack([kv[name] for kv in layers])
    aux = None
    if "k_scale" in layers[0]:
        aux = tuple(stack(name)[..., 0] for name in AUX)
    return pack_pages(stack("k"), stack("v"), aux)


def _attend(q, kv, *args, **kw):
    """`ops.paged_attention` over the logical pages `kv`, packed."""
    return kops.paged_attention(q, _pool(kv), *args,
                                kv_heads=kv["k"].shape[2], **kw)


def _dequant(codes, scale, zero, bits):
    off = 2 ** (bits - 1)
    return scale * (codes.astype(jnp.float32) + off + zero)


def _oracle(q, kv, bt, qpos, *, kv_bits, rope_theta):
    """Gather-to-slab + plain-softmax causal attention (the pre-kernel
    data path, written independently of the kernel helpers)."""
    b, s, h = q.shape[:3]
    t, kh = kv["k"].shape[1], kv["k"].shape[2]
    g = h // kh
    sk = bt.shape[1] * t
    k = kv["k"][bt].reshape(b, sk, kh, DH)
    v = kv["v"][bt].reshape(b, sk, kh, DH)
    if kv_bits is not None:
        ks = kv["k_scale"][bt].reshape(b, sk, kh, 1)
        kz = kv["k_zero"][bt].reshape(b, sk, kh, 1)
        vs = kv["v_scale"][bt].reshape(b, sk, kh, 1)
        vz = kv["v_zero"][bt].reshape(b, sk, kh, 1)
        k = _dequant(k, ks, kz, kv_bits)
        v = _dequant(v, vs, vz, kv_bits)
        kpos = jnp.broadcast_to(jnp.arange(sk)[None], (b, sk))
        k = L.apply_rope(k, kpos, rope_theta)
    qg = q.astype(jnp.float32).reshape(b, s, kh, g, DH)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg,
                        k.astype(jnp.float32)) / math.sqrt(DH)
    valid = jnp.arange(sk)[None, None, :] <= qpos[:, :, None]
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, DH)


def _ragged_setup(rng, page_size, *, s):
    """Per-sequence ragged lengths → block tables (distinct pages, scratch
    padded) and query positions for an s-token chunk ending the context."""
    lengths = [page_size + 3, 3 * page_size, 2 * page_size - 1]
    n_cols = max(-(-n // page_size) for n in lengths)
    n_pages = 1 + sum(-(-n // page_size) for n in lengths)
    perm = rng.permutation(np.arange(1, n_pages)).tolist()
    bt = []
    for n in lengths:
        need = -(-n // page_size)
        row = [perm.pop() for _ in range(need)]
        bt.append(row + [0] * (n_cols - need))
    bt = jnp.asarray(bt, jnp.int32)
    qpos = jnp.asarray([[n - s + j for j in range(s)] for n in lengths],
                       jnp.int32)
    return lengths, n_pages, bt, qpos


@pytest.mark.parametrize("page_size", [8, 16])
@pytest.mark.parametrize("fmt,kv_bits", [("float", None), ("int8", 8),
                                         ("int4", 4)])
@pytest.mark.parametrize("s", [1, S_CHUNK])
def test_kernel_matches_gather_dense_oracle(page_size, fmt, kv_bits, s):
    # crc32, not hash(): string hashing is per-process randomized and would
    # make a failing draw unreproducible
    rng = np.random.default_rng(
        zlib.crc32(f"{page_size}-{fmt}-{s}".encode()))
    lengths, n_pages, bt, qpos = _ragged_setup(rng, page_size, s=s)
    kv = _make_pool(rng, fmt, n_pages, page_size)
    q = jnp.asarray(rng.standard_normal((B, s, H, DH)), jnp.float32)

    got = _attend(q, kv, bt, qpos, rope_theta=500000.0, kv_bits=kv_bits,
                  kv_group=DH if kv_bits else None)
    want = _oracle(q, kv, bt, qpos, kv_bits=kv_bits, rope_theta=500000.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_scratch_padded_columns_are_exact_noops():
    """Widening a block table with scratch columns (what decode batching
    does when one sequence is much longer) must not change any output bit:
    fully masked pages contribute exactly zero to the online softmax."""
    rng = np.random.default_rng(7)
    _, n_pages, bt, qpos = _ragged_setup(rng, 8, s=1)
    kv = _make_pool(rng, "float", n_pages, 8)
    q = jnp.asarray(rng.standard_normal((B, 1, H, DH)), jnp.float32)
    narrow = _attend(q, kv, bt, qpos)
    wide = _attend(q, kv, jnp.pad(bt, ((0, 0), (0, 5))), qpos)
    np.testing.assert_array_equal(np.asarray(narrow), np.asarray(wide))


def test_widening_is_exact_across_split_boundaries():
    """The regression the fixed-WIDTH split partitioning exists for:
    sequences whose live pages straddle a split boundary (5 and 6 live
    pages vs the 4-column split width) must keep bitwise-identical
    decode outputs as the table widens. Equal-width `ceil(n_cols /
    kv_splits)` partitioning moves the boundary when the table grows
    (5 cols → splits of 3, 8 cols → splits of 4), silently re-ordering
    a running sequence's online-softmax reduction every time a longer
    request is admitted and the engine's pow2 column bucket doubles."""
    rng = np.random.default_rng(31)
    t = 8
    lengths = [5 * t - 2, 3 * t, 6 * t - 1]        # 5, 3, 6 live pages
    n_cols = 6
    n_pages = 1 + sum(-(-n // t) for n in lengths)
    perm = rng.permutation(np.arange(1, n_pages)).tolist()
    bt = []
    for n in lengths:
        need = -(-n // t)
        bt.append([perm.pop() for _ in range(need)] + [0] * (n_cols - need))
    bt = jnp.asarray(bt, jnp.int32)
    qpos = jnp.asarray([[n - 1] for n in lengths], jnp.int32)
    kv = _make_pool(rng, "float", n_pages, t)
    q = jnp.asarray(rng.standard_normal((B, 1, H, DH)), jnp.float32)
    base = _attend(q, kv, bt, qpos)
    for extra in (2, 10):                          # 8 and 16 columns
        wide = _attend(q, kv, jnp.pad(bt, ((0, 0), (0, extra))), qpos)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(wide))


@pytest.mark.parametrize("fmt,kv_bits", [("float", None), ("int8", 8),
                                         ("int4", 4)])
@pytest.mark.parametrize("g", [1, 2, 4])
@pytest.mark.parametrize("q_block,kv_splits,head_block", [(1, 3, 1),
                                                          (2, 2, 2)])
def test_gqa_tiling_sweep_matches_oracle(fmt, kv_bits, g, q_block,
                                         kv_splits, head_block):
    """GQA group sizes (KH = 2 < H for g > 1) × explicit (q_block,
    kv_splits, head_block) tilings × all three KV formats against the
    gather+dense oracle: the flash-decoding grid axes and the split-K
    combine must be invisible to the math."""
    rng = np.random.default_rng(
        zlib.crc32(f"{fmt}-{g}-{q_block}-{kv_splits}-{head_block}".encode()))
    page_size, s = 8, 4
    lengths, n_pages, bt, qpos = _ragged_setup(rng, page_size, s=s)
    kv = _make_pool(rng, fmt, n_pages, page_size)
    q = jnp.asarray(rng.standard_normal((B, s, KH * g, DH)), jnp.float32)

    got = _attend(
        q, kv, bt, qpos, jnp.asarray(lengths, jnp.int32),
        rope_theta=500000.0, kv_bits=kv_bits,
        kv_group=DH if kv_bits else None,
        q_block=q_block, kv_splits=kv_splits, head_block=head_block)
    want = _oracle(q, kv, bt, qpos, kv_bits=kv_bits, rope_theta=500000.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("fmt,kv_bits", [("float", None), ("int8", 8),
                                         ("int4", 4)])
def test_split_config_dispatch_matches_reference_bitwise(fmt, kv_bits):
    """A non-trivial flash-decoding config — multiple splits (with a
    ragged tail split), blocked queries AND blocked heads — must stay
    bit-for-bit between the interpret kernel and `use_kernels(False)`:
    the reference replays the identical split/combine reduction order,
    LSE combine included."""
    rng = np.random.default_rng(zlib.crc32(f"split-{fmt}".encode()))
    lengths, n_pages, bt, qpos = _ragged_setup(rng, 8, s=4)
    kv = _make_pool(rng, fmt, n_pages, 8)
    q = jnp.asarray(rng.standard_normal((B, 4, H, DH)), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)

    outs = {}
    for enabled in (True, False):
        with kops.use_kernels(enabled):
            outs[enabled] = np.asarray(_attend(
                q, kv, bt, qpos, lens, rope_theta=500000.0,
                kv_bits=kv_bits, kv_group=DH if kv_bits else None,
                q_block=2, kv_splits=2, head_block=2))
    np.testing.assert_array_equal(outs[True], outs[False])


@pytest.mark.parametrize("q_block,kv_splits,head_block", [
    (None, None, None), (1, 2, 2)])
def test_ragged_early_exit_is_exact(q_block, kv_splits, head_block):
    """The early-exit work reduction must be invisible bit for bit: a
    walk trimmed to each sequence's live pages (true `seq_lengths`)
    equals a forced full walk (`seq_lengths` = table capacity) exactly —
    a fully-masked page leaves m/l/acc bitwise unchanged, and an empty
    split carries exactly zero combine weight."""
    rng = np.random.default_rng(23)
    lengths, n_pages, bt, qpos = _ragged_setup(rng, 8, s=1)
    kv = _make_pool(rng, "float", n_pages, 8)
    q = jnp.asarray(rng.standard_normal((B, 1, H, DH)), jnp.float32)
    kw = dict(q_block=q_block, kv_splits=kv_splits, head_block=head_block)
    trimmed = _attend(
        q, kv, bt, qpos, jnp.asarray(lengths, jnp.int32), **kw)
    full = _attend(
        q, kv, bt, qpos, jnp.full((B,), bt.shape[1] * 8, jnp.int32), **kw)
    np.testing.assert_array_equal(np.asarray(trimmed), np.asarray(full))


def test_zero_length_rows_skip_the_whole_walk():
    """seq_lengths = 0 (a padded decode slot) skips every column: the
    row's output is exactly zero and — the part that matters — the other
    rows' outputs are untouched bit for bit."""
    rng = np.random.default_rng(29)
    lengths, n_pages, bt, qpos = _ragged_setup(rng, 8, s=1)
    kv = _make_pool(rng, "float", n_pages, 8)
    q = jnp.asarray(rng.standard_normal((B, 1, H, DH)), jnp.float32)
    base = _attend(q, kv, bt, qpos, jnp.asarray(lengths, jnp.int32))
    lens0 = jnp.asarray([lengths[0], 0, lengths[2]], jnp.int32)
    out = _attend(q, kv, bt, qpos, lens0)
    assert np.all(np.asarray(out[1]) == 0.0)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(base[0]))
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(base[2]))


def test_single_page_walk_tracks_plain_softmax_tightly():
    """One table column degenerates the online softmax to exp(x−max)/Σ —
    only the final normalisation order differs from the dense oracle
    (probs·V vs (p·V)/Σ), so the two must agree to f32 rounding."""
    rng = np.random.default_rng(11)
    kv = _make_pool(rng, "float", 4, 16)
    bt = jnp.asarray([[1], [2], [3]], jnp.int32)
    qpos = jnp.asarray([[15], [9], [4]], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, DH)), jnp.float32)
    got = _attend(q, kv, bt, qpos)
    want = _oracle(q, kv, bt, qpos, kv_bits=None, rope_theta=None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("fmt,kv_bits", [("float", None), ("int8", 8)])
def test_layer_index_selects_the_layer(fmt, kv_bits):
    """Two layers hold different pages under the same page ids: the walk
    of layer i reads layer i's pages alone, on both dispatch paths."""
    rng = np.random.default_rng(zlib.crc32(f"layers-{fmt}".encode()))
    lengths, n_pages, bt, qpos = _ragged_setup(rng, 8, s=1)
    layers = [_make_pool(rng, fmt, n_pages, 8) for _ in range(2)]
    pool = _pool(*layers)
    q = jnp.asarray(rng.standard_normal((B, 1, H, DH)), jnp.float32)
    kw = dict(kv_heads=KH, rope_theta=500000.0, kv_bits=kv_bits,
              kv_group=DH if kv_bits else None)
    outs = []
    for i, kv in enumerate(layers):
        want = _oracle(q, kv, bt, qpos, kv_bits=kv_bits, rope_theta=500000.0)
        for enabled in (True, False):
            with kops.use_kernels(enabled):
                got = kops.paged_attention(q, pool, bt, qpos, None,
                                           jnp.asarray(i, jnp.int32), **kw)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=3e-5, rtol=3e-5)
        outs.append(np.asarray(got))
    assert not np.allclose(outs[0], outs[1])
