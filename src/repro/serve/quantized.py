"""True-integer W4A4 serving path for dense-family archs.

Unlike the fake-quant evaluation path (which stores dequantized bf16
weights), this module *packs* every projection to int4 (two nibbles/byte,
per-output-channel scale) and executes decode with int8 integer arithmetic:

    per projection:  x → per-token asym int4 codes (+scale,+zero)
                     q_a @ q_w int8·int8→int32 on the MXU
                     float epilogue  s_a·s_w·(acc + z_a·colsum)

Every online op runs through the backend dispatch in `repro.kernels.ops` —
never `kernels.ref` directly — so serving gets the Pallas kernels on TPU
(Mosaic), interpret mode elsewhere, and the jnp references under
`use_kernels(False)` (dry-run/roofline). The online block-Hadamard at R̃₃
runs fused with the quantizer (`ops.hadamard_quant`), and `decode_step` /
`prefill` are jit'd end-to-end around the kernel calls (one compiled
function per kernels-enabled state). Weight HBM traffic drops 4× vs bf16
and activation traffic 2×, which is what moves the memory-roofline term in
§Perf.

Dense/VLM decoder geometry only (the paper's serving target). The KV cache
is bf16 by default; `kv_bits ∈ {4, 8}` switches to an integer cache with
asymmetric per-(position, head) scale+zero pairs (KIVI-style), with K
cached pre-RoPE (the rotation is re-applied after dequant at read time —
RoPE mixes each outlier channel across a position-dependent pair of
channels, which inflates the quantization range and wastes code points).

The cache is a page pool in the tile-dense page format of
`kernels.paged_attention` (a dense cache is the same pool with one page
per batch row). The layer loop carries it: each layer scatters its new
KV rows (codes + scale/zero for integer caches) in place at [layer,
page, slot]. Under the paged serving engine `forward_chunk` additionally
takes the per-sequence block tables and attention walks them through
`ops.paged_attention`, which reads pages by (layer, page), dequantizes
and re-rotates K inside the kernel — the same arithmetic as the dense
read path, minus the slab — so a step neither slices nor copies the
pool.

`forward_chunk(..., probe=True)` compiles a probe variant that
additionally returns per-layer rotation-quality stats from the fused
rotate+quantize site (the R̃₃ → W_down path): blockwise ℓ1 mass
imbalance before/after the online rotation, int4 code saturation rate,
and pre/post-rotation kurtosis (`serve.telemetry.quality`). The probe
math reads barrier-isolated copies of the main path's values, so the
serving arithmetic — and hence every sampled token — is bit-identical
with probes on or off; the engine samples it every K decode dispatches.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.context import shard_act
from repro.kernels import ops as kops
from repro.kernels.paged_attention import (ROWS, pack_pages, unpack_pages,
                                           zero_pages)
from repro.models import layers as L
from repro.models.config import ArchConfig
from repro.serve.telemetry.quality import activation_probe_stats

Params = dict[str, Any]

PROJ_NAMES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def pack_dense_params(params: Params, cfg: ArchConfig) -> Params:
    """Pack every per-layer projection; keep embeddings/norms/head bf16.

    Uses the shared `kernels.ops.pack_int4_weights` packer (vmapped over
    the layer axis) so the serving grid is identical to the fake-quant
    grid the PTQ pipeline produced.
    """
    L_ = params["layers"]
    # jit'd so the scale search's per-candidate round trips fuse instead
    # of materializing [7, n_layers, K, N] f32 intermediates on device
    pack = jax.jit(jax.vmap(kops.pack_int4_weights))
    out = {
        "embed": params["embed"],
        "final_norm": params["final_norm"],
        "lm_head": params["lm_head"],
        "layers": {
            "attn_norm": L_["attn_norm"],
            "ffn_norm": L_["ffn_norm"],
        },
    }
    packed_attn = {}
    for name in ("wq", "wk", "wv", "wo"):
        packed_attn[name] = pack(L_["attn"][name])
    for bias in ("bq", "bk", "bv"):
        if bias in L_["attn"]:
            packed_attn[bias] = L_["attn"][bias]
    out["layers"]["attn"] = packed_attn
    packed_ffn = {}
    for name in ("w_gate", "w_up", "w_down"):
        if name in L_["ffn"]:
            packed_ffn[name] = pack(L_["ffn"][name])
    out["layers"]["ffn"] = packed_ffn
    return out


def _int_linear(x: jnp.ndarray, packed: Params, *, bits: int = 4):
    """x [..., K] float → int4 quantize per token → integer GEMM → float."""
    codes, s, z = kops.quantize_act(x, bits)
    y = kops.int4_matmul(codes, s, z, packed["packed"], packed["scale"])
    return y.astype(x.dtype)


def _rot_int_linear(h: jnp.ndarray, packed: Params, block_size: int):
    """Online block rotation fused with quantization, then integer GEMM
    (the R̃₃ → Q_A → W_down path of Figure 7). Also returns the activation
    codes so the quality probes can read the saturation the main path
    actually dispatched."""
    codes, s, z = kops.hadamard_quant(h, block_size, bits=4)
    y = kops.int4_matmul(codes, s, z, packed["packed"], packed["scale"])
    return y.astype(h.dtype), codes


class QuantizedDenseLM:
    """Integer-arithmetic decode for dense-family configs.

    Built from a PTQ result: `pack_dense_params(ptq.params, cfg)`. Matches
    the fake-quant model's outputs up to activation-quant rounding ties.
    `decode_step` and `prefill` are jit'd end-to-end; the kernels-enabled
    flag is captured per trace, so toggling `ops.use_kernels` transparently
    switches between the Pallas and reference compiled paths.
    """

    def __init__(self, cfg: ArchConfig, *, block_size: int = 32,
                 kv_bits: int | None = None):
        if cfg.family not in ("dense", "vlm"):
            raise ValueError("integer serving path covers dense archs")
        self.cfg = cfg.validate()
        self.block_size = block_size
        # kv_bits=4 → int4 KV cache with asymmetric per-(position, head)
        # scales: cache HBM traffic drops ~3.6× vs bf16 at head_dim 128
        # (the dominant decode byte stream at 32k context — §Perf cell
        # C3). None → bf16 cache.
        self.kv_bits = kv_bits
        self.attn_spec = L.AttnSpec(
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, causal=True, rope_theta=cfg.rope_theta,
            qkv_bias=cfg.qkv_bias)
        # scale granularity: one (scale, zero) pair per (position, head) —
        # KIVI-style. Sub-head groups (e.g. 8) look finer-grained but pair
        # a head's outlier channel with only 7 small neighbours, so the
        # group range is outlier-set while the code budget stays 8 wide;
        # head-wide asymmetric min/max tracks the fake-quant path strictly
        # better on the outlier-injected serving tests.
        self.kv_group = cfg.head_dim
        self._jit_cache: dict = {}

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        """The KV cache as a page pool in the tile-dense page format of
        `kernels.paged_attention`, stacked over layers: "kv_rows"
        [n_layers, batch, max_len·KH, 2·Dh] (int8 codes with `kv_bits`,
        else `dtype` K|V rows) plus, for integer caches, "kv_scale_zero"
        [n_layers, batch, 4, max_len·KH] f32. The engine builds its pool
        as `init_cache(n_pages, page_size)`; a dense cache is the same
        pool with one `max_len`-row page per batch row (`_forward`
        addresses it through the identity block table)."""
        quant = self.kv_bits is not None
        return zero_pages((self.cfg.n_layers, batch, max_len,
                           self.cfg.n_kv_heads, self.cfg.head_dim),
                          jnp.int8 if quant else dtype, scale_zero=quant)

    def _quantize_kv(self, x):
        """Asymmetric per-(position, head) KV quantization of x [..., Dh]
        → (codes int8 [..., Dh], scale f32 [...], zero f32 [...]). Codes
        are stored offset by 2^(bits-1) so the unsigned range fits the
        int8 cache buffer at kv_bits=8."""
        bits = self.kv_bits
        levels = 2 ** bits - 1
        off = 2 ** (bits - 1)
        xf = x.astype(jnp.float32)
        mn = jnp.min(xf, -1, keepdims=True)
        mx = jnp.max(xf, -1, keepdims=True)
        # floor keeps zero-range heads from dividing by 0 while leaving
        # the zero-point small enough for exact f32 arithmetic
        s = jnp.maximum((mx - mn) / levels, 1e-6)
        z = jnp.round(mn / s)
        codes = jnp.clip(jnp.round(xf / s) - z, 0, levels)
        return (codes - off).astype(jnp.int8), s[..., 0], z[..., 0]

    def _paged_cache_write(self, cache, k, v, layer, positions,
                           block_table):
        """Scatter new rows k/v [B, S, KH, Dh] (integer caches: quantized
        here, K pre-RoPE) straight into their pages of layer `layer`:
        row (b, s) lands in place at [layer, block_table[b, pos // T],
        pos % T] of every pool leaf, so a layer loop that carries the pool
        never copies it."""
        ids = block_table + layer * cache[ROWS].shape[1]   # [layer, page]
        if self.kv_bits is None:
            new = pack_pages(k[:, :, None], v[:, :, None])
        else:
            kq, ks, kz = self._quantize_kv(k)
            vq, vs, vz = self._quantize_kv(v)
            new = pack_pages(kq[:, :, None], vq[:, :, None],
                             tuple(a[:, :, None] for a in (ks, kz, vs, vz)))
        return L.paged_write_rows(cache, new, ids, positions)

    def _cache_read(self, cache):
        """Dequantize a dense cache (pages of `max_len` rows, any leading
        axes) → (K, V) [..., B, max_len, KH, Dh]; K is still pre-RoPE for
        integer caches."""
        k, v, aux = unpack_pages(cache, self.cfg.n_kv_heads)
        if self.kv_bits is None:
            return k, v
        off = 2 ** (self.kv_bits - 1)
        ks, kz, vs, vz = (a[..., None] for a in aux)

        def dq(codes, scale, zero):
            return scale * (codes.astype(jnp.float32) + off + zero)

        return dq(k, ks, kz), dq(v, vs, vz)

    def _block(self, x, blk, cache, layer, index, block_table=None,
               seq_lengths=None, probe=False):
        cfg = self.cfg
        spec = self.attn_spec
        b, s, d = x.shape
        h_, kv, dh = spec.n_heads, spec.n_kv_heads, spec.head_dim

        hx = L.apply_norm(x, blk["attn_norm"], cfg.norm)
        q = _int_linear(hx, blk["attn"]["wq"])
        k = _int_linear(hx, blk["attn"]["wk"])
        v = _int_linear(hx, blk["attn"]["wv"])
        if spec.qkv_bias:
            q = q + blk["attn"]["bq"]
            k = k + blk["attn"]["bk"]
            v = v + blk["attn"]["bv"]
        q = q.reshape(b, s, h_, dh)
        k = k.reshape(b, s, kv, dh)
        v = v.reshape(b, s, kv, dh)
        # index may be a scalar (lockstep batch / prefill chunk) or [B]
        # (per-slot fill positions from the continuous-batching engine)
        per_slot = jnp.ndim(index) == 1
        if per_slot and s != 1:
            raise ValueError("per-slot cache_index requires q_len == 1")
        base = index[:, None] if per_slot else jnp.reshape(index, (1, 1))
        pos = jnp.broadcast_to(jnp.arange(s)[None, :] + base, (b, s))
        q = L.apply_rope(q, pos, spec.rope_theta)
        if self.kv_bits is None:
            # bf16 cache: rotate only the new rows, store post-RoPE
            k = L.apply_rope(k, pos, spec.rope_theta)
        if block_table is not None:
            # block-table-native: scatter the new rows into their pages and
            # walk the table in the kernel (in-kernel dequant + pre-RoPE K
            # rotation for the integer page formats)
            new_cache = self._paged_cache_write(cache, k, v, layer, pos,
                                                block_table)
            attn = kops.paged_attention(
                q, new_cache, block_table, pos, seq_lengths, layer,
                kv_heads=kv,
                rope_theta=spec.rope_theta if self.kv_bits is not None
                else None,
                kv_bits=self.kv_bits,
                kv_group=self.kv_group if self.kv_bits is not None else None)
            attn = attn.reshape(b, s, h_ * dh).astype(x.dtype)
        else:
            # a dense cache is a pool of one page per batch row
            rows = jnp.arange(b, dtype=jnp.int32)[:, None]
            new_cache = self._paged_cache_write(cache, k, v, layer, pos,
                                                rows)
            k_all, v_all = self._cache_read(
                jax.tree.map(lambda a: a[layer], new_cache))
            s_k = k_all.shape[1]
            if self.kv_bits is not None:
                # integer cache holds pre-RoPE K: rotate after dequant
                all_pos = jnp.broadcast_to(jnp.arange(s_k)[None], (b, s_k))
                k_all = L.apply_rope(k_all.astype(jnp.float32), all_pos,
                                     spec.rope_theta)
            # causal per-query validity: the query at position p sees keys
            # ≤ p (per-row positions when `index` is per-slot)
            valid = jnp.arange(s_k)[None, None, :] <= pos[:, :, None]
            g = h_ // kv
            qg = q.reshape(b, s, kv, g, dh)
            logits = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                                k_all.astype(jnp.float32)) / math.sqrt(dh)
            logits = jnp.where(valid[:, None, None, :, :], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1)
            attn = jnp.einsum("bkgqs,bskd->bqkgd", probs,
                              v_all.astype(jnp.float32))
            attn = attn.reshape(b, s, h_ * dh).astype(x.dtype)
        x = x + _int_linear(attn, blk["attn"]["wo"])

        hx = L.apply_norm(x, blk["ffn_norm"], cfg.norm)
        if "w_gate" in blk["ffn"]:
            hid = jax.nn.silu(_int_linear(hx, blk["ffn"]["w_gate"])) \
                * _int_linear(hx, blk["ffn"]["w_up"])
        else:
            hid = jax.nn.gelu(_int_linear(hx, blk["ffn"]["w_up"]))
        hid = shard_act(hid, ("batch", "seq", "mlp"))
        down, act_codes = _rot_int_linear(hid, blk["ffn"]["w_down"],
                                          self.block_size)
        x = x + down
        stats = None
        if probe:
            # rotation-quality probe on the paper's fused rotate+quantize
            # site: barrier-isolated reads of the main path's values (the
            # rotated activation is recomputed from a barriered copy — the
            # fused kernel never materializes it), so the probe cannot
            # perturb serving arithmetic
            hid_p = jax.lax.optimization_barrier(hid.astype(jnp.float32))
            post = kops.block_hadamard(hid_p, self.block_size)
            stats = activation_probe_stats(hid_p, post, act_codes, bits=4,
                                           block_size=self.block_size)
        return x, new_cache, stats

    def _forward(self, params: Params, tokens: jnp.ndarray, cache: Params,
                 index: jnp.ndarray, block_table=None, seq_lengths=None,
                 probe=False):
        cfg = self.cfg
        cdt = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
        x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
        x = shard_act(x, ("batch", "seq", "embed"))

        # the cache rides in the carry, written in place at [layer, page,
        # slot] and read by the kernel through the layer index: scanning
        # it as xs/ys would slice each layer out and stack it back
        def body(carry, inp):
            x, c = carry
            blk, layer = inp
            x, c, stats = self._block(x, blk, c, layer, index, block_table,
                                      seq_lengths, probe)
            return (x, c), stats

        layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        (x, new_cache), stats = jax.lax.scan(body, (x, cache),
                                             (params["layers"], layers))
        x = L.apply_norm(x, params["final_norm"], cfg.norm)
        logits = x @ params["lm_head"].astype(x.dtype)
        if probe:
            # stats: dict of [n_layers] arrays (scan-stacked per-layer
            # probe scalars)
            return logits, new_cache, stats
        return logits, new_cache

    def _jitted(self, name, impl, probe=False):
        """jit `impl` once per (entry point, kernels-enabled, probe)
        triple; the kernels flag is re-pinned inside the traced body so
        retraces (new shapes) keep the path they were requested under,
        and the probe variant is a separate executable whose extra
        outputs never touch the non-probe path's jit cache."""
        key = (name, kops.kernels_enabled(), probe)
        fn = self._jit_cache.get(key)
        if fn is None:
            enabled = key[1]

            def wrapped(params, tokens, cache, index, block_table=None,
                        seq_lengths=None):
                with kops.use_kernels(enabled):
                    return impl(params, tokens, cache, index, block_table,
                                seq_lengths, probe)

            fn = self._jit_cache[key] = jax.jit(wrapped)
        return fn

    def forward_chunk(self, params: Params, tokens: jnp.ndarray,
                      cache: Params, index: jnp.ndarray,
                      block_table: jnp.ndarray | None = None,
                      seq_lengths: jnp.ndarray | None = None, *,
                      probe: bool = False):
        """Token chunk [B, S] at fill position `index` → per-position
        logits [B, S, V] + updated cache. S == 1 with a [B] vector index
        is a per-slot continuous-batching decode step; S > 1 with a
        scalar index is one chunk of a chunked prefill (causal within
        the chunk, attending to everything already cached). With
        `block_table` [B, P] the cache is the engine's page pool and
        attention runs block-table-native; `seq_lengths` [B] feed the
        paged kernel's ragged early-exit. `probe=True` additionally
        returns per-layer rotation-quality stats (see module docstring);
        the main outputs are bit-identical either way."""
        return self._jitted("forward", self._forward, probe)(
            params, tokens, cache, jnp.asarray(index, jnp.int32),
            block_table, seq_lengths)

    def decode_step(self, params: Params, tokens: jnp.ndarray,
                    cache: Params, index: jnp.ndarray):
        """One decode step for [B, 1] tokens at fill position `index`
        (scalar, or [B] per-slot fill positions)."""
        logits, new_cache = self.forward_chunk(params, tokens, cache, index)
        return logits[:, 0], new_cache

    def prefill(self, params: Params, tokens: jnp.ndarray, cache: Params):
        """Process a [B, S] prompt from position 0 (causal within the
        block); returns per-position logits [B, S, V] and the filled
        cache — decode then continues at index S."""
        return self._jitted("forward", self._forward)(
            params, tokens, cache, jnp.asarray(0, jnp.int32))
