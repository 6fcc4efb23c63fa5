"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
chip that is described rather than attached. Interpret mode cannot show
what these compiles do — Mosaic refuses block shapes off the (8, 128)
tiling, unaligned DMA slices, and operations it does not legalize — so
every kernel the W4A4 engine calls is compiled here at llama3-1b widths
(d_model 2048, d_ff 8192, 32 query / 8 KV heads of 64, page size 16), at
the engine's decode shape (4 rows, S = 1) and its 8-token prefill chunk.
The whole model forward is compiled too, at granite-3-2b widths, to show
that a step moves no pool-shaped data.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under several test workers each
worker imports this module. All the tests stay in this one file so the
worker that is given it is the only one that loads the library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_attention as PA
from repro.kernels.hadamard_quant import hadamard_quant
from repro.kernels.int4_matmul import int4_matmul

D, D_FF, H, KH, DH, PAGE = 2048, 8192, 32, 8, 64, 16
N_PAGES = 64
V5E_HBM_BYTES = 16 * 10**9       # one v5e chip (Google Cloud, "TPU v5e")
ROWS = {"decode": 4, "prefill": 8}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used <= V5E_HBM_BYTES, used


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("step", sorted(ROWS))
@pytest.mark.parametrize("k,n", [(D, D), (D, KH * DH), (D, D_FF),
                                 (D_FF, D)])
def test_int4_matmul_compiles(one_chip, step, k, n):
    m = ROWS[step]
    _compile(lambda a, s, z, w, ws: int4_matmul(a, s, z, w, ws,
                                                interpret=False),
             one_chip, _s((m, k), jnp.int8), _s((m, 1), jnp.float32),
             _s((m, 1), jnp.float32), _s((k // 2, n), jnp.uint8),
             _s((n,), jnp.float32))


@pytest.mark.parametrize("step", sorted(ROWS))
@pytest.mark.parametrize("d,b", [(D, 1), (D_FF, 16)])
def test_hadamard_quant_compiles(one_chip, step, d, b):
    _compile(lambda x: hadamard_quant(x, b, interpret=False), one_chip,
             _s((ROWS[step], d), jnp.bfloat16))


@pytest.mark.parametrize("kv_bits", [None, 8])
@pytest.mark.parametrize("b,s,cols", [(4, 1, 2), (4, 1, 8), (1, 8, 4)])
def test_paged_attention_compiles(one_chip, kv_bits, b, s, cols):
    """Decode at a short table (one walk) and a long one (split-K partials
    plus the LSE combine kernel), and one prefill chunk, over a two-layer
    pool in the page format, reading its second layer."""
    dtype = jnp.int8 if kv_bits else jnp.bfloat16
    kv = {PA.ROWS: _s((2, N_PAGES, PAGE * KH, 2 * DH), dtype)}
    if kv_bits:
        kv[PA.SCALE_ZERO] = _s((2, N_PAGES, 4, PAGE * KH), jnp.float32)
    _compile(lambda q, kv, bt, qpos, lens, layer: PA.paged_attention(
                 q, kv, bt, qpos, lens, layer, kv_heads=KH,
                 rope_theta=500_000.0 if kv_bits else None, kv_bits=kv_bits,
                 kv_group=DH if kv_bits else None, interpret=False),
             one_chip, _s((b, s, H, DH), jnp.bfloat16), kv,
             _s((b, cols), jnp.int32), _s((b, s), jnp.int32),
             _s((b,), jnp.int32), _s((), jnp.int32))


# The forward at granite-3-2b widths (d_model 2048, d_ff 8192, 32/8 heads
# of 64) with two layers. The page count shares no dim with any width, so
# a shape that holds it (or layers · pages) is the pool's; and one layer
# of the pool (288 MB) is too large to be staged whole in the chip's fast
# memory, as at the deployed size.
FWD_LAYERS, FWD_PAGES, FWD_VOCAB = 2, 16_400, 49_155
FORWARD = {"decode": (64, 1), "prefill": (1, 256)}
_MOVES = re.compile(r"\s*(?:ROOT )?%\S+ = (.+?) "
                    r"(copy|copy-start|slice|slice-start|dynamic-slice)\(")


def _pool_moves(hlo: str, n_layers: int, n_pages: int) -> list[str]:
    """Instructions that copy or slice pool-shaped data: a copy (sync or
    async), slice or dynamic slice with the page count, or the layer ·
    page count of a flattened leaf, among its dims. The page write is a
    dynamic-update-slice in place, not a move."""
    moves = []
    for line in hlo.splitlines():
        m = _MOVES.match(line)
        if m is None:
            continue
        dims = {int(d) for shape in re.findall(r"\[([\d,]*)\]", m.group(1))
                for d in shape.split(",") if d}
        if dims & {n_pages, n_layers * n_pages}:
            moves.append(line.strip()[:160])
    return moves


@pytest.mark.parametrize("step", sorted(FORWARD))
def test_forward_moves_no_pool(one_chip, monkeypatch, step):
    """The decode forward (64 rows, S = 1) and one 256-token prefill chunk
    of `QuantizedDenseLM` on int8 KV pages, with the pool donated and the
    real Mosaic kernels: the layer loop carries the pool, the rows are
    scattered in place and the kernel reads pages by (layer, page), so no
    instruction copies or slices the pool, and the program's temporaries
    stay below one layer of it."""
    from repro.kernels import ops as kops
    from repro.models.config import ArchConfig
    from repro.models.transformer import build_model
    from repro.serve.quantized import QuantizedDenseLM, pack_dense_params
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    cfg = ArchConfig(name="granite-widths", family="dense",
                     n_layers=FWD_LAYERS, d_model=D, vocab=FWD_VOCAB,
                     n_heads=H, n_kv_heads=KH, head_dim=DH, d_ff=D_FF,
                     act="silu", norm="rmsnorm", param_dtype="bfloat16",
                     compute_dtype="bfloat16")
    qlm = QuantizedDenseLM(cfg, block_size=16, kv_bits=8)
    params = jax.eval_shape(lambda: pack_dense_params(
        build_model(cfg).init(jax.random.PRNGKey(0)), cfg))
    pool = jax.eval_shape(lambda: qlm.init_cache(FWD_PAGES, PAGE))
    rows, s = FORWARD[step]
    index = (rows,) if s == 1 else ()
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (params, _s((rows, s), jnp.int32), pool, _s(index, jnp.int32),
         _s((rows, 64), jnp.int32), _s((rows,), jnp.int32)))
    compiled = jax.jit(qlm._forward, donate_argnums=(2,)).lower(
        *args).compile()
    hlo = compiled.as_text()
    assert "paged_attention" in hlo and "tpu_custom_call" in hlo
    assert _pool_moves(hlo, FWD_LAYERS, FWD_PAGES) == []
    layer_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(pool)) // FWD_LAYERS
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
