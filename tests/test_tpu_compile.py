"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
chip that is described rather than attached. Interpret mode cannot show
what these compiles do — Mosaic refuses block shapes off the (8, 128)
tiling, unaligned DMA slices, and operations it does not legalize — so
every kernel the W4A4 engine calls is compiled here at llama3-1b widths
(d_model 2048, d_ff 8192, 32 query / 8 KV heads of 64, page size 16), at
the engine's decode shape (4 rows, S = 1) and its 8-token prefill chunk.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under several test workers each
worker imports this module. All the tests stay in this one file so the
worker that is given it is the only one that loads the library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.hadamard_quant import hadamard_quant
from repro.kernels.int4_matmul import int4_matmul
from repro.kernels.paged_attention import paged_attention

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
    plus the LSE combine kernel), and one prefill chunk."""
    dtype = jnp.int8 if kv_bits else jnp.bfloat16
    kv = {"k": _s((N_PAGES, PAGE, KH, DH), dtype),
          "v": _s((N_PAGES, PAGE, KH, DH), dtype)}
    if kv_bits:
        for name in ("k_scale", "k_zero", "v_scale", "v_zero"):
            kv[name] = _s((N_PAGES, PAGE, KH, 1), jnp.float32)
    _compile(lambda q, kv, bt, qpos, lens: paged_attention(
                 q, kv, bt, qpos, lens,
                 rope_theta=500_000.0 if kv_bits else None, kv_bits=kv_bits,
                 kv_group=DH if kv_bits else None, interpret=False),
             one_chip, _s((b, s, H, DH), jnp.bfloat16), kv,
             _s((b, cols), jnp.int32), _s((b, s), jnp.int32),
             _s((b,), jnp.int32))
