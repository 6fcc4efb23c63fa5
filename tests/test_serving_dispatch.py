"""Kernel-vs-reference parity of the dispatched serving path.

`QuantizedDenseLM` routes every online op through `repro.kernels.ops`;
with kernels enabled that is the Pallas path (interpret mode on CPU), with
`use_kernels(False)` the plain-XLA reference path. Both compute the same
arithmetic — the rotation as a dot against the block-diagonal operand, the
quantizers and integer GEMM bit-identically — so prefill and decode must
match *bit for bit* on a smoke config, int codes and float epilogues alike.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels import ops as kops
from repro.kernels.paged_attention import pack_pages, unpack_pages
from repro.models.transformer import build_model
from repro.serve.quantized import QuantizedDenseLM, pack_dense_params

TOKENS = [3, 14, 15, 92, 6]


@pytest.fixture(scope="module")
def packed_setup():
    cfg = get_config("llama3-1b").reduced()   # 2-layer smoke config
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, pack_dense_params(params, cfg)


def _run(qlm, packed, *, kernels: bool):
    with kops.use_kernels(kernels):
        cache = qlm.init_cache(1, 16)
        pre, cache = qlm.prefill(
            packed, jnp.asarray([TOKENS[:3]], jnp.int32), cache)
        dec = []
        for j, t in enumerate(TOKENS[3:]):
            logits, cache = qlm.decode_step(
                packed, jnp.asarray([[t]], jnp.int32), cache,
                jnp.asarray(3 + j, jnp.int32))
            dec.append(np.asarray(logits))
        return np.asarray(pre), np.stack(dec), cache


@pytest.mark.parametrize("kv_bits", [None, 8, 4])
def test_dispatched_path_matches_reference_bitwise(packed_setup, kv_bits):
    cfg, packed = packed_setup
    qlm = QuantizedDenseLM(cfg, block_size=16, kv_bits=kv_bits)
    pre_k, dec_k, cache_k = _run(qlm, packed, kernels=True)
    pre_r, dec_r, cache_r = _run(qlm, packed, kernels=False)
    np.testing.assert_array_equal(pre_k, pre_r)
    np.testing.assert_array_equal(dec_k, dec_r)
    # the cache state (including integer codes for kv_bits) matches too
    for (pk, lk), (pr, lr) in zip(
            jax.tree_util.tree_leaves_with_path(cache_k),
            jax.tree_util.tree_leaves_with_path(cache_r)):
        assert pk == pr
        np.testing.assert_array_equal(np.asarray(lk), np.asarray(lr))


@pytest.mark.parametrize("kv_bits", [None, 8])
def test_dispatched_path_matches_reference_bitwise_bf16(kv_bits):
    """The same contract on the paged path at the bf16 compute dtype of
    the published configs. XLA may skip the bf16 rounding of a value it
    fuses into an f32 consumer (excess precision), which a kernel operand
    never skips, so both paths compile with that liberty off — as
    `chip_smoke.py` compares them on the chip."""
    cfg = get_config("llama3-1b").reduced(param_dtype="bfloat16",
                                          compute_dtype="bfloat16")
    packed = pack_dense_params(
        build_model(cfg).init(jax.random.PRNGKey(0)), cfg)
    qlm = QuantizedDenseLM(cfg, block_size=16, kv_bits=kv_bits)
    args = (packed, jnp.asarray([TOKENS], jnp.int32), qlm.init_cache(2, 16),
            jnp.asarray(0, jnp.int32), jnp.asarray([[1]], jnp.int32),
            jnp.asarray([len(TOKENS)], jnp.int32))
    outs = {}
    for enabled in (True, False):
        with kops.use_kernels(enabled):
            fwd = jax.jit(qlm.forward_chunk).lower(*args).compile(
                compiler_options={"xla_allow_excess_precision": False})
            outs[enabled] = np.asarray(fwd(*args)[0])
    np.testing.assert_array_equal(outs[True], outs[False])


def test_prefill_matches_stepwise_decode(packed_setup):
    """Causal prefill must produce the same per-position logits and cache
    as feeding the prompt token by token.

    Not bit for bit: prefill multiplies S-row activations where decode
    multiplies one row, and XLA:CPU sums the f32 contraction of an M-row
    and a 1-row matmul in different orders (a plain `x @ w` differs in
    most elements between the two), a few ulp apart. 1e-5 is two orders
    above that and three below bf16 rounding of the same logits."""
    cfg, packed = packed_setup
    qlm = QuantizedDenseLM(cfg, block_size=16)
    cache = qlm.init_cache(1, 16)
    pre, _ = qlm.prefill(packed, jnp.asarray([TOKENS], jnp.int32), cache)
    cache = qlm.init_cache(1, 16)
    for i, t in enumerate(TOKENS):
        step, cache = qlm.decode_step(
            packed, jnp.asarray([[t]], jnp.int32), cache,
            jnp.asarray(i, jnp.int32))
        np.testing.assert_allclose(np.asarray(pre[:, i]), np.asarray(step),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kv_bits", [None, 8, 4])
def test_paged_attention_dispatch_matches_reference_bitwise(kv_bits):
    """`ops.paged_attention` under kernels (interpret mode here) and under
    `use_kernels(False)` must agree bit for bit — the reference replays
    the kernel's exact page walk (same shared helpers, same op order), the
    contract the engine's kernels-on/off equivalence test builds on."""
    rng = np.random.default_rng(3)
    b, s, kh, g, dh, t, n_cols, n_pages = 2, 4, 2, 2, 32, 8, 3, 7
    q = jnp.asarray(rng.standard_normal((b, s, kh * g, dh)), jnp.float32)
    bt = jnp.asarray(rng.permutation(np.arange(1, n_pages))[:b * n_cols]
                     .reshape(b, n_cols), jnp.int32)
    qpos = jnp.asarray([[9 + j for j in range(s)],
                        [14 + j for j in range(s)]], jnp.int32)
    shape = (1, n_pages, t, kh, dh)
    if kv_bits is None:
        kv = pack_pages(jnp.asarray(rng.standard_normal(shape), jnp.float32),
                        jnp.asarray(rng.standard_normal(shape), jnp.float32))
    else:
        off, levels = 2 ** (kv_bits - 1), 2 ** kv_bits - 1

        def codes():
            return jnp.asarray(rng.integers(0, levels + 1, shape) - off,
                               jnp.int8)

        def aux(lo, hi, rounded=False):
            a = rng.uniform(lo, hi, shape[:-1])
            return jnp.asarray(np.round(a) if rounded else a, jnp.float32)

        kv = pack_pages(codes(), codes(),
                        (aux(0.02, 0.2), aux(-12, 2, True), aux(0.02, 0.2),
                         aux(-12, 2, True)))
    outs = {}
    for enabled in (True, False):
        with kops.use_kernels(enabled):
            outs[enabled] = np.asarray(kops.paged_attention(
                q, kv, bt, qpos, kv_heads=kh, rope_theta=500000.0,
                kv_bits=kv_bits, kv_group=dh if kv_bits else None))
    np.testing.assert_array_equal(outs[True], outs[False])


def test_decode_uses_dispatch_not_ref():
    """The serving module must go through the ops dispatch layer only —
    no direct kernels.ref calls on the hot path."""
    import ast
    import inspect

    import repro.serve.quantized as SQ

    tree = ast.parse(inspect.getsource(SQ))
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                assert not a.name.endswith("kernels.ref"), \
                    "serve.quantized imports kernels.ref directly"
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            names = [a.name for a in node.names]
            assert not ("kernels" in mod and "ref" in names), \
                "serve.quantized imports kernels.ref directly"
            assert not mod.endswith("kernels.ref"), \
                "serve.quantized imports kernels.ref directly"
