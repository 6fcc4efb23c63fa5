"""The reference computes the served model's arithmetic: with the program
switched to float32 activations (bfloat16 rounding gone) the two agree
to float32 rounding; in bfloat16, as served, they differ only by the
rounding that int4 activation codes amplify."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import spec
from bench.weights import seed_key

granite = spec.load_model(tiny.CONFIG)


def _served_logits(dtype):
    from repro.serve.engine import (EngineRequest, SamplingParams,
                                    ServeEngine, as_servable)
    from repro.serve.quantized import QuantizedDenseLM
    dims = granite.dims_of(tiny.CONFIG)
    params = granite.make_params(5, dims)
    cfg = dataclasses.replace(granite.arch(tiny.CONFIG, dims),
                              compute_dtype=dtype, param_dtype=dtype)
    if dtype == "float32":
        params = jax.tree.map(lambda a: a.astype(jnp.float32)
                              if a.dtype == jnp.bfloat16 else a, params)
    qlm = QuantizedDenseLM(cfg, block_size=16, kv_bits=8)
    eng = ServeEngine(as_servable(qlm, params), n_pages=64, page_size=16,
                      max_seqs=4, prefill_chunk=32, token_budget=40,
                      record_logits=True, max_context=256)
    prompt = np.random.default_rng(0).integers(0, 512, size=40).tolist()
    eng.submit(EngineRequest(rid=1, prompt=prompt,
                             sampling=SamplingParams(max_new=10)))
    r = eng.run()[0]
    ref = granite.forward(seed_key(5), dims, [prompt + r.generated],
                          [list(range(39, 49))])[0]
    return np.stack(r.step_logits), ref


def _rel_rms(a, b):
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


@pytest.mark.parametrize("dtype,limit", [("float32", 1e-4),
                                         ("bfloat16", 0.3)])
def test_reference_follows_the_program(dtype, limit):
    prog, ref = _served_logits(dtype)
    assert _rel_rms(prog, ref) < limit
