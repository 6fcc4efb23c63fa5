"""Compile one prefill chunk and one decode step of a configuration at its
real size for a described TPU v5e, without a chip (nothing runs):

    JAX_PLATFORMS=cpu python3 perfbench/tools/compile_check.py \\
        perfbench/configs/granite-3-2b.json [prefill rows]

Prints each program's memory analysis; raises what the chip's compiler
would raise. These are the model's forward alone: the engine's fused step
program also holds a second copy of the page pool, in another layout, as
a temporary, which this does not see (size the pool on the chip)."""
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import spec  # noqa: E402


def main(path, chunk=None):
    from repro.kernels import ops as kops
    jax.config.update("jax_enable_compilation_cache", False)
    kops._on_tpu = lambda: True
    config = spec.load_json(Path(path))
    model = spec.load_model(config)
    dims = model.dims_of(config)
    e = config["engine"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    params = sds(jax.eval_shape(lambda: model.make_params(0, dims)))
    sv = model.servable(config, dims, params)
    b = e["max_seqs"]
    state = sds(jax.eval_shape(lambda: sv.init_state(
        e["n_pages"], e["page_size"], b)))
    i32 = jnp.int32
    for name, (tok, idx, bt, lens) in {
        "prefill": ((1, chunk or e["prefill_chunk"]), (), (1, 64), (1,)),
        "decode": ((b, 1), (b,), (b, 64), (b,)),
    }.items():
        args = (params, jax.ShapeDtypeStruct(tok, i32, sharding=one), state,
                jax.ShapeDtypeStruct(idx, i32, sharding=one),
                jax.ShapeDtypeStruct(bt, i32, sharding=one),
                jax.ShapeDtypeStruct(lens, i32, sharding=one))
        t = time.time()
        comp = jax.jit(sv.forward_chunk, donate_argnums=(2,)).lower(
            *args).compile()
        ma = comp.memory_analysis()
        print(f"{name}: compiled in {time.time() - t:.1f}s; args "
              f"{ma.argument_size_in_bytes} B "
              f"({ma.argument_size_in_bytes / 2**30:.2f} GiB), temp "
              f"{ma.temp_size_in_bytes} B "
              f"({ma.temp_size_in_bytes / 2**30:.2f} GiB), custom calls "
              f"{comp.as_text().count('tpu_custom_call')}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1], *(int(a) for a in sys.argv[2:3]))
