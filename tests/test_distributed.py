"""Distribution-layer tests: sharding-rule unit tests (mesh-free) plus a
subprocess smoke of the real dry-run machinery on an 8-device host mesh
(device count must be set before jax initializes, hence the subprocess).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_param_spec_rules():
    """Spec rules are pure functions of (name, ndim) — verify key layouts
    without touching jax device state (mesh mocked)."""
    from repro.distributed import shardings as SH

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((4, 2))

    mesh = FakeMesh()
    assert tuple(SH.param_spec("embed", 2, mesh, zero3=False)) == \
        ("model", None)
    assert tuple(SH.param_spec("layers/attn/wq", 3, mesh, zero3=False)) == \
        (None, None, "model")
    assert tuple(SH.param_spec("layers/attn/wo", 3, mesh, zero3=False)) == \
        (None, "model", None)
    assert tuple(SH.param_spec("layers/attn/wq", 3, mesh, zero3=True)) == \
        (None, "data", "model")
    # MoE expert weights: experts on model, ZeRO dim on data
    assert tuple(SH.param_spec("layers/moe/w_gate", 4, mesh, zero3=True)) \
        == (None, "model", "data", None)
    assert tuple(SH.param_spec("layers/norm/scale", 2, mesh,
                               zero3=True)) == (None, None)


def test_fit_replicates_nondivisible():
    from jax.sharding import PartitionSpec as P
    from repro.distributed import shardings as SH

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((4, 2))

    mesh = FakeMesh()
    spec = SH._fit(P("model", "data"), (7, 8), mesh)   # 7 % 2 != 0
    assert tuple(spec) == (None, "data")


def test_cache_spec_seq_fallback():
    from repro.distributed import shardings as SH

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((4, 16))

    mesh = FakeMesh()
    # kv heads 8 % 16 != 0 → sequence-sharded cache
    spec = SH.cache_spec("layers/k", (16, 8, 32768, 8, 64), mesh)
    assert tuple(spec) == (None, "data", "model", None, None)
    # kv heads 32 % 16 == 0 → head-sharded cache
    spec = SH.cache_spec("layers/k", (16, 8, 32768, 32, 64), mesh)
    assert tuple(spec) == (None, "data", None, "model", None)


SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.configs.registry import get_config
    from repro.distributed import shardings as SH
    from repro.distributed.context import mesh_context
    from repro.models.transformer import build_model
    from repro.optim import adamw
    from repro.train.step import TrainConfig, make_train_step

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config("llama3-1b").reduced(n_layers=2, d_model=64, vocab=128,
                                          n_heads=4, n_kv_heads=2,
                                          head_dim=16, d_ff=128)
    model = build_model(cfg)
    with mesh_context(mesh):
        params = model.init(jax.random.PRNGKey(0))
        pshard = SH.param_shardings(mesh, params, cfg.name)
        params = jax.tree.map(jax.device_put, params, pshard)
        opt_cfg = adamw.AdamWConfig(lr=1e-3)
        opt = adamw.init_state(opt_cfg, params)
        oshard = SH.opt_state_shardings(mesh, opt, params, cfg.name)
        opt = jax.device_put(opt, oshard) if False else jax.tree.map(
            jax.device_put, opt,
            {"step": oshard["step"], "m": oshard["m"], "v": oshard["v"]})
        batch = {
            "tokens": jnp.zeros((8, 16), jnp.int32),
            "labels": jnp.zeros((8, 16), jnp.int32),
        }
        bshard = SH.batch_shardings(mesh, batch)
        batch = jax.tree.map(jax.device_put, batch, bshard)
        step = jax.jit(make_train_step(model, opt_cfg,
                                       TrainConfig(num_microbatches=2,
                                                   remat=True),
                                       param_shardings=pshard),
                       in_shardings=(pshard, oshard, bshard),
                       out_shardings=(pshard, oshard, None))
        params, opt, metrics = step(params, opt, batch)
        loss1 = float(metrics["loss"])
        params, opt, metrics = step(params, opt, batch)
        print(json.dumps({"loss1": loss1, "loss2": float(metrics["loss"]),
                          "n_dev": jax.device_count()}))
""")


@pytest.mark.slow
def test_sharded_train_step_executes_on_8_devices():
    """Actually EXECUTE (not just compile) a sharded, microbatched,
    rematerialized train step on 8 host devices."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_dev"] == 8
    assert np.isfinite(rec["loss1"]) and np.isfinite(rec["loss2"])
    assert rec["loss2"] < rec["loss1"] + 1.0  # sane optimization step
