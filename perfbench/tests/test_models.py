"""Each configuration brings its model as a file: the configuration's
`model.model_type` names `perfbench/models/<model_type>.py`. The dense
model's code moved there (`granite.py`) with every number kept bit-equal
to digests recorded before the move (`data/pins_granite.json`): the
served params, the reference's logits in float32 and in its float8
control, and the work model's fields."""
import copy
import hashlib
import json
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

import run
import tiny
from bench import driver, spec, work
from bench.driver import StepRecord
from bench.weights import seed_key

PINS = json.loads((Path(__file__).resolve().parent / "data"
                   / "pins_granite.json").read_text())
granite = spec.load_model(tiny.CONFIG)
DIMS = granite.dims_of(tiny.CONFIG)


def _digest(a) -> str:
    a = np.asarray(a)
    h = hashlib.sha256(f"{a.dtype}{a.shape}".encode())
    h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", PINS["inputs"]["param_seeds"])
def test_params_are_pinned(seed):
    flat, _ = jax.tree_util.tree_flatten_with_path(
        granite.make_params(seed, DIMS))
    got = {jax.tree_util.keystr(k): _digest(v) for k, v in flat}
    assert got == PINS["params"][str(seed)]


@pytest.mark.parametrize("mode", ["f32", "fp8"])
def test_reference_logits_are_pinned(mode):
    i = PINS["inputs"]
    out = granite.forward(seed_key(i["ref_seed"]), DIMS, i["streams"],
                          i["rows"], mode=mode, block=i["block"],
                          max_len=i["max_len"])
    assert [_digest(o) for o in out] == PINS["logits"][mode]


def test_work_is_pinned():
    i = PINS["inputs"]
    pk = json.loads(work.PEAKS_FILE.read_text())[i["peaks"]]
    steps = [StepRecord(0.0, 1.0, dec, [tuple(p) for p in pre], 0)
             for dec, pre in i["steps"]]
    w = work.window_work(granite, steps, DIMS, pk, i["chunk"])
    got = {"int8_ops": w.int8_ops, "bf16_flops": w.bf16_flops,
           "dispatches": w.dispatches, "roof_s": w.roof_s,
           "kernels_roof_s": w.kernels_roof_s}
    assert got == PINS["work"]


def test_module_is_chosen_by_model_type():
    config = spec.load_json(spec.ROOT / "perfbench" / "configs"
                            / "granite-3-2b.json")
    assert config["model"]["model_type"] == "granite"
    mod = spec.load_model(config)
    assert mod is granite
    assert Path(mod.__file__) == spec.BENCH_DIR / "models" / "granite.py"


def test_unknown_model_type_names_the_file():
    config = {"model": {"model_type": "no_such_model"}}
    with pytest.raises(FileNotFoundError,
                       match=r"perfbench/models/no_such_model\.py"):
        spec.load_model(config)


TOY = '''"""A model type the harness has not seen: granite's functions under
another name, each call counted."""
import collections

from bench import spec

_base = spec.load_model({"model": {"model_type": "granite"}})
CALLS = collections.Counter()


def _counted(f):
    def call(*a, **k):
        CALLS[f.__name__] += 1
        return f(*a, **k)
    return call


dims_of, make_params, servable, forward, step_work = map(_counted, (
    _base.dims_of, _base.make_params, _base.servable, _base.forward,
    _base.step_work))
'''

READER = '''"""Engine batching: decode rows the window's steps served."""


def read(rec):
    return float(sum(len(s.decode_ctx) for s in rec.steps_in(*rec.window)))
'''


def test_toy_module_in_another_models_dir_is_picked_up(tmp_path):
    models = tmp_path / "perfbench" / "models"
    models.mkdir(parents=True)
    (models / "toy.py").write_text(TOY)
    mod = spec.load_model({"model": {"model_type": "toy"}}, tmp_path)
    assert Path(mod.__file__) == models / "toy.py"
    assert mod.dims_of(tiny.CONFIG) == DIMS
    assert spec.load_model({"model": {"model_type": "toy"}}, tmp_path) is mod


def test_new_model_type_is_only_new_files(tmp_path, monkeypatch, capsys):
    """A checkout whose benchmark gains a configuration of a new model
    type by new files alone (a model module, a configuration, a traffic
    mix, a reader, entries in BENCHMARK.json) runs that cell through the
    harness as it stands, and every call reaches the new module."""
    bench_dir = tmp_path / "perfbench"
    shutil.copytree(spec.BENCH_DIR, bench_dir, ignore=shutil.ignore_patterns(
        "tests", ".out", "__pycache__"))
    (tmp_path / "src").symlink_to(spec.ROOT / "src")
    (bench_dir / "models" / "toy.py").write_text(TOY)
    config = copy.deepcopy(tiny.CONFIG)
    config.update(name="toy")
    config["model"]["model_type"] = "toy"
    (bench_dir / "configs" / "toy.json").write_text(json.dumps(config))
    base = tiny.cell("backlog")
    (bench_dir / "traffic" / "toy_batch.json").write_text(
        json.dumps(base.traffic))
    (bench_dir / "readers" / "decode_rows.py").write_text(READER)
    bench = {
        "configs": [{"name": "toy", "file": "perfbench/configs/toy.json"}],
        "workloads": [{"name": "toy.batch", "config": "toy",
                       "traffic": "toy_batch", "chips": 1}],
        "end_to_end": [{"name": "output_tok_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "decode_rows.toy", "unit": "rows"},
                      {"name": "mfu.toy", "unit": "%"}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    use_cache = driver.use_checkout_cache
    monkeypatch.setattr(driver, "use_checkout_cache",
                        lambda root: use_cache(spec.ROOT))

    rc = run.main(["--workload", "toy.batch", "--seed", "3000000021",
                   "--seconds", "4", "--trace", "1"], require_tpu=False,
                  root=tmp_path)
    out, err = capsys.readouterr()
    assert rc == 0, err
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"], err
    assert res["metrics"]["decode_rows.toy"]["value"] > 0
    toy = spec.load_model(config, tmp_path)
    assert set(toy.CALLS) == {"dims_of", "make_params", "servable",
                              "forward", "step_work"}
