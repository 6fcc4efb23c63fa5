"""Find a cell's files by name, under a checkout's root: `BENCHMARK.json`
holds the cells and the metric entries; a configuration is
`perfbench/configs/<config>.json`, its model module
`perfbench/models/<model_type>.py`, a traffic mix
`perfbench/traffic/<traffic>.json`, a per-layer metric's reader
`perfbench/readers/<name>.py` (or, for a suffixed entry such as
`kernels_roofline.online`, the reader of its base name
`kernels_roofline.py`)."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _dir(root: Path, kind: str) -> Path:
    return root / BENCH_DIR.name / kind


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(_dir(root, "traffic") / f"{w['traffic']}.json")
    return Cell(name=name, config_name=w["config"], traffic_name=w["traffic"],
                chips=w["chips"], config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def load_reader(metric_name: str, root: Path = ROOT):
    """The reader module of a per-layer metric: `<name>.py`, else the file
    of the name before its first dot. Each module has `read(rec) ->
    float | None` (None: nothing to read, so the metric is left out)."""
    for stem in (metric_name, metric_name.split(".")[0]):
        path = _dir(root, "readers") / f"{stem}.py"
        if path.exists():
            return _module(path, f"perfbench_reader_{stem.replace('.', '_')}")
    raise FileNotFoundError(f"no reader for per-layer metric {metric_name!r}")


_MODELS: dict[Path, object] = {}


def load_model(config: dict, root: Path = ROOT):
    """The model module that the configuration's `model.model_type` names,
    loaded once per file. It provides `dims_of(config)` (a hashable `Dims`
    with at least `vocab` and `n_layers`), `make_params(seed, dims)` (the
    served tree, on the device), `servable(config, dims, params)` (what
    `ServeEngine` takes), `forward(key, dims, streams, rows, *, mode,
    block, max_len)` (the float32 reference, `mode="fp8"` its control)
    and `step_work(step, dims, peaks, chunk)` (a `work.Work`)."""
    model_type = config["model"]["model_type"]
    path = _dir(root, "models") / f"{model_type}.py"
    if not path.exists():
        raise FileNotFoundError(f"no model module for model_type "
                                f"{model_type!r}: looked for {path}")
    if path not in _MODELS:
        _MODELS[path] = _module(path, f"perfbench_model_{model_type}"
                                .replace(".", "_").replace("-", "_"))
    return _MODELS[path]
