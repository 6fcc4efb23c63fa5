"""Find a cell's files by name: `BENCHMARK.json` holds the cells and the
metric entries; a configuration is `perfbench/configs/<config>.json`, a
traffic mix `perfbench/traffic/<traffic>.json`, a per-layer metric's
reader `perfbench/readers/<name>.py` (or, for a suffixed entry such as
`kernels_roofline.online`, the reader of its base name
`kernels_roofline.py`)."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
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
    traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, config_name=w["config"], traffic_name=w["traffic"],
                chips=w["chips"], config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def load_reader(metric_name: str, readers_dir: Path = BENCH_DIR / "readers"):
    """The reader module of a per-layer metric: `<name>.py`, else the file
    of the name before its first dot. Each module has `read(rec) ->
    float | None` (None: nothing to read, so the metric is left out)."""
    for stem in (metric_name, metric_name.split(".")[0]):
        path = readers_dir / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"perfbench_reader_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no reader for per-layer metric {metric_name!r}")
