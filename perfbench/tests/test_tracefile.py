"""The trace reduction: against a hand-made trace, and against a slice of
a trace recorded on a TPU v5e (`data/trace_sample.json`)."""
import json
from pathlib import Path

import pytest

from bench import tracefile

DATA = Path(__file__).resolve().parent / "data" / "trace_sample.json"


def test_reduce_hand_made():
    ex = {"devices": [{"plane": "/device:TPU:0", "events": [
        ["fusion.1", 0, 100, False],
        ["int4_matmul.2", 50, 100, True],      # overlaps: union 0..150
        ["fusion.3", 300, 50, False],          # gap 150..300 (mid 225)
        ["int4_matmul.4", 400, 100, True],     # gap 350..400 (mid 375)
        ["while.5", 390, 120, False],          # a container: not in ops
    ]}], "host": [["bench.step", 0, 250, ], ["bench.idle", 360, 30]],
        "lines": {}}
    r = tracefile.reduce(ex)
    assert r["busy_s"] == pytest.approx(320e-9)
    assert r["kernel_s"] == pytest.approx(200e-9)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.step": 150e-9, "bench.idle": 40e-9})
    assert dict(r["ops"])["int4_matmul"] == pytest.approx(200e-9)
    assert "while" not in dict(r["ops"])


@pytest.mark.skipif(not DATA.exists(), reason="no recorded trace slice")
def test_reduce_recorded_slice():
    ex = json.loads(DATA.read_text())
    r = tracefile.reduce(ex)
    ev = ex["devices"][0]["events"]
    span = (max(e[1] + e[2] for e in ev) - min(e[1] for e in ev)) / 1e9
    assert 0 < r["kernel_s"] <= r["busy_s"] <= span * (1 + 1e-9)
    assert any(is_k for *_, is_k in ev) and not all(is_k for *_, is_k in ev)
    assert r["idle_gaps"] and r["idle_gaps"][0][1] > 0
    # the Pallas kernels are the ops whose custom-call target is
    # tpu_custom_call, and only those (AllocateBuffer and ConcatBitcast
    # custom calls are not kernels)
    for name, _, _, is_k in ev:
        assert tracefile._is_kernel(name, {}) == is_k
        assert is_k == ('custom_call_target="tpu_custom_call"' in name)
    ops = dict(r["ops"])
    assert {"paged_attention", "int4_matmul"} <= set(ops)
    assert "while" not in ops
