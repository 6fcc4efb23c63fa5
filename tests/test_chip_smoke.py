"""`chip_smoke.py` end to end at the 2-layer smoke width on the CPU.

The script itself refuses any device but a TPU; its `run` is the path it
drives there (the launcher's own build, quantize, engine and run steps
plus the kernel/reference comparison), checked here with interpret-mode
kernels.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402


def test_smoke_path_at_reduced_width(monkeypatch):
    # keep the persistent compile cache out of the test process
    monkeypatch.setattr(chip_smoke, "enable_compile_cache", lambda: "off")
    res = chip_smoke.run(reduced=True)
    assert len(res["outcomes"]) == res["n_requests"] == 8
    assert set(res["outcomes"].values()) <= {"length", "stop"}
    assert res["generated"] == {rid: 16 for rid in range(8)}
    assert res["prefix_hit_tokens"] == 32      # the shared two-page prefix
    assert all(res["tallies"].values()), res["tallies"]
    assert res["finite"] and res["poisoned"] == 0
    # interpret mode on the CPU: kernels and reference agree bit for bit,
    # and no Mosaic call is lowered
    assert res["gaps"] == {"prefill": 0.0, "decode": 0.0}
    assert not res["custom_call"]
