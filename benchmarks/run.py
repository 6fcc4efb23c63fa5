"""Benchmark runner: one harness per paper table/figure + kernel timings.

Prints ``name,us_per_call,derived`` CSV rows per harness, then each
harness's own table output. A harness that raises gets a ``fail:`` row,
the others still run, and the runner exits 1.
"""
from __future__ import annotations

import io
import sys
import time
from contextlib import redirect_stdout


def _run(name, fn, *args):
    buf = io.StringIO()
    t0 = time.perf_counter()
    status = "ok"
    try:
        with redirect_stdout(buf):
            fn(*args)
    except Exception as e:  # noqa: BLE001
        status = f"fail:{type(e).__name__}"
        buf.write(f"\nERROR {e}\n")
    dt_us = (time.perf_counter() - t0) * 1e6
    print(f"{name},{dt_us:.0f},{status}")
    return name, buf.getvalue(), status == "ok"


def main() -> int:
    from . import (fig3_delta, fig45_bounds, massdiff_speed,
                   table1_blocksize, table2_formats, table34_opcounts,
                   table6_permutations)
    from .kernel_bench import main as kernel_main

    jobs = [
        ("table34_opcounts", table34_opcounts.main),
        ("massdiff_speed", massdiff_speed.main),
        ("fig3_delta", fig3_delta.main),
        ("fig45_bounds", fig45_bounds.main),
        ("table1_blocksize_qronos", table1_blocksize.main, []),
        ("table1_blocksize_rtn", table1_blocksize.main,
         ["--rounding", "rtn"]),
        ("table6_permutations", table6_permutations.main),
        ("table2_formats", table2_formats.main),
        ("kernel_bench", kernel_main),
    ]
    print("name,us_per_call,derived")
    outputs = []
    for job in jobs:
        name, fn, *rest = job
        outputs.append(_run(name, fn, *rest))
    print()
    for name, text, _ in outputs:
        print(f"===== {name} =====")
        print(text)
    return 0 if all(ok for _, _, ok in outputs) else 1


if __name__ == "__main__":
    sys.exit(main())
