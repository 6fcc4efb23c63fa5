"""Run a cell once per seed, one process per run as the benchmark's
checks do, and keep each run's output under `chiprun_out/<cell>/`:

    python3 perfbench/tools/sets.py granite-3-2b.chat 30 11,12,13 [--trace 1]
        [--control 1] [--record chiprun_out/rec_{seed}.json]

This process never imports JAX, so each child has the chip to itself. A
run is stopped after 1300 s (the first, which compiles) or 420 s."""
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main():
    cell, seconds, seeds = sys.argv[1], sys.argv[2], sys.argv[3].split(",")
    extra = sys.argv[4:]
    out = ROOT / "chiprun_out" / cell
    out.mkdir(parents=True, exist_ok=True)
    for i, seed in enumerate(seeds):
        args = [a.replace("{seed}", seed) for a in extra]
        tag = "_".join([f"{i:02d}", seed] + [a.strip("-") for a in extra
                                               if "/" not in a])
        t = time.time()
        with open(out / f"{tag}.out", "w") as fo, \
                open(out / f"{tag}.err", "w") as fe:
            try:
                rc = subprocess.call(
                    [sys.executable, str(ROOT / "perfbench" / "run.py"),
                     "--workload", cell, "--seed", seed, "--seconds",
                     seconds, *args], stdout=fo, stderr=fe, cwd=ROOT,
                    timeout=1300 if i == 0 else 420)
            except subprocess.TimeoutExpired:
                rc = "timeout"
        print(f"{cell} seed {seed} {extra}: rc {rc}, "
              f"{time.time() - t:.0f}s", flush=True)


if __name__ == "__main__":
    main()
