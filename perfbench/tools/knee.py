"""Find the highest arrival rate an open-loop cell sustains: one process
builds and warms the engine once, then offers the cell's mix at each rate
for a window and reports whether the queue of requests waiting for
admission grew over it.

    python3 perfbench/tools/knee.py --workload granite-3-2b.chat \\
        --rates 1,2,3,4 --seconds 30 --seed 5
"""
import argparse
import copy
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from bench import driver, metrics, spec, traffic, warmup  # noqa: E402


def waiting(rec, t):
    return sum(1 for r in rec.reqs if r.submit is not None and r.submit <= t
               and (r.handle.t_admit is None or r.handle.t_admit > t))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    driver.use_checkout_cache()
    cfg, mix = cell.config, cell.traffic
    model = spec.load_model(cfg)
    dims = model.dims_of(cfg)
    eng = cfg["engine"]
    t0 = time.perf_counter()
    params = model.make_params(args.seed, dims)
    engine = driver.build_engine(cfg, model.servable(cfg, dims, params),
                                 args.seed)
    p_min, p_max, o_max = traffic.max_lengths(mix)
    plan = warmup.plan(p_min, p_max, o_max, chunk=eng["prefill_chunk"],
                       page=eng["page_size"])
    driver.run_warmup(engine, mix, plan, dims.vocab)
    print(f"setup {time.perf_counter() - t0:.1f}s", flush=True)
    knee = None
    for rate in [float(x) for x in args.rates.split(",")]:
        m = copy.deepcopy(mix)
        m.update(rate_rps=rate, grace_s=0)
        reqs = traffic.generate(m, args.seed, args.seconds, dims.vocab)
        rec = driver.drive(engine, reqs, m, args.seconds)
        w0, w1 = rec.window
        e2e = metrics.end_to_end(rec, time.perf_counter())
        ttft = metrics.ttft_s(rec, time.perf_counter())
        q = [waiting(rec, w0 + f * (w1 - w0)) for f in (0, 0.25, 0.5, 0.75,
                                                         1.0)]
        steps = rec.steps_in(w0, w1)
        rows = [len(s.decode_ctx) for s in steps if s.decode_ctx]
        print(json.dumps({
            "rate": rate, "ttft_p50_ms": metrics.pct(ttft, 50) * 1e3,
            "ttft_p90_ms": e2e["ttft_p90_ms"], "itl_p99_ms": e2e["itl_p99_ms"],
            "itl_p50_ms": metrics.pct(metrics.itl_s(rec), 50) * 1e3,
            "output_tok_s": e2e["output_tok_s"], "waiting": q,
            "mean_decode_rows": sum(rows) / max(len(rows), 1),
            "steps": len(steps)}), flush=True)
        # sustained: the admission queue at the window's end is no longer
        # than at its start (two requests of slack for arrival noise)
        if q[-1] <= q[0] + 2 and (knee is None or rate > knee):
            knee = rate
        for r in list(engine.queue) + list(engine.active):
            engine.cancel(r.rid)
        while engine.queue or engine.active:
            engine.step()
        engine.reset_metrics()
    print(json.dumps({"knee_rps": knee}), flush=True)


if __name__ == "__main__":
    main()
