"""Run one benchmark cell on the chip and print its result line.

    python3 perfbench/run.py --workload granite-3-2b.batch --seed 7 \\
        --seconds 30 --trace 0

Everything a cell is made of is found by name: the cell in
`BENCHMARK.json`, its configuration in `perfbench/configs/`, the model
module of its `model_type` in `perfbench/models/`, its traffic mix in
`perfbench/traffic/`, the reader of each per-layer metric in
`perfbench/readers/`. One run: make the weights on the device from the
seed, build the engine, warm every shape the traffic reaches, offer the
traffic and measure for `--seconds` (with `--trace 1`, under the profiler
for the first `trace_s` seconds of the window), then compare what was
served with the float32 reference. The last line of standard output is
one JSON object; the numbers compared, with their limits, are the last
lines of standard error. Without a TPU, or with fewer chips than the cell
asks for, it exits 2 and prints no result.

`--control 1` also reads the reference's float8 control (not used by the
benchmark's own runs); `--record PATH` writes per-run details as JSON.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import spec  # noqa: E402


def _err(msg: str):
    print(msg, file=sys.stderr, flush=True)


def _parse(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=[0, 1])
    ap.add_argument("--control", type=int, default=0, choices=[0, 1])
    ap.add_argument("--record", default=None)
    return ap.parse_args(argv)


def main(argv=None, *, require_tpu: bool = True, root: Path | None = None,
         cell=None) -> int:
    args = _parse(argv)
    root = root or spec.ROOT
    if not (root / "src" / "repro").is_dir():
        _err(f"no program under {root / 'src'}: nothing to measure")
        return 2
    cell = cell or spec.load_cell(args.workload, root)

    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        _err(f"needs a TPU; JAX found {devs[0].platform}")
        return 2
    if require_tpu and len(devs) < cell.chips:
        _err(f"cell asks for {cell.chips} chips; JAX found {len(devs)}")
        return 2

    sys.path.insert(0, str(root / "src"))
    from bench import check, driver, metrics, tracefile, traffic, warmup
    driver.use_checkout_cache(root)
    from bench import work

    cfg, mix = cell.config, cell.traffic
    model = spec.load_model(cfg, root)
    dims = model.dims_of(cfg)
    eng = cfg["engine"]
    dev = devs[0]
    pk = work.peaks(dev.device_kind) if require_tpu else \
        json.loads(work.PEAKS_FILE.read_text())["TPU v5 lite"]

    tally = driver.CompileTally()
    params = model.make_params(args.seed, dims)
    jax.block_until_ready(params)
    engine = driver.build_engine(cfg, model.servable(cfg, dims, params),
                                 args.seed)
    del params
    p_min, p_max, o_max = traffic.max_lengths(mix)
    plan = warmup.plan(p_min, p_max, o_max, chunk=eng["prefill_chunk"],
                       page=eng["page_size"])
    warm_steps = driver.run_warmup(engine, mix, plan, dims.vocab)
    engine.reset_metrics()
    setup_s = time.perf_counter() - T_PROCESS
    _err(f"setup {setup_s:.1f}s: {len(plan.requests)} warm-up requests, "
         f"{warm_steps} steps, decode widths {plan.decode_widths}; "
         f"{tally.summary()}")

    reqs = traffic.generate(mix, args.seed, args.seconds, dims.vocab)
    trace_dir = None
    if args.trace:
        trace_dir = root / "perfbench" / ".out" / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    rec = driver.drive(engine, reqs, mix, args.seconds, trace_dir=trace_dir,
                       trace_s=min(args.seconds, mix["trace_s"]))
    t_end = time.perf_counter()
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))

    rec.dims, rec.peaks = dims, pk
    attempted, failed = metrics.attempted_failed(rec)
    e2e = metrics.end_to_end(rec, t_end)
    e2e["setup_s"] = setup_s
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out_metrics = {}
    breakdown = None
    if args.trace:
        xp = tracefile.find_xplane(trace_dir)
        ex = tracefile.extract(xp) if xp else None
        red = tracefile.reduce(ex) if ex else None
        rec.trace = red
        lo, hi = rec.traced or rec.window
        rec.traced_steps = rec.steps_in(lo, hi)
        rec.traced_work = work.window_work(model, rec.traced_steps, dims,
                                           pk, eng["prefill_chunk"])
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = hi - lo
            breakdown = {"device_ops": red["ops"],
                         "idle_gaps": red["idle_gaps"]}
        for m in cell.per_layer:
            v = spec.load_reader(m["name"], root).read(rec)
            if v is not None:
                out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is not None:
                out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    _err("window: " + ", ".join(f"{k} {v}" for k, v in e2e.items())
         + f" | attempted {attempted} failed {failed} | steps "
         f"{len(rec.steps_in(*rec.window))} | jit traces in window "
         f"{rec.window_traces} | peak bytes {peak}")

    corr = cfg["correct"]
    sampled = mix["sampling"]["greedy_share"] < 1.0
    picks = [check.sample(rec, args.seed, corr["sample_tokens"], greedy=g)
             for g in ((True, False) if sampled else (True,))]
    record = _record(rec, e2e, plan) if args.record else None
    if record is not None and args.trace and ex is not None:
        record["trace_sample"] = tracefile.sample(ex)
    rec.engine = None
    del engine, rec
    gc.collect()
    t_ref = time.perf_counter()
    prog, ctrl, detail = check.readings(
        args.seed, model, dims, picks[0], picks[1] if sampled else [],
        p_max + o_max, far_gap=corr["far_gap"], control=bool(args.control))
    checks, ok = check.judge(prog, corr, sampled)
    _err(f"served tokens against the reference: {prog}")
    if ctrl is not None:
        ctrl_checks, ctrl_ok = check.judge(ctrl, corr, sampled)
        _err(f"control (float8 reference): {ctrl}")
        _err(f"control correct: {ctrl_ok} {ctrl_checks}")
    if record is not None:
        record.update(readings=prog, control=ctrl, token_gaps=detail,
                      control_correct=None if ctrl is None else ctrl_ok,
                      reference_s=time.perf_counter() - t_ref)
        Path(args.record).parent.mkdir(parents=True, exist_ok=True)
        Path(args.record).write_text(json.dumps(record))
    for name, c in checks.items():
        bound = ">=" if name.endswith("_compared") else "<="
        _err(f"check {name}: {c['value']} (limit {bound} {c['limit']})")
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": out_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


def _record(rec, e2e, plan) -> dict:
    w0, w1 = rec.window
    return {
        "e2e": e2e, "window": [0.0, w1 - w0],
        "warm_requests": len(plan.requests),
        "steps": [[s.t0 - w0, s.t1 - w0, len(s.decode_ctx),
                   sum(b - a for a, b, _ in s.prefill)] for s in rec.steps],
        "reqs": [[r.due - w0, (r.submit or 0) - w0,
                  (r.handle.t_admit - w0) if r.handle is not None
                  and r.handle.t_admit else None,
                  [t - w0 for t in r.token_times[:1]], len(r.token_times),
                  len(r.req.prompt), r.req.max_new]
                 for r in rec.reqs if r.handle is not None],
        "trace": rec.trace,
    }


if __name__ == "__main__":
    sys.exit(main())
