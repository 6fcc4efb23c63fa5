"""Serving launcher CLI: quantize (PeRQ) then serve through the paged-KV
continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \\
        --reduced --preset perq_star --block-size 16 --requests 8

Every path runs batched through `repro.serve.engine.ServeEngine` (paged
state pools, chunked prefill, per-step admission): the bf16 model
(`--no-quant`), the fake-quant PTQ output (default), and the packed-int4
integer engine (`--integer-path`, dense archs, optional `--kv-bits {4,8}`
integer KV pages). The engine serves every decode-capable token-LM family
in the registry — dense, MoE, pure-SSM, hybrid — through the same
scheduler (`--model mamba2-1.3b --reduced --no-quant` serves the Mamba2
smoke config); encoder/frontend archs are rejected with a capability
error. `--legacy-scheduler` keeps the old dense-slot `BatchScheduler` for
comparison (bf16/fake-quant only).

`--prefix-cache` turns on the prefix-sharing radix cache (refcounted
copy-on-write KV pages, kv-only specs; `--prefix-cache-pages N` bounds
the LRU tree), and `--shared-prefix N` prepends one N-token system
prompt to every request to exercise it; the run summary then reports the
prefix hit-rate.

`--swap-host-mb MB` attaches a host KV swap tier (`--swap-policy
{never,cost,always}` picks when swap beats recompute-by-replay under
page pressure), and `--drain-after N` exercises graceful shutdown:
after N steps admission stops and the engine drains every tier empty.

Observability: `--metrics-json PATH` writes the engine's schema-validated
registry snapshot, `--trace PATH` records request lifecycles and the
engine's step phases as Chrome Trace JSON (open in
https://ui.perfetto.dev), and `--probe-every K` samples the
rotation-quality activation probes on the integer path. Every engine run
ends with a one-line summary (tokens/s, per-token latency quantiles, peak
pool utilization, admission wait) computed from the same registry
snapshot.

`main` is a sequence of steps — `build_model_params`, `quantize`,
`build_engine`, `run_engine` — that `chip_smoke.py` calls in the same
order to serve the full-width model on the chip.
"""
import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config
from repro.core import pipeline as PL
from repro.core.synthetic import inject_outlier_channels
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import build_model
from repro.serve.engine import (EngineRequest, SamplingParams, ServeEngine,
                                as_servable, pages_for)
from repro.serve.step import BatchScheduler, Request
from repro.serve.telemetry import (QualityProbes, Tracer, validate_snapshot,
                                   validate_trace)


def _ms(v) -> str:
    return "n/a" if v is None else f"{v * 1e3:.1f}ms"


def summary_line(snap: dict) -> str:
    """One-line end-of-run summary from a registry snapshot."""
    c, g, h = snap["counters"], snap["gauges"], snap["histograms"]
    toks = c["engine.generated_tokens"]
    wall = h["engine.step.wall_s"]["sum"]
    lat = h["engine.decode.token_latency_s"]
    wait = h["engine.admission.wait_s"]
    out = (f"summary: {toks} tokens in {wall:.2f}s engine time "
           f"({toks / max(wall, 1e-9):.1f} tok/s) | "
           f"token latency p50 {_ms(lat['p50'])} p95 {_ms(lat['p95'])} | "
           f"peak pages {g['engine.pages.peak_in_use']:.0f}"
           f"/{g['engine.pages.capacity']:.0f} "
           f"({g['engine.pages.utilization_peak']:.0%} peak util)")
    if "engine.register_slots.peak_in_use" in g:
        out += (f" | peak slots {g['engine.register_slots.peak_in_use']:.0f}"
                f"/{g['engine.register_slots.capacity']:.0f}")
    lookups = c["engine.prefix.hits"] + c["engine.prefix.misses"]
    if lookups:
        out += (f" | prefix hit-rate "
                f"{c['engine.prefix.hits'] / lookups:.0%} "
                f"({c['engine.prefix.hit_tokens']} tokens, "
                f"{c['engine.prefix.cow_copies']} COW)")
    if c["engine.swap.out"] or c["engine.swap.in"] or c["engine.swap.fallbacks"]:
        out += (f" | swap out {c['engine.swap.out']} "
                f"in {c['engine.swap.in']} "
                f"({c['engine.swap.bytes'] / 2**20:.1f} MiB, "
                f"{c['engine.swap.retries']} retries, "
                f"{c['engine.swap.fallbacks']} fallbacks)")
    out += (f" | preempt {c['engine.preemptions']} "
            f"cancel {c['engine.requests.cancelled']} "
            f"expire {c['engine.requests.expired']} "
            f"fail {c['engine.requests.failed']}")
    return out + f" | admission wait p95 {_ms(wait['p95'])}"


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", "--model", dest="arch",
                    default="qwen1.5-0.5b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--preset", default="perq_star",
                    choices=sorted(PL.PRESETS))
    ap.add_argument("--block-size", type=int, default=32)
    ap.add_argument("--rounding", default=None,
                    choices=["rtn", "gptq", "qronos"],
                    help="override the preset's weight rounding (RTN skips "
                    "the Hessian solves, which dominate PTQ at full width)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k most likely tokens "
                    "(0 disables)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 disables)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--integer-path", action="store_true")
    ap.add_argument("--kv-bits", type=int, default=None, choices=[4, 8])
    ap.add_argument("--no-quant", action="store_true",
                    help="serve the bf16 model instead")
    ap.add_argument("--legacy-scheduler", action="store_true",
                    help="use the dense-slot BatchScheduler (no paging)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the schema-validated engine metrics "
                    "snapshot as JSON")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a Chrome Trace Event JSON (Perfetto)")
    ap.add_argument("--probe-every", type=int, default=0, metavar="K",
                    help="sample rotation-quality activation probes every "
                    "K decode dispatches (integer path only; 0 disables)")
    ap.add_argument("--admission", default="optimistic",
                    choices=["optimistic", "reserve"],
                    help="admission policy: optimistic (prompt pages + "
                    "headroom, preemption-backed) or reserve (worst-case "
                    "pages up front, never preempts)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request TTL in seconds, enforced at step "
                    "boundaries (expired requests return their pages)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the prefix-sharing radix cache "
                    "(refcounted copy-on-write KV pages; kv-only specs)")
    ap.add_argument("--prefix-cache-pages", type=int, default=None,
                    metavar="N", help="LRU budget of pool pages the radix "
                    "tree may hold (default: unbounded — pressure evicts)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend the same N-token system prompt to every "
                    "request (exercises the prefix cache)")
    ap.add_argument("--swap-host-mb", type=float, default=None, metavar="MB",
                    help="attach a host KV swap tier of this many MiB: "
                    "under page pressure the engine may swap a victim's "
                    "pages to host instead of preempting it for recompute")
    ap.add_argument("--swap-policy", default="cost",
                    choices=["never", "cost", "always"],
                    help="when to prefer swap over recompute-by-replay "
                    "under pressure: cost-model the round-trip bytes vs "
                    "replayed tokens (default), always swap, or never "
                    "(preempt only; implied without --swap-host-mb)")
    ap.add_argument("--drain-after", type=int, default=None, metavar="N",
                    help="after N engine steps stop admission and drain: "
                    "never-admitted requests cancel, in-flight work "
                    "(including swapped residents) finishes, and the "
                    "engine asserts every tier came back empty")
    return ap


def build_model_params(cfg):
    """The served model, built from seed 0 with LLM-like outlier channels
    injected (no checkpoint is needed)."""
    model = build_model(cfg)
    return model, inject_outlier_channels(model.init(jax.random.PRNGKey(0)))


def quantize(args, model, params):
    """PTQ per `args` (calibrated on random tokens from seed 1) →
    (served model, served params); the bf16 model under --no-quant."""
    if args.no_quant:
        return model, params
    cfg = model.cfg
    key = jax.random.PRNGKey(1)
    calib = [{"tokens": jax.random.randint(key, (4, 128), 0, cfg.vocab),
              "labels": jnp.zeros((4, 128), jnp.int32)}]
    overrides = {"rounding": args.rounding} if args.rounding else {}
    res = PL.quantize_model(
        model, params, calib,
        PL.preset(args.preset, block_size=args.block_size, cayley_steps=8,
                  **overrides))
    print(f"quantized with {args.preset} (b={args.block_size}"
          + (f", {args.rounding}" if args.rounding else "") + ")")
    return PL.build_quantized_model(model, res), res.params


def default_prompts(args, cfg) -> list[list[int]]:
    """`--requests` random prompts of 3–8 tokens (seed 0), each behind
    the same `--shared-prefix`-token system prompt."""
    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab, size=args.shared_prefix).tolist()
    return [system + rng.integers(0, cfg.vocab,
                                  size=int(rng.integers(3, 9))).tolist()
            for _ in range(args.requests)]


def build_engine(args, cfg, smodel, sparams, prompts):
    """The paged engine serving `smodel` per `args`, with a pool sized
    for `prompts` → (engine, label)."""
    if args.integer_path:
        if cfg.family not in ("dense", "vlm"):
            raise SystemExit(f"--integer-path packs dense projections only; "
                             f"{cfg.name} is family {cfg.family!r}")
        from repro.serve.quantized import QuantizedDenseLM, \
            pack_dense_params
        qlm = QuantizedDenseLM(cfg, block_size=args.block_size,
                               kv_bits=args.kv_bits)
        adapter = as_servable(qlm, pack_dense_params(sparams, cfg))
        label = f"integer path (kv_bits={args.kv_bits})"
    else:
        adapter = as_servable(smodel, sparams,
                              name="bf16" if args.no_quant else "fake-quant")
        label = "bf16" if args.no_quant else "fake-quant"

    # enough pool for every slot to hold its worst-case sequence (the
    # larger of --max-len and the longest prompt + --max-new, which is
    # what engine admission reserves), plus the reserved scratch page
    per_seq = max([pages_for(args.max_len, args.page_size)]
                  + [pages_for(len(p) + args.max_new, args.page_size)
                     for p in prompts])
    n_pages = args.slots * per_seq + 1
    tracer = Tracer() if args.trace else None
    probes = QualityProbes(every_k=args.probe_every) if args.probe_every \
        else None
    engine = ServeEngine(adapter, n_pages=n_pages, page_size=args.page_size,
                         max_seqs=args.slots,
                         prefill_chunk=args.prefill_chunk,
                         admission=args.admission,
                         deadline_s=args.deadline_s,
                         max_context=args.max_len,
                         prefix_cache=args.prefix_cache,
                         prefix_cache_pages=args.prefix_cache_pages,
                         swap_host_mb=args.swap_host_mb,
                         swap_policy=args.swap_policy,
                         tracer=tracer, quality_probes=probes)
    return engine, label


def run_engine(args, engine, prompts):
    """Submit `prompts` and serve them to completion (or drain after
    `--drain-after` steps) → the requests that reached a terminal state."""
    for rid, prompt in enumerate(prompts):
        engine.submit(EngineRequest(
            rid=rid, prompt=prompt,
            sampling=SamplingParams(temperature=args.temperature,
                                    max_new=args.max_new,
                                    top_k=args.top_k, top_p=args.top_p)))
    if args.drain_after is None:
        return engine.run()
    done = []
    while (engine.queue or engine.active) \
            and engine.n_steps < args.drain_after:
        done.extend(engine.step())
    done.extend(engine.drain())
    return done


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    smodel, sparams = quantize(args, *build_model_params(cfg))
    prompts = default_prompts(args, cfg)

    if args.probe_every and not args.integer_path:
        raise SystemExit("--probe-every needs --integer-path: the probes "
                         "read the fused rotate+quantize site")
    if args.legacy_scheduler:
        if args.integer_path:
            raise SystemExit("--legacy-scheduler cannot drive the integer "
                             "path; the paged engine serves it")
        if args.metrics_json or args.trace:
            raise SystemExit("--metrics-json/--trace instrument the paged "
                             "engine; drop --legacy-scheduler")
        if args.top_k > 0 or args.top_p < 1.0:
            raise SystemExit("--legacy-scheduler has no top-k/top-p "
                             "support; drop the flags or use the engine")
        if args.prefix_cache:
            raise SystemExit("--prefix-cache is a paged-engine feature; "
                             "drop --legacy-scheduler")
        if args.swap_host_mb is not None or args.drain_after is not None:
            raise SystemExit("--swap-host-mb/--drain-after are paged-"
                             "engine features; drop --legacy-scheduler")
        sched = BatchScheduler(smodel, sparams, slots=args.slots,
                               max_len=args.max_len,
                               temperature=args.temperature)
        for rid, prompt in enumerate(prompts):
            sched.submit(Request(rid=rid, prompt=prompt,
                                 max_new=args.max_new))
        done = sched.run()
        for r in sorted(done, key=lambda r: r.rid):
            print(f"req {r.rid}: {r.prompt} → {r.generated}")
        return

    engine, label = build_engine(args, cfg, smodel, sparams, prompts)
    done = run_engine(args, engine, prompts)
    print(f"{label}: served {len(done)} requests over {args.slots} slots "
          f"in {engine.n_steps} engine steps "
          f"({engine.n_prefill_tokens} prefill + "
          f"{engine.n_decode_tokens} decode tokens)")
    for r in sorted(done, key=lambda r: r.rid):
        mark = "" if r.outcome in ("length", "stop") else f" [{r.outcome}]"
        print(f"req {r.rid}: {r.prompt} → {r.generated}{mark}")

    snap = engine.metrics_snapshot()
    validate_snapshot(snap)     # never write an off-schema artifact
    print(summary_line(snap))
    if engine.quality_probes is not None:
        imb = snap["histograms"]["quality.l1_imbalance_post"]
        print(f"quality: post-rotation l1 imbalance p50 {imb['p50']:.3f} "
              f"over {imb['count']} layer observations "
              f"({snap['counters']['quality.probe_dispatches']} probed "
              "dispatches)")
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(snap, f, indent=1)
        print(f"metrics snapshot → {args.metrics_json}")
    if engine.tracer is not None:
        validate_trace(engine.tracer.to_dict())
        engine.tracer.save(args.trace)
        print(f"trace ({len(engine.tracer.events)} events) → {args.trace}")


if __name__ == "__main__":
    main()
