"""Smoke run of the W4A4 serving path on one TPU chip, at full width.

    python chip_smoke.py

Builds Llama-3.2 1B (`llama3-1b`: 16 layers, d_model 2048, vocab 128256,
32/8 heads of 64, d_ff 8192, bf16) from a seed with LLM-like outlier
channels, quantizes it with the MixQuant preset (MassDiff permutation,
QuaRot stream rotation, block rotation at b = 16; RTN rounding — Qronos's
Hessian solves at d_ff 8192 would dominate the run), packs every
projection to int4 and serves 8 requests over 4 slots through
`ServeEngine` on int8 KV pages: chunked prefill, batched decode, paged
attention, and the prefix cache for one shared-prefix pair. All of it
goes through the launcher's own steps (`repro.launch.serve`).

It then checks, and fails (exit 1) unless all hold:

  * every request ended `length` or `stop`, none was poisoned, and every
    logit compared below is finite;
  * the Pallas kernels ran on the chip: the engine's
    `kernels.dispatch.<entry>.kernels` tallies are nonzero for
    `int4_matmul`, `hadamard_quant` and `paged_attention`, and the lowered
    decode step holds `tpu_custom_call`;
  * one prefill chunk and one decode step give the same logits with
    `ops.use_kernels(True)` and `ops.use_kernels(False)` within
    `KERNEL_REF_REL_RMS` (reason beside it; both compiled with XLA's
    excess precision off).

The wall times it prints are host-clock times of this one run, compiles
included where named; they are not benchmark metrics. The last line of
standard output is one JSON object naming the device. Without a TPU it
exits 2 before doing any work.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs.registry import get_config  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARGV = ["--arch", "llama3-1b", "--integer-path", "--kv-bits", "8",
        "--preset", "perq_star", "--block-size", "16", "--rounding", "rtn",
        "--slots", "4", "--max-new", "16", "--max-len", "80",
        "--page-size", "16", "--prefill-chunk", "8", "--prefix-cache"]
KERNELS = ("int4_matmul", "hadamard_quant", "paged_attention")

# Kernel-vs-reference logit gap, as RMS(kernels − ref) / RMS(ref). XLA
# may skip the bf16 rounding of a value it fuses into an f32 consumer
# (excess precision), which a kernel operand never skips, so both paths
# are compiled with that liberty off (`EXACT`): they then compute the
# same declared arithmetic — integer GEMMs, full-precision f32 dots,
# bf16 roundings — and differ at most in f32 summation order and
# multiply-add fusion, a few ulp. Such a difference flips an int4
# activation code only on a rounding tie, moving one of thousands of
# inputs by one step. Without `EXACT` the gap measured 1.3 on the chip;
# a rotation or attention dot done in one bf16 pass moves logits by
# several percent.
EXACT = {"xla_allow_excess_precision": False}
KERNEL_REF_REL_RMS = 1e-3


def smoke_prompts(vocab: int) -> list[list[int]]:
    """8 prompts of 16–64 tokens (seed 0), all whole prefill chunks.
    Requests 0 and 4 share a 32-token (two-page) prefix: request 0
    prefills and finishes first, so its pages are in the radix tree when
    request 4 is admitted to the slot it frees."""
    rng = np.random.default_rng(0)
    lens = [16, 24, 40, 48, 24, 56, 64, 32]
    prompts = [rng.integers(0, vocab, size=n).tolist() for n in lens]
    prefix = rng.integers(0, vocab, size=32).tolist()
    prompts[0] = prefix + prompts[0]          # 48 tokens
    prompts[4] = prefix + prompts[4]          # 56 tokens
    return prompts


def kernel_ref_gap(qlm, packed, page_size: int, slots: int, vocab: int):
    """One prefill chunk (8 tokens into a fresh page) and one decode step
    (`slots` rows, three of them padding) under kernels and under the
    reference → (relative RMS gaps, all-finite flag, lowered decode text
    of the kernel path)."""
    rng = np.random.default_rng(1)
    chunk = jnp.asarray([rng.integers(0, vocab, size=8)], jnp.int32)
    bt_pre = jnp.asarray([[1]], jnp.int32)
    dec_tok = jnp.asarray([[int(rng.integers(0, vocab))]]
                          + [[0]] * (slots - 1), jnp.int32)
    fill = jnp.asarray([8] + [0] * (slots - 1), jnp.int32)
    lens = jnp.asarray([9] + [0] * (slots - 1), jnp.int32)
    bt_dec = jnp.asarray([[1]] + [[0]] * (slots - 1), jnp.int32)

    def forward(*args):
        lowered = jax.jit(qlm.forward_chunk).lower(*args)
        return lowered, lowered.compile(compiler_options=EXACT)(*args)

    out, hlo = {}, None
    for enabled in (True, False):
        with kops.use_kernels(enabled):
            cache = qlm.init_cache(2, page_size)
            _, (pre, cache) = forward(packed, chunk, cache,
                                      jnp.asarray(0, jnp.int32), bt_pre,
                                      jnp.asarray([8], jnp.int32))
            lowered, (dec, _) = forward(packed, dec_tok, cache, fill, bt_dec,
                                        lens)
            out[enabled] = (np.asarray(pre[0], np.float32),
                            np.asarray(dec[0, 0], np.float32))
            if enabled:
                hlo = lowered.as_text()
    gaps = {}
    for name, k, r in zip(("prefill", "decode"), out[True], out[False]):
        gaps[name] = float(np.sqrt(np.mean((k - r) ** 2) / np.mean(r ** 2)))
    finite = all(np.isfinite(a).all() for pair in out.values() for a in pair)
    return gaps, finite, hlo


def run(reduced: bool = False) -> dict:
    """Build, calibrate, pack and serve; returns what `main` checks.
    `reduced` swaps in the 2-layer smoke width (CPU rehearsal)."""
    args = serve.build_parser().parse_args(ARGV + ["--reduced"] * reduced)
    res = {"compile_cache": enable_compile_cache()}
    t0 = time.perf_counter()
    cfg = get_config(args.arch)
    if reduced:
        cfg = cfg.reduced()
    model, params = serve.build_model_params(cfg)
    jax.block_until_ready(params)
    res["wall_build_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    smodel, sparams = serve.quantize(args, model, params)
    del params
    prompts = smoke_prompts(cfg.vocab)
    engine, label = serve.build_engine(args, cfg, smodel, sparams, prompts)
    del sparams
    jax.block_until_ready(engine.adapter.params)
    res["wall_calibrate_pack_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    done = serve.run_engine(args, engine, prompts)
    res["wall_serve_s"] = time.perf_counter() - t0
    snap = engine.metrics_snapshot()
    res.update(
        label=label, n_steps=engine.n_steps,
        outcomes={r.rid: r.outcome for r in done},
        generated={r.rid: len(r.generated) for r in done},
        median_step_s=snap["histograms"]["engine.step.wall_s"]["p50"],
        poisoned=snap["counters"]["engine.requests.poisoned"],
        prefix_hit_tokens=snap["counters"]["engine.prefix.hit_tokens"],
        tallies={k: snap["counters"].get(f"kernels.dispatch.{k}.kernels", 0)
                 for k in KERNELS})

    t0 = time.perf_counter()
    gaps, finite, hlo = kernel_ref_gap(engine.adapter.qlm,
                                       engine.adapter.params, args.page_size,
                                       args.slots, cfg.vocab)
    res.update(wall_compare_s=time.perf_counter() - t0, gaps=gaps,
               finite=finite, custom_call="tpu_custom_call" in hlo,
               n_requests=len(prompts))
    return res


def main() -> int:
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev[0].platform}",
              file=sys.stderr)
        return 2
    res = run()
    print(f"device: {dev[0].device_kind} x{len(dev)}; "
          f"compile cache: {res['compile_cache']}")
    print(f"{res['label']}: {len(res['outcomes'])}/{res['n_requests']} "
          f"requests in {res['n_steps']} engine steps; outcomes "
          f"{res['outcomes']}; tokens {res['generated']}; prefix-cache hit "
          f"tokens {res['prefix_hit_tokens']}")
    print(f"kernel dispatch tallies: {res['tallies']}; tpu_custom_call in "
          f"decode step: {res['custom_call']}")
    print("kernels vs reference logits, relative RMS gap: "
          + ", ".join(f"{k} {v:.3e}" for k, v in res["gaps"].items())
          + f" (limit {KERNEL_REF_REL_RMS})")
    print(f"wall time (host clock, this run): model build "
          f"{res['wall_build_s']:.1f}s | calibration + int4 pack "
          f"{res['wall_calibrate_pack_s']:.1f}s | serving incl. compiles "
          f"{res['wall_serve_s']:.1f}s | median engine step "
          f"{res['median_step_s'] * 1e3:.2f}ms (registry histogram "
          f"estimate) | kernel/ref comparison incl. compiles "
          f"{res['wall_compare_s']:.1f}s")

    failures = []
    if len(res["outcomes"]) != res["n_requests"] or any(
            o not in ("length", "stop") for o in res["outcomes"].values()):
        failures.append(f"request outcomes {res['outcomes']}")
    if res["poisoned"] or not res["finite"]:
        failures.append("non-finite logits")
    if not all(res["tallies"].values()):
        failures.append(f"kernel tallies {res['tallies']}")
    if not res["custom_call"]:
        failures.append("no tpu_custom_call in the decode step")
    if max(res["gaps"].values()) > KERNEL_REF_REL_RMS:
        failures.append(f"kernel/reference gap {res['gaps']}")
    if failures:
        print("chip_smoke FAILED: " + "; ".join(failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
