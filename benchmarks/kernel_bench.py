"""Kernel micro-benchmarks (interpret-mode correctness cost + jnp-reference
wall time on CPU; TPU wall-time comes from the roofline, not this host).

Reports per-op bytes/FLOPs and the modeled v5e time for the block-Hadamard
rotation and the fused rotate+quant kernel, plus the measured CPU time of
the jnp reference (sanity anchor, not a perf claim), and an end-to-end
decode-step latency pair for the dispatched serving path — reference
(`use_kernels(False)`) vs kernel dispatch — so the serving-path win (or,
on this CPU host, the interpret-mode overhead) is recorded in the bench
trajectory alongside the per-op numbers.

Also benchmarks the flash-decoding paged-attention kernel across context
length × head count × `kv_splits`, and — the headline of the scale-out PR —
the ragged early-exit: each row reports the pages walked per decode step
with the walk trimmed to each sequence's live pages versus the full-table
walk the pre-flash-decode kernel did (`batch · n_cols`), plus both wall
times. The work reduction is real even in interpret mode on this host:
skipped columns run neither their page copy nor their softmax update.

Rows are appended to `artifacts/BENCH_kernels.json` so the kernel perf
trajectory is tracked across PRs; `_check_schema` validates every row
against its op family's required fields before anything is written, so a
partial row fails the smoke job instead of landing in the history.

    PYTHONPATH=src python benchmarks/kernel_bench.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.paged_attention import pack_pages

try:
    from .common import ragged_paged_batch
    from .roofline import peaks_for
except ImportError:                      # run as a plain script
    from common import ragged_paged_batch
    from roofline import peaks_for

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")



def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def hadamard_rows():
    peak = peaks_for(jax.devices()[0].device_kind)
    m, d, b = 2048, 8192, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (m, d), jnp.float32)

    rot = jax.jit(lambda x: kref.block_hadamard_ref(x, b))
    us_rot = _time(rot, x)
    fused = jax.jit(lambda x: kref.hadamard_quant_ref(x, b))
    us_fused = _time(fused, x)

    bytes_unfused = m * d * 2 * 2 + (m * d * 2 + m * d * 1 + m * 8)
    bytes_fused = m * d * 2 + m * d * 1 + m * 8
    flops_rot = 2 * m * d * b

    rows = []
    print("# kernel model (v5e bf16) + CPU jnp reference timing")
    print("op,cpu_ref_us,model_bytes,model_flops,v5e_time_us,bound")
    t_mem = m * d * 2 * 2 / peak["hbm_bw"] * 1e6
    t_cmp = flops_rot / peak["flops"] * 1e6
    rows.append({"op": f"block_hadamard_b{b}", "cpu_ref_us": round(us_rot),
                 "model_bytes": m * d * 4, "model_flops": flops_rot,
                 "v5e_time_us": round(max(t_mem, t_cmp), 1),
                 "bound": "memory" if t_mem > t_cmp else "compute"})
    print(f"block_hadamard_b{b},{us_rot:.0f},{m*d*4},{flops_rot},"
          f"{max(t_mem,t_cmp):.1f},{'memory' if t_mem>t_cmp else 'compute'}")
    t_mem_f = bytes_fused / peak["hbm_bw"] * 1e6
    rows.append({"op": f"hadamard_quant_fused_b{b}",
                 "cpu_ref_us": round(us_fused), "model_bytes": bytes_fused,
                 "model_flops": flops_rot,
                 "v5e_time_us": round(max(t_mem_f, t_cmp), 1),
                 "bound": "memory"})
    print(f"hadamard_quant_fused_b{b},{us_fused:.0f},{bytes_fused},"
          f"{flops_rot},{max(t_mem_f,t_cmp):.1f},memory")
    saving = 1 - bytes_fused / bytes_unfused
    rows.append({"op": "fusion_hbm_byte_saving", "value": round(saving, 3)})
    print(f"fusion_hbm_byte_saving,{saving:.3f}")
    return rows


def decode_step_bench(iters: int = 3):
    """ref-vs-dispatched-kernel decode-step latency on the smoke config.

    Both paths run through `QuantizedDenseLM` (jit'd end to end); only the
    `use_kernels` flag differs. On TPU the kernel column is the Mosaic
    path; on CPU it is interpret mode, whose overhead this row makes
    visible rather than hides.
    """
    from repro.configs.registry import get_config
    from repro.models.transformer import build_model
    from repro.serve.quantized import QuantizedDenseLM, pack_dense_params

    cfg = get_config("llama3-1b").reduced()
    model = build_model(cfg)
    packed = pack_dense_params(model.init(jax.random.PRNGKey(0)), cfg)
    qlm = QuantizedDenseLM(cfg, block_size=16)
    tok = jnp.asarray([[7]], jnp.int32)
    idx = jnp.asarray(3, jnp.int32)

    rows = []
    print("serving_path,decode_step_us")
    for label, enabled in (("ref", False), ("kernels", True)):
        with kops.use_kernels(enabled):
            cache = qlm.init_cache(1, 32)
            qlm.decode_step(packed, tok, cache, idx)[0].block_until_ready()
            t0 = time.perf_counter()
            for _ in range(iters):
                out, _ = qlm.decode_step(packed, tok, cache, idx)
                out.block_until_ready()
        us = (time.perf_counter() - t0) / iters * 1e6
        rows.append({"op": f"decode_{label}", "decode_step_us": round(us)})
        print(f"decode_{label},{us:.0f}")
    return rows


def paged_attention_bench(*, smoke: bool, iters: int = 5):
    """Flash-decoding paged attention: context × heads × kv_splits, and
    the ragged early-exit's pages-walked-per-step reduction.

    One decode step (S = 1) over a ragged batch whose sequence lengths
    span 25%..100% of the context budget. `full_walk` forces
    `seq_lengths` to the table capacity — every instance walks every
    column, which is exactly what the PR 3 `(batch, page)` grid did — and
    `early_exit` passes the true lengths. Pages walked per step is the
    analytic `Σ_b ceil(len_b / page_size)` vs `batch · n_cols`; the wall
    times show the skip is real work deleted (no page copy, no softmax
    update), interpret-mode overhead included.
    """
    page_size, batch, dh = 16, 4, 64
    cases = ([(64, 2, 4, 1), (64, 2, 4, 4)] if smoke else
             [(256, 2, 8, 1), (256, 2, 8, 4),
              (1024, 2, 8, 1), (1024, 2, 8, 4), (1024, 2, 8, 8),
              (1024, 8, 32, 4)])
    rng = np.random.default_rng(0)
    rows = []
    print("op,ctx,kv_heads,q_heads,kv_splits,pages_per_step,us_per_step")
    for ctx, kh, h, kv_splits in cases:
        n_cols = ctx // page_size
        lengths, n_pages, table, positions = ragged_paged_batch(
            batch, ctx, page_size)
        kv = pack_pages(*(jnp.asarray(rng.standard_normal(
            (1, n_pages, page_size, kh, dh)), jnp.float32)
            for _ in range(2)))
        bt = jnp.asarray(table, jnp.int32)
        qpos = jnp.asarray(positions, jnp.int32)
        q = jnp.asarray(rng.standard_normal((batch, 1, h, dh)), jnp.float32)
        true_lens = jnp.asarray(lengths, jnp.int32)
        full_lens = jnp.full((batch,), n_cols * page_size, jnp.int32)

        walked = {"full_walk": batch * n_cols,
                  "early_exit": sum(-(-n // page_size) for n in lengths)}
        for label, lens in (("full_walk", full_lens),
                            ("early_exit", true_lens)):
            fn = jax.jit(lambda lens=lens: kops.paged_attention(
                q, kv, bt, qpos, lens, kv_heads=kh, kv_splits=kv_splits))
            fn().block_until_ready()
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn()
            out.block_until_ready()
            us = (time.perf_counter() - t0) / iters * 1e6
            rows.append({
                "op": f"paged_attention_{label}", "ctx": ctx,
                "kv_heads": kh, "q_heads": h, "kv_splits": kv_splits,
                "page_size": page_size, "batch": batch,
                "pages_per_step": walked[label],
                "us_per_step": round(us, 1),
            })
            print(f"paged_attention_{label},{ctx},{kh},{h},{kv_splits},"
                  f"{walked[label]},{us:.1f}")
    return rows


def swap_io_bench(*, smoke: bool, iters: int = 5):
    """Host<->device swap-tier page I/O: the fused gather+device_get
    (swap-out) and device_put+scatter (swap-in) round trip the tiered
    paging engine pays per evicted page, as a measured bandwidth.

    A synthetic `PagedKVCache` with an attached host pool swaps one
    sequence's pages out and back per cycle; the first cycle compiles
    both fused dispatches and is discarded. The bytes/cycle is the
    per-direction payload (`pages · page_bytes`) — the quantity the
    scheduler's swap-vs-replay cost rule weighs against replayed
    prefill tokens.
    """
    from repro.serve.engine.pages import PagedKVCache

    nl, kh, dh = (2, 2, 64) if smoke else (4, 8, 128)
    page_size, n_pages, pages_move = 16, 32, 8
    rng = np.random.default_rng(0)
    kv = {"k": jnp.asarray(rng.standard_normal(
              (nl, n_pages, page_size, kh, dh)), jnp.float32),
          "v": jnp.asarray(rng.standard_normal(
              (nl, n_pages, page_size, kh, dh)), jnp.float32)}
    cache = PagedKVCache(kv, n_pages, page_size, n_slots=2)
    cache.attach_host_pool(64)
    rid = 0
    cache.tables[rid] = cache.allocator.alloc(pages_move)

    cache.swap_out(rid)          # compile both fused dispatches
    cache.swap_in(rid)
    t_out = t_in = 0.0
    for _ in range(iters):
        t0 = time.perf_counter()
        _, nbytes = cache.swap_out(rid)
        t_out += time.perf_counter() - t0
        t0 = time.perf_counter()
        cache.swap_in(rid)
        jax.tree.leaves(cache.state["kv"])[0].block_until_ready()
        t_in += time.perf_counter() - t0

    rows = []
    print("op,pages,bytes_per_cycle,us_per_cycle,gib_per_s")
    for label, wall in (("swap_out_io", t_out), ("swap_in_io", t_in)):
        us = wall / iters * 1e6
        rows.append({
            "op": label, "pages": pages_move, "bytes_per_cycle": nbytes,
            "us_per_cycle": round(us, 1),
            "gib_per_s": round(nbytes / (wall / iters) / 2 ** 30, 3),
        })
        print(f"{label},{pages_move},{nbytes},{us:.1f},"
              f"{rows[-1]['gib_per_s']}")
    return rows


# required measurement fields per op family — `_check_schema` refuses to
# append a history row that lost one (mirrors serve_bench's row check)
_ROW_FIELDS = {
    "block_hadamard": ("cpu_ref_us", "model_bytes", "model_flops",
                       "v5e_time_us", "bound"),
    "hadamard_quant": ("cpu_ref_us", "model_bytes", "model_flops",
                       "v5e_time_us", "bound"),
    "fusion_hbm": ("value",),
    "paged_attention": ("ctx", "kv_heads", "q_heads", "kv_splits",
                        "page_size", "batch", "pages_per_step",
                        "us_per_step"),
    "decode": ("decode_step_us",),
    "swap": ("pages", "bytes_per_cycle", "us_per_cycle", "gib_per_s"),
}


def _check_schema(rows):
    """Every row must carry `op` plus the measurement fields its op family
    defines — a bench path that crashed mid-collection or renamed a field
    fails the smoke job instead of silently appending a partial row to the
    JSON history."""
    for row in rows:
        op = row.get("op")
        if not op:
            raise ValueError(f"bench row {row!r} is missing 'op'")
        for prefix, fields in _ROW_FIELDS.items():
            if op.startswith(prefix):
                missing = [k for k in fields if k not in row]
                if missing:
                    raise ValueError(
                        f"bench row {op!r} is missing required field(s) "
                        f"{missing}; refusing to write partial history")
                break
        else:
            raise ValueError(f"bench row has unknown op family {op!r}; "
                             "add its required fields to _ROW_FIELDS")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI: compiles every bench path "
                    "once, minimal wall time")
    ap.add_argument("--out", default=os.path.join(ART, "BENCH_kernels.json"))
    args = ap.parse_args(argv)

    rows = []
    if not args.smoke:
        rows += hadamard_rows()
    rows += paged_attention_bench(smoke=args.smoke)
    rows += decode_step_bench()
    rows += swap_io_bench(smoke=args.smoke)

    out = {
        "bench": "kernels",
        "backend": jax.default_backend(),
        "smoke": bool(args.smoke),
        "rows": rows,
    }
    _check_schema(rows)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    history = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            history = json.load(f).get("history", [])
    history.append(out)
    with open(args.out, "w") as f:
        json.dump({"history": history}, f, indent=1)
    print(f"wrote {args.out} ({len(history)} entries)")


if __name__ == "__main__":
    main()
