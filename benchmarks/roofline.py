"""§Roofline: three-term roofline per (arch × shape × mesh) from the dry-run.

    compute    = FLOPs_per_device / peak_FLOP/s        (197e12 bf16, v5e)
    memory     = HBM_bytes_per_device / HBM_bw         (819e9 B/s)
    collective = collective_bytes_per_device / link_bw (50e9 B/s ICI)

FLOPs/bytes come from the trip-count-corrected HLO walker
(`repro.launch.hlo_analysis`), which XLA's stock `cost_analysis` undercounts
for scanned layer stacks (validated within 2% of the analytic 8·N·D for a
rematerialized train step). Collective bytes are per-device payloads of every
all-gather/all-reduce/reduce-scatter/all-to-all/collective-permute, trip-
corrected. All shapes in the partitioned module are per-device shards, so
each term is per-device seconds; the slowest term is the bottleneck.

MODEL_FLOPS (useful work) = 6·N·D for train (N = matmul params, D = tokens),
2·N_active·D for prefill/decode — the ratio MODEL_FLOPS / HLO_FLOPS exposes
remat/redundant compute.

Usage:
    PYTHONPATH=src python -m benchmarks.roofline [--mesh sp|mp] [--csv out]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

# Published peaks of one chip, keyed by the `device_kind` JAX reports
# (Google Cloud documentation, "TPU v5e"): bf16 FLOP/s, HBM bytes/s, and
# bytes/s per ICI link (1,600 Gbit/s over four links). A device that is
# not here is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks_for(device_kind: str | None) -> dict:
    """The peak table row of `device_kind`; raises for any other device."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak figures for device_kind {device_kind!r} "
                         f"(known: {sorted(PEAKS)})") from None

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")


def _arch_matmul_params(cfg) -> float:
    """Matmul (FLOP-relevant) parameter count per the config."""
    d = cfg.d_model
    n = 0.0
    if cfg.family in ("ssm", "hybrid"):
        d_inner = cfg.d_inner
        state = cfg.ssm_state
        in_dim = 2 * d_inner + 2 * state + cfg.ssm_heads
        n += cfg.n_layers * (d * in_dim + d_inner * d)
        if cfg.family == "hybrid":
            h = cfg.n_heads * cfg.head_dim
            kvd = cfg.n_kv_heads * cfg.head_dim
            n += d * h + 2 * d * kvd + h * d + 3 * d * cfg.d_ff
    else:
        h = cfg.n_heads * cfg.head_dim
        kvd = cfg.n_kv_heads * cfg.head_dim
        attn = d * h + 2 * d * kvd + h * d
        if cfg.uses_moe:
            ffn_active = 3 * d * cfg.moe_d_ff * (cfg.top_k
                                                 + cfg.n_shared_experts)
        else:
            gates = 3 if cfg.act == "silu" else 2
            ffn_active = gates * d * cfg.d_ff
        n += cfg.n_layers * (attn + ffn_active)
    n += 2 * d * cfg.vocab  # embed (gather ~free, but lm_head matmul counts once)
    return n


def model_flops(arch: str, shape: str, kind: str) -> float:
    from repro.configs.registry import get_config
    from repro.models.config import ALL_SHAPES
    cfg = get_config(arch)
    cell = {c.name: c for c in ALL_SHAPES}[shape]
    n = _arch_matmul_params(cfg)
    if kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n * tokens
    if kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * cell.global_batch


def load_records(mesh: str) -> list[dict]:
    suffix = "__mp.json" if mesh == "mp" else "__sp.json"
    recs = []
    for path in sorted(glob.glob(os.path.join(ART, f"*{suffix}"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def roofline_row(rec: dict) -> dict | None:
    if rec.get("status") != "ok":
        return None
    hc = rec.get("hlo_costs", {})
    if "flops_per_device" not in hc:
        return None
    chips = 1
    for s in rec["mesh"]["shape"]:
        chips *= s
    peak = peaks_for(rec.get("device_kind"))
    t_compute = hc["flops_per_device"] / peak["flops"]
    t_memory = hc["bytes_per_device"] / peak["hbm_bw"]
    t_coll = sum(hc["collective_bytes_by_kind"].values()) / peak["link_bw"]
    dominant = max(
        (("compute", t_compute), ("memory", t_memory),
         ("collective", t_coll)), key=lambda kv: kv[1])[0]
    mf = model_flops(rec["arch"], rec["shape"], rec["kind"])
    mf_dev = mf / chips
    useful = mf_dev / hc["flops_per_device"] if hc["flops_per_device"] else 0
    # roofline fraction: useful work at peak / modeled step time
    t_step = max(t_compute, t_memory, t_coll)
    frac = (mf_dev / peak["flops"]) / t_step if t_step > 0 else 0.0
    mem = rec.get("memory", {})
    hbm = (mem.get("argument_size_bytes", 0)
           + mem.get("temp_size_bytes", 0)) / 2 ** 30
    return {
        "arch": rec["arch"], "shape": rec["shape"], "kind": rec["kind"],
        "mesh": "x".join(str(s) for s in rec["mesh"]["shape"]),
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops_global": mf, "hlo_flops_dev": hc["flops_per_device"],
        "useful_ratio": useful, "roofline_frac": frac,
        "hbm_gib_dev": hbm,
    }


def fmt_table(rows: list[dict]) -> str:
    hdr = (f"{'arch':26s} {'shape':12s} {'mesh':9s} {'compute_s':>10s} "
           f"{'memory_s':>10s} {'collect_s':>10s} {'dominant':>10s} "
           f"{'useful':>7s} {'roofline':>9s} {'HBM GiB':>8s}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['arch']:26s} {r['shape']:12s} {r['mesh']:9s} "
            f"{r['t_compute_s']:10.4f} {r['t_memory_s']:10.4f} "
            f"{r['t_collective_s']:10.4f} {r['dominant']:>10s} "
            f"{r['useful_ratio']:7.2f} {r['roofline_frac']:9.3f} "
            f"{r['hbm_gib_dev']:8.1f}")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["sp", "mp"], default="sp")
    ap.add_argument("--csv", default=None)
    args = ap.parse_args(argv)
    rows = [r for r in (roofline_row(rec) for rec in load_records(args.mesh))
            if r is not None]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    print(fmt_table(rows))
    if args.csv:
        import csv
        with open(args.csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
            w.writeheader()
            w.writerows(rows)
        print(f"\nwrote {args.csv}")
    return rows


if __name__ == "__main__":
    main()
