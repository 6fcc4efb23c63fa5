"""From a JAX profiler trace to the numbers the per-layer readers use.

`extract` reads the `.xplane.pb` that `jax.profiler` wrote (with JAX's own
`ProfileData`) into a small dict: per device plane the op events, and the
benchmark's own host annotations (`bench.*`). `reduce` turns that into:

  busy_s       union of the intervals in which a device op ran, averaged
               over the device planes
  kernel_s     device time of the Pallas kernels (ops whose HLO names the
               custom-call target `tpu_custom_call`; on a TPU v5e trace
               the op names are `%int4_matmul.N`, `%hadamard_quant.N`,
               `%paged_attention.N`)
  ops          [op name, seconds] of the device ops that took most time,
               summed by HLO op name without its number; control-flow
               ops (`while`, `conditional`, `call`) are left out, since
               the ops they run are listed themselves
  idle_gaps    [host activity, seconds]: device-idle time summed by the
               benchmark's host annotation that covers each gap
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
KERNEL_MARKS = ("tpu_custom_call",)
CONTAINERS = ("while", "conditional", "call")


def _is_kernel(name: str, stats: dict) -> bool:
    text = name + " " + " ".join(str(v) for v in stats.values())
    return any(m in text for m in KERNEL_MARKS)


def find_xplane(trace_dir) -> str | None:
    hits = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def extract(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    lines_seen = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            events = []
            for line in plane.lines:
                lines_seen.setdefault(plane.name, []).append(line.name)
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    stats = {}
                    try:
                        stats = {k: v for k, v in ev.stats
                                 if isinstance(v, str) and len(v) < 400}
                    except Exception:
                        pass
                    events.append([ev.name, ev.start_ns, ev.duration_ns,
                                   _is_kernel(ev.name, stats)])
            devices.append({"plane": plane.name, "events": events})
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"devices": devices, "host": host, "lines": lines_seen}


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _base(name: str) -> str:
    """`%paged_attention.16 = (...) custom-call(...)` -> `paged_attention`."""
    op = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.:]\d+$", "", op)


def reduce(ex: dict, top: int = 10) -> dict:
    devs = [d for d in ex["devices"] if d["events"]]
    if not devs:
        return {"busy_s": 0.0, "kernel_s": 0.0, "ops": [], "idle_gaps": [],
                "n_devices": 0}
    busy, kernel, by_name, gaps_by = 0.0, 0.0, {}, {}
    # the benchmark's host spans do not nest: at most one covers a point
    host = sorted(ex["host"], key=lambda e: e[1])
    starts = [h[1] for h in host]
    for d in devs:
        iv = [(s, s + dur) for _, s, dur, _ in d["events"]]
        merged = _union(iv)
        busy += sum(b - a for a, b in merged) / 1e9
        for name, _, dur, is_k in d["events"]:
            key = _base(name)
            if key not in CONTAINERS:
                by_name[key] = by_name.get(key, 0.0) + dur / 1e9
            if is_k:
                kernel += dur / 1e9
        for (_, a), (b, _) in zip(merged, merged[1:]):
            mid = (a + b) / 2
            i = bisect.bisect_right(starts, mid) - 1
            who = host[i][0] if i >= 0 and mid <= host[i][1] + host[i][2] \
                else "no bench span"
            gaps_by[who] = gaps_by.get(who, 0.0) + (b - a) / 1e9
    n = len(devs)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps_by.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy / n, "kernel_s": kernel / n,
            "ops": [[k, v / n] for k, v in ops],
            "idle_gaps": [[k, v / n] for k, v in gaps], "n_devices": n}


def sample(ex: dict, n: int = 3000) -> dict:
    """A small slice of an extract (the first `n` device events of each
    plane and the host annotations over the same span), kept with the
    benchmark to check `reduce` against a trace recorded on the chip."""
    out = {"devices": [], "host": [], "lines": ex["lines"]}
    end = 0
    for d in ex["devices"]:
        ev = sorted(d["events"], key=lambda e: e[1])[:n]
        if ev:
            end = max(end, ev[-1][1] + ev[-1][2])
        out["devices"].append({"plane": d["plane"], "events": ev})
    out["host"] = [h for h in ex["host"] if h[1] <= end]
    return out
