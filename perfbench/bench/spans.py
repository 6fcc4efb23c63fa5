"""The engine's own step phases in a JAX profiler trace.

`ServeEngine.step` writes its phases as profiler host spans (`engine.step`
with `step_num`, `rows` and `chunks`; inside it `engine.admit`,
`engine.{prepare,dispatch,wait,commit}.{decode,prefill}`,
`engine.account`, `engine.stream`), and names its device programs
`engine_decode*` / `engine_prefill*`. The profiler puts both on the
clock of the device ops. `extract` reads from the `.xplane.pb`:

  spans     [name, start_ns, duration_ns, args] of every `engine.*` host
            span
  devices   per device plane, the [start_ns, duration_ns] of each op of
            the `XLA Ops` line (what `tracefile` counts as busy) and the
            [name, start_ns, duration_ns] of each `XLA Modules` event

`reduce` turns that, with the traced window's length, into:

  host_ms     mean over the decode-only steps (an `engine.dispatch.decode`
              child and no `engine.dispatch.prefill` one) of the step
              span minus its `engine.wait.*` children: the host's own time
  wait_ms     mean `engine.wait.decode` time of the same steps
  busy_s      union of the device op intervals (averaged over planes)
  idle_host_s the window minus the union of the device op intervals and
              the `engine.wait.*` spans: device idle while the host was
              not waiting on the device (averaged over planes)
  device_ms   mean duration of the `engine_decode*` module events

Each is None where the trace has nothing to read it from, as in a trace
of a program without these spans. `of(rec)` reads the run's trace once
and keeps the reduction on the run's record for the readers.
"""
from __future__ import annotations

from . import spec, tracefile

PREFIX = "engine."
STEP = "engine.step"
WAIT = "engine.wait."
MODULES_LINE = "XLA Modules"
DECODE_PROGRAM = "engine_decode"
TRACE_DIR = spec.BENCH_DIR / ".out" / "trace"


def _args(ev) -> dict:
    return {k: v for k, v in ev.stats if not k.startswith("_")}


def extract(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith(tracefile.DEVICE_PREFIX):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == tracefile.OPS_LINE:
                    ops.extend([ev.start_ns, ev.duration_ns]
                               for ev in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend([ev.name, ev.start_ns, ev.duration_ns]
                                   for ev in line.events)
            devices.append({"plane": plane.name, "ops": ops,
                            "modules": modules})
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append([ev.name, ev.start_ns, ev.duration_ns,
                                  _args(ev)])
    return {"spans": spans, "devices": devices}


def steps(ex: dict) -> list[tuple[list, list]]:
    """Each `engine.step` span with the spans that lie inside it, both in
    start order."""
    out = []
    # an enclosing span starts no later than its first child: on a tie
    # the longer one comes first
    for s in sorted(ex["spans"], key=lambda s: (s[1], -s[2])):
        if s[0] == STEP:
            out.append((s, []))
        elif out and s[1] + s[2] <= out[-1][0][1] + out[-1][0][2]:
            out[-1][1].append(s)
    return out


def _covered(intervals) -> float:
    return sum(b - a for a, b in tracefile._union(intervals))


def _mean(xs):
    return sum(xs) / len(xs) if xs else None


def reduce(ex: dict, window_s: float) -> dict:
    by_step = steps(ex)
    host, wait = [], []
    for s, children in by_step:
        names = {c[0] for c in children}
        if "engine.dispatch.decode" not in names \
                or "engine.dispatch.prefill" in names:
            continue
        waits = sum(c[2] for c in children if c[0].startswith(WAIT))
        host.append((s[2] - waits) / 1e6)
        wait.append(sum(c[2] for c in children
                        if c[0] == "engine.wait.decode") / 1e6)
    waits = [(s[1], s[1] + s[2]) for s in ex["spans"]
             if s[0].startswith(WAIT)]
    devs = [d for d in ex["devices"] if d["ops"]]
    busy = idle_host = None
    if devs:
        ops = [[(a, a + d) for a, d in dev["ops"]] for dev in devs]
        busy = _mean([_covered(iv) / 1e9 for iv in ops])
        if by_step:
            idle_host = _mean([window_s - _covered(iv + waits) / 1e9
                               for iv in ops])
    device = [d / 1e6 for dev in ex["devices"]
              for name, _, d in dev["modules"] if DECODE_PROGRAM in name]
    return {"host_ms": _mean(host), "wait_ms": _mean(wait), "busy_s": busy,
            "idle_host_s": idle_host, "device_ms": _mean(device),
            "n_steps": len(by_step), "n_decode_steps": len(host)}


def of(rec) -> dict | None:
    """The reduction of the run's traced window (read once, then kept on
    `rec`); None where the run was not traced or wrote no trace."""
    if not hasattr(rec, "engine_spans"):
        rec.engine_spans = None
        xp = tracefile.find_xplane(TRACE_DIR) if rec.traced else None
        if xp is not None and rec.traced[1] is not None:
            lo, hi = rec.traced
            rec.engine_spans = reduce(extract(xp), hi - lo)
    return rec.engine_spans


def sample(ex: dict, n_steps: int = 4) -> dict:
    """A small slice of an extract, kept with the benchmark to check
    `reduce` against a chip trace: the first `n_steps` engine steps with
    their spans, and over the same interval the modules and the device
    ops, merged into their union (all that `reduce` reads of them);
    `window_s` is the interval's length."""
    first = steps(ex)[:n_steps]
    if not first:
        return {"spans": [], "devices": [], "window_s": 0.0}
    lo = first[0][0][1]
    hi = first[-1][0][1] + first[-1][0][2]
    spans = [s for s in ex["spans"] if lo <= s[1] and s[1] + s[2] <= hi]
    devices = []
    for d in ex["devices"]:
        busy = tracefile._union([(max(a, lo), min(a + n, hi))
                                 for a, n in d["ops"]
                                 if a < hi and a + n > lo])
        devices.append({"plane": d["plane"],
                        "ops": [[a, b - a] for a, b in busy],
                        "modules": [m for m in d["modules"]
                                    if lo <= m[1] and m[1] + m[2] <= hi]})
    return {"spans": spans, "devices": devices, "window_s": (hi - lo) / 1e9}
