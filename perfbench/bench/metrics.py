"""End-to-end metrics from the client-side record (host clock).

  ttft_p90_ms   90th percentile over the requests due in the window of
                (first token delivered - scheduled arrival); a request
                with no first token by the end of the grace period counts
                with the time it had waited then
  itl_p99_ms    99th percentile over every gap between consecutive tokens
                of one request whose later token was delivered in the
                window
  prompt_tok_s  prompt rows newly cached in the window (each request's
                high-water mark, so a replay is not counted twice) over
                the window's length
  output_tok_s  generated tokens delivered in the window over its length
"""
from __future__ import annotations

import math


def pct(values, q: float) -> float | None:
    """Nearest-rank percentile (q in [0, 100])."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def due_in_window(rec):
    w0, w1 = rec.window
    return [r for r in rec.reqs if w0 <= r.due < w1]


def ttft_s(rec, end: float) -> list[float]:
    return [(r.token_times[0] if r.token_times else end) - r.due
            for r in due_in_window(rec)]


def itl_s(rec) -> list[float]:
    w0, w1 = rec.window
    out = []
    for r in rec.reqs:
        t = r.token_times
        out.extend(b - a for a, b in zip(t, t[1:]) if w0 <= b < w1)
    return out


def end_to_end(rec, end: float) -> dict[str, float | None]:
    w0, w1 = rec.window
    span = w1 - w0
    in_win = rec.steps_in(w0, w1)
    out = {}
    t = ttft_s(rec, end)
    out["ttft_p90_ms"] = None if not t else pct(t, 90) * 1e3
    g = itl_s(rec)
    out["itl_p99_ms"] = None if not g else pct(g, 99) * 1e3
    out["prompt_tok_s"] = sum(s.prompt_rows for s in in_win) / span
    out["output_tok_s"] = sum(
        sum(1 for x in r.token_times if w0 <= x < w1) for r in rec.reqs) / span
    return out


def attempted_failed(rec) -> tuple[int, int]:
    bad = ("failed", "expired", "cancelled")
    if rec.kind == "open_loop":
        due = due_in_window(rec)
        failed = sum(1 for r in due if not r.token_times
                     or (r.handle.outcome in bad))
        return len(due), failed
    w0, w1 = rec.window
    adm = [r for r in rec.reqs if r.handle is not None
           and r.handle.t_admit is not None and w0 <= r.handle.t_admit < w1]
    return len(adm), sum(1 for r in adm if r.handle.outcome in bad)
