"""Model step: host wall time (ms) of the window's steps that prefilled,
per 1000 prompt rows they cached."""


def read(rec):
    steps = [s for s in rec.steps_in(*rec.window) if s.prefill]
    rows = sum(b - a for s in steps for a, b, _ in s.prefill)
    if not rows:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / (rows / 1000.0)
