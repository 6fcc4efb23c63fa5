"""Device, host-bound: share of the traced window in which no op ran on
the chip and the host was not inside an `engine.wait.*` span, in
percent; interval intersection of the device ops with the engine's wait
spans (`bench/spans.py`), over the window `device_idle` divides by.
`device_idle` minus this is the idle the chip causes itself while the
host waits on it."""
from bench import spans


def read(rec):
    r = spans.of(rec)
    if r is None or r["idle_host_s"] is None:
        return None
    lo, hi = rec.traced
    return 100.0 * max(0.0, r["idle_host_s"] / (hi - lo))
