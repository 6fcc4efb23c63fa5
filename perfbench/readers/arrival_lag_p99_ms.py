"""Load generator: 99th percentile of how late each request due in the
window was submitted against its schedule (host clock, ms). A starved
generator reads high here, not as a slow server."""
from bench.metrics import due_in_window, pct


def read(rec):
    lags = [r.submit - r.due for r in due_in_window(rec) if r.submit]
    return None if not lags else pct(lags, 99) * 1e3
