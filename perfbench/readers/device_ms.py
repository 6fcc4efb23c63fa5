"""Model step (device): mean device time (ms) of the decode programs
(`engine_decode*` events of the trace's `XLA Modules` line) in the
traced window (`bench/spans.py`)."""
from bench import spans


def read(rec):
    r = spans.of(rec)
    return None if r is None else r["device_ms"]
