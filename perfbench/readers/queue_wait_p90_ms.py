"""Engine admission: 90th percentile over the requests due in the window
of admission (`EngineRequest.t_admit`) minus scheduled arrival (ms)."""
from bench.metrics import due_in_window, pct


def read(rec):
    waits = [r.handle.t_admit - r.due for r in due_in_window(rec)
             if r.handle is not None and r.handle.t_admit is not None]
    return None if not waits else pct(waits, 90) * 1e3
