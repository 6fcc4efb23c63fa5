"""Engine host: mean time (ms) of the traced decode-only engine steps
outside their device waits, from the engine's own `engine.step` and
`engine.wait.*` profiler spans (`bench/spans.py`). A synchronous engine
leaves the chip idle for this long each step."""
from bench import spans


def read(rec):
    r = spans.of(rec)
    return None if r is None else r["host_ms"]
