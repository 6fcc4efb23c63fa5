"""Model step: mean host wall time (ms) of the window's engine steps that
decoded and ran no prefill chunk."""


def read(rec):
    walls = [s.t1 - s.t0 for s in rec.steps_in(*rec.window)
             if s.decode_ctx and not s.prefill]
    return None if not walls else 1e3 * sum(walls) / len(walls)
