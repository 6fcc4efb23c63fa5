"""Engine batching: mean number of rows in the window's decode
dispatches (the batch the engine keeps decoding)."""


def read(rec):
    rows = [len(s.decode_ctx) for s in rec.steps_in(*rec.window)
            if s.decode_ctx]
    return None if not rows else sum(rows) / len(rows)
