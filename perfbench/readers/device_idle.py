"""Device: share of the traced window in which no op ran on the chip, in
percent."""


def read(rec):
    if not rec.trace or not rec.traced or rec.trace["n_devices"] == 0:
        return None
    lo, hi = rec.traced
    return 100.0 * max(0.0, 1.0 - rec.trace["busy_s"] / (hi - lo))
