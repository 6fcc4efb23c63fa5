"""Whole step: the logical model work of the traced steps at the chip's
peaks (int4 projections at the int8 peak, attention and the output head
at the bf16 peak), over the steps' summed host wall time, in percent."""


def read(rec):
    steps = getattr(rec, "traced_steps", None)
    if not steps:
        return None
    wall = sum(s.t1 - s.t0 for s in steps)
    w, pk = rec.traced_work, rec.peaks
    at_peak = w.int8_ops / pk["int8_ops"] + w.bf16_flops / pk["bf16_flops"]
    return 100.0 * at_peak / wall
