"""Kernels: the time the chip needs at its roofline for the logical work
of the three Pallas kernels (int4 GEMMs, quantizers, paged attention) in
the traced steps (`bench/work.py`), over the device time of every Pallas
kernel in the trace, in percent."""


def read(rec):
    if not rec.trace or rec.trace["kernel_s"] <= 0:
        return None
    return 100.0 * rec.traced_work.kernels_roof_s / rec.trace["kernel_s"]
