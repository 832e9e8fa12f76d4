"""The whole step's share of the chip's peak: the operations of every
convolution and DCL of the traced steps, each over its datapath's peak,
over the traced window."""
from bench.work import compute_seconds


def read(rec):
    t = rec.trace
    if not t or not t["steps"]:
        return None
    return compute_seconds(rec.work_per_step, rec.peaks) * t["steps"] \
        / t["window_s"] * 100
