"""Device time of the DCL kernels (the Pallas custom calls) per step,
from the profiler trace."""


def read(rec):
    t = rec.trace
    if not t or not t["kernel_s"] or not t["steps"]:
        return None
    return t["kernel_s"] / t["steps"] * 1e3
