"""The DCL kernels' share of their roofline: the least time of one
step's DCLs (per layer the larger of operations over the datapath's
peak and bytes over HBM bandwidth) over their device time per step."""
from bench.work import least_seconds


def read(rec):
    t = rec.trace
    if not t or not t["kernel_s"] or not t["steps"]:
        return None
    dcls = [w for w in rec.work_per_step if w["kind"] == "dcl"]
    return least_seconds(dcls, rec.peaks) / (t["kernel_s"] / t["steps"]) \
        * 100
