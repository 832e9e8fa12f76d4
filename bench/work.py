"""Operations and bytes of a layer, from its shapes alone.

A layer's work is what the algorithm needs, never what one
implementation moves: the same DCL reads the same work whatever kernel
computes it.  Operations count a multiply-add as two.  Bytes are the
layer's input, weights and output, each read or written once, at the
datapath's element width (``at_width``).
"""
from __future__ import annotations

K = 3                    # the DCLs are 3x3
OFFSET_CHANNELS = 2 * K * K


def conv(name, n, ho, wo, k, cin, cout, *, h=None):
    """A k x k convolution to (n, ho, wo, cout); ``h`` is the input's
    height and width when they differ from the output's."""
    h = ho if h is None else h
    return {"kind": "conv", "name": name,
            "ops": 2 * n * ho * wo * k * k * cin * cout,
            "elems": n * h * h * cin + k * k * cin * cout + n * ho * wo * cout}


def dcl(name, n, h, w, ho, wo, c, m):
    """A 3x3 deformable convolution C -> M with its offset convolution
    (2*K*K outputs per position): ``2 N Ho Wo K^2 C (M + 2 K^2)``."""
    return {"kind": "dcl", "name": name,
            "ops": 2 * n * ho * wo * K * K * c * (m + OFFSET_CHANNELS),
            "elems": (n * h * w * c + K * K * c * (m + OFFSET_CHANNELS)
                      + n * ho * wo * m)}


def at_width(layer: dict, bytes_per_elem: int) -> int:
    return layer["elems"] * bytes_per_elem


def least_seconds(layers, peaks: dict) -> float:
    """Least time the chip could take for ``layers``: per layer the larger
    of operations over the peak of its datapath and bytes over the HBM
    bandwidth, summed."""
    return sum(max(w["ops"] / peaks[w["datapath"]],
                   w["bytes"] / peaks["hbm_bytes_per_s"]) for w in layers)


def compute_seconds(layers, peaks: dict) -> float:
    """Operations over the peak of each layer's datapath, summed: the
    numerator of a model-FLOP utilization."""
    return sum(w["ops"] / peaks[w["datapath"]] for w in layers)
