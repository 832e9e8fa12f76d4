"""The arithmetic of the comparisons that decide ``correct``."""
from __future__ import annotations

import numpy as np

OUTPUTS = ("cls", "box")


def _f64(a):
    return np.asarray(a, np.float64)


def worst_max_rel(got, want) -> float:
    """Over the requests, the largest of max|got - want| / max|want| of
    each output."""
    return max(float(np.max(np.abs(_f64(g[k]) - _f64(w[k])))
                     / max(float(np.max(np.abs(_f64(w[k])))), 1e-30))
               for g, w in zip(got, want) for k in OUTPUTS)


def worst_l2_rel(got, want) -> float:
    """Over the requests, the largest ||got - want|| / ||want|| of the
    request's outputs taken together."""
    worst = 0.0
    for g, w in zip(got, want):
        num = sum(float(np.sum((_f64(g[k]) - _f64(w[k])) ** 2))
                  for k in OUTPUTS)
        den = sum(float(np.sum(_f64(w[k]) ** 2)) for k in OUTPUTS)
        worst = max(worst, (num / max(den, 1e-300)) ** 0.5)
    return worst


def flip_share(got: dict, want: dict) -> float:
    """Over the layers, the largest share of outputs whose value on the
    layer's output grid differs from the reference's.  ``got[name]`` is
    the dequantized output; ``want[name]`` is (grid values, grid scale)."""
    worst = 0.0
    for name, (q, scale) in want.items():
        g = np.rint(_f64(got[name]) / scale)
        worst = max(worst, float(np.count_nonzero(g != _f64(q)) / g.size))
    return worst


def percentile(sorted_vals, q: float) -> float:
    """Nearest-rank sample percentile, 0 < q <= 1 (copied from the
    serving bench's exact percentile)."""
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1,
              max(0, int(np.ceil(q * len(sorted_vals))) - 1))
    return sorted_vals[idx]
