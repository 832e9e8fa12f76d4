"""The one request generator: a traffic file of parameters, read here.

A traffic file (``bench/traffic/<name>.json``) holds:

* ``loop``: ``"closed"`` keeps ``queued`` requests waiting in the engine
  at every step (a backlog: every step is a full batch), or ``"open"``
  sends on a schedule whatever the engine does (independent users);
* for ``"open"``: ``rate_per_s``, and ``arrivals``: ``"poisson"``
  (exponential gaps) or ``"bursty"`` (exponential gaps at
  ``rate_per_s * (on_s + off_s) / on_s`` during ``on_s`` seconds, none
  during the ``off_s`` seconds that follow: the same mean rate);
* optionally ``sizes``: square image sizes drawn uniformly per request
  (default: the configuration's bucket).

Every seed gets the same number of requests and the same set of gaps,
in an order drawn from the seed, so the seed changes which requests
arrive together and not how much work there is.  Request ``i``'s image
is the seeded pool image ``i % POOL``, rolled by an offset that grows
with ``i // POOL``: every request in a run is a distinct image, and
making one costs a copy, not a draw.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

POOL = 8


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, stream])


def load(root: pathlib.Path, name: str) -> dict:
    return json.loads((root / "bench" / "traffic" / f"{name}.json")
                      .read_text())


class Images:
    """Seeded request images of the traffic's sizes."""

    def __init__(self, seed: int, sizes):
        r = rng(seed, 1)
        self.sizes = list(sizes)
        self.pool = {s: r.standard_normal((POOL, s, s, 3), np.float32)
                     for s in self.sizes}
        self.size_of = rng(seed, 2).choice(self.sizes, size=1 << 16)

    def __call__(self, i: int) -> np.ndarray:
        s = int(self.size_of[i % len(self.size_of)])
        k = i // POOL
        return np.roll(self.pool[s][i % POOL], (37 * k % s, 61 * k % s),
                       axis=(0, 1))


def gaps(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Seconds from the window's start to each arrival of an open loop."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)                     # exponential quantiles
    g = rng(seed, 3).permutation(g)
    if traffic["arrivals"] == "poisson":
        t = np.cumsum(g)
        return t * (seconds / t[-1])
    if traffic["arrivals"] == "bursty":
        on, off = traffic["on_s"], traffic["off_s"]
        t = np.cumsum(g)
        t = t * (seconds * on / (on + off) / t[-1])   # on-time only
        return t + np.floor(t / on) * off
    raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
