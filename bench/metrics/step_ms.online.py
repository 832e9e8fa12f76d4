"""Median host-clock time of the window's engine steps, each ending when
its results are on the host."""
from bench.checks import percentile


def read(rec):
    return percentile(sorted(e - s for s, e, _ in rec.steps), 0.5) * 1e3 \
        if rec.steps else None
