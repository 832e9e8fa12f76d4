"""95th percentile, over every request due in the window, of the time from its
scheduled send to its retirement; a request not served ok counts as
missing."""
import math

from bench.checks import percentile


def read(rec):
    v = percentile(sorted(rec.latencies_s), 0.95)
    return v * 1e3 if math.isfinite(v) else None
