"""The percentile the metric readers share."""

import math


def percentile(values, q):
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    vals = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]

