"""The benchmark's own arithmetic for tails."""
from __future__ import annotations

import math


def percentile(values, q):
    """Nearest-rank percentile: the smallest sample with at least q % of
    the samples at or below it. None for no samples."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]
