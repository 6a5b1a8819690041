"""Order statistics used by the end-to-end metrics."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of a non-empty sample: the
    smallest value with at least q% of the sample at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]
