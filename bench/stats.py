"""Order statistics used by the metric readers."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (0..100) of ``values`` by linear
    interpolation between closest ranks; ``None`` for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
