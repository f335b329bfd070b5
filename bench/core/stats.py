"""Percentiles the benchmark reports.

Percentiles are nearest-rank over every sample, with `inf` standing for a
request that failed or was refused: the p-th percentile turns infinite as
soon as more than (100 - p)% of the samples are.
"""
from __future__ import annotations

import math
from typing import Iterable, Optional

# A JSON result cannot carry infinity; an infinite latency prints as this.
INFINITE_MS = 1e12


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank q-th percentile (0 < q <= 100); None for no samples."""
    xs = sorted(values)
    if not xs:
        return None
    rank = max(math.ceil(q / 100.0 * len(xs)), 1)
    return xs[rank - 1]


def finite_or_sentinel(x: float) -> float:
    return x if math.isfinite(x) else INFINITE_MS

