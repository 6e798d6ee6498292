"""Order statistics the benchmark reports.

``percentile`` is the nearest-rank percentile of the serving stack's own
statistics (``repro.core.telemetry.metrics.percentile``), copied here so
that a change to the program cannot move the yardstick.
"""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: ``sorted(values)[min(n - 1, int(q/100*n))]``,
    ``inf`` for no values; ``q`` is clamped to [0, 100]."""
    n = len(values)
    if n == 0:
        return math.inf
    q = min(100.0, max(0.0, q))
    idx = min(n - 1, int(q / 100.0 * n))
    return sorted(values)[idx]


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``, the default method)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
