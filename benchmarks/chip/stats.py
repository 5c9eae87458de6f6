"""Order statistics shared by the harness and its metric readers."""

from __future__ import annotations

import statistics


def quantile(values, q: float) -> float:
    """The q-quantile (``statistics.quantiles``, inclusive method, at
    whole percents) of at least one value."""
    values = sorted(values)
    if len(values) == 1:
        return float(values[0])
    cut = statistics.quantiles(values, n=100, method="inclusive")
    return float(cut[round(q * 100) - 1])
