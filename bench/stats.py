"""Small statistics the harness and the metric readers share."""
from __future__ import annotations

import math
from typing import List, Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The ``q`` quantile by nearest rank: the smallest value with at least
    a share ``q`` of the values at or below it."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def intervals(start: float, completions: Sequence[float]) -> List[float]:
    """Time from each completion to the next, the first from ``start``."""
    out, prev = [], start
    for c in completions:
        out.append(c - prev)
        prev = c
    return out
