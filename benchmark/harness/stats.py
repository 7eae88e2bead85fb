"""Exact statistics over every sample of a window."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest rank: the smallest sample with at least ``q`` of the
    samples at or below it (a value that was measured, no
    interpolation)."""
    if not values:
        return None
    ranked = sorted(values)
    return ranked[max(0, math.ceil(q * len(ranked)) - 1)]


def mean(values: Sequence[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None
