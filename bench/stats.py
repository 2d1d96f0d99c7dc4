"""End-to-end arithmetic: workflow latency percentiles and tokens served
per second.  The percentile is the nearest-rank one of
``repro.core.executor.nearest_rank_p95``, copied so that the yardstick
does not move with the program."""
from __future__ import annotations

import math
from typing import Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The nearest-rank ``q``-quantile (0 < q <= 1): the smallest value
    with at least ``q`` of the sample at or below it."""
    if not values:
        raise ValueError("no values")
    if not 0.0 < q <= 1.0:
        raise ValueError(f"quantile {q} outside (0, 1]")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def tokens_per_s(stage_ends: Sequence[float], tokens_per_stage: int,
                 window_start: float, seconds: float) -> float:
    """Tokens of the stages that completed inside the window, over the
    window's length."""
    end = window_start + seconds
    done = sum(1 for t in stage_ends if window_start <= t <= end)
    return done * tokens_per_stage / seconds
