"""Order statistics shared by the metric readers."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank ``q`` percentile; ``None`` (a missing answer) ranks
    as infinitely late."""
    xs = sorted(math.inf if v is None else float(v) for v in values)
    if not xs:
        return math.nan
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


def whole_window_rate(studies, window_start: float) -> float:
    """Configurations of every study over the time from the window's
    start to the end of the last study."""
    if not studies:
        return math.nan
    end = max(s["t_end"] for s in studies)
    return sum(s["n_configs"] for s in studies) / (end - window_start)
