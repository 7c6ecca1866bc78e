"""Percentile and window arithmetic, kept with the yardstick."""

from __future__ import annotations

import math
from typing import Iterable, List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of no samples")
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def tokens_in_window(landings: Iterable[Tuple[float, int]], t0: float, t1: float) -> int:
    """Tokens whose landing time lies in [t0, t1]; ``landings`` is (time, n)."""
    return sum(n for t, n in landings if t0 <= t <= t1)


def gaps_in_window(landings: Sequence[Tuple[float, int]], t0: float, t1: float) -> List[float]:
    """Gaps between one request's output tokens, one sample per token after
    the first that landed inside [t0, t1]. An iteration that lands n tokens
    dt after the previous one gives n samples of dt/n."""
    out: List[float] = []
    prev = None
    for t, n in landings:
        if n <= 0:
            continue
        if prev is None:
            n -= 1  # the first token has no gap before it
            prev = t
            if n <= 0:
                continue
        if t0 <= t <= t1 and n > 0:
            out.extend([(t - prev) / n] * n)
        prev = t
    return out


def spread(values: Sequence[float]) -> float:
    """Distance between the quartiles over the median, as the contract
    takes it (``statistics.quantiles(values, n=4)``)."""
    import statistics

    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
