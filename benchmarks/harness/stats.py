"""Percentiles, latencies from due times, and the generator's lateness."""

from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0-100), linear between closest ranks (numpy's
    default). None of an empty sample."""
    if not values:
        return None
    xs = sorted(values)
    rank = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(rank), math.ceil(rank)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median: the spread the bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def ttft_ms(due: float, token_times: Sequence[float]) -> Optional[float]:
    """Open loop: from the instant the request was DUE, not sent, so a
    stalled generator or server charges the wait to the requests behind."""
    if not token_times:
        return None
    return (token_times[0] - due) * 1e3


def gaps_ms(token_times: Sequence[float], lo: float = -math.inf,
            hi: float = math.inf) -> List[float]:
    """Gaps between consecutive tokens of one request, for the tokens
    received in [lo, hi]."""
    return [(b - a) * 1e3 for a, b in zip(token_times, token_times[1:])
            if lo <= b <= hi]


def lateness_ms(due: Iterable[float], sent: Iterable[float]) -> List[float]:
    return [max(0.0, (s - d) * 1e3) for d, s in zip(due, sent)]
