"""Statistics the metric readers share."""

from __future__ import annotations

import math

import numpy as np


def pct(values, q: float) -> float | None:
    """The q-th percentile (linear between order statistics); None when
    there is nothing to read.  A failed request enters as +inf."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return None
    if np.isinf(v).any():
        v = np.sort(v)
        k = (v.size - 1) * q / 100.0
        lo = math.floor(k)
        if math.isinf(v[min(lo + 1, v.size - 1)]) or math.isinf(v[lo]):
            return math.inf
    return float(np.percentile(v, q))


def ttft_ms(r) -> float:
    """Due time to first token; a request that failed never had one."""
    return (r.first - r.due) * 1e3 if r.status == "ok" else math.inf


def tpot_ms(r) -> float | None:
    """(finish - first token) / (tokens - 1); None for one-token replies."""
    if r.status != "ok":
        return math.inf
    n = len(r.tokens)
    return (r.finish - r.first) * 1e3 / (n - 1) if n > 1 else None
