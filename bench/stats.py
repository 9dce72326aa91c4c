"""Order statistics the metrics share."""

from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float, missing: int = 0) -> float:
    """Nearest-rank ``q``-th percentile of ``values`` with ``missing``
    more samples counted as infinitely late (never decided)."""
    v = np.sort(np.asarray(values, np.float64))
    n = v.size + int(missing)
    if n == 0:
        raise ValueError("no samples")
    k = max(1, math.ceil(q / 100.0 * n))
    return float(v[k - 1]) if k <= v.size else math.inf


def due_latency_ms(result: dict, q: float) -> float:
    """``q``-th percentile of an open loop's latency from due time, in ms,
    over every packet due in the window."""
    lat = (result["done"] - result["due"]) * 1e3
    return percentile(lat, q, missing=result["attempted"] - result["decided"])
