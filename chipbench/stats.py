"""Order statistics the metric readers share."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float | None:
    """Nearest-rank ``q``-th percentile (0-100) of ``values``: the
    smallest value with at least ``q``% of the sample at or under it.
    None for an empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


def latency_p95_ms(records, local_only: bool = False) -> float | None:
    """p95 of due -> hand-back over requests due in the window; one never
    answered counts with the time it was waited for, as missing."""
    lat = [r["latency"] for r in records
           if not local_only or r["source"] == "local"]
    p = percentile(lat, 95)
    return None if p is None else p * 1e3
