"""Device counters a counted local tier returns with each gated step
(``CascadeEngine`` records them on its observability trace, so a traced
run's ``Run.spans`` holds them beside the per-request spans)."""

from __future__ import annotations

import numpy as np

HELD_ROWS = "moe.held_rows"


def values(run, name: str) -> list:
    """The counter's value for every dispatch recorded inside the measured
    window, as arrays; empty without a trace or without the counter."""
    if not run.spans:
        return []
    lo, hi = run.opened, run.opened + run.seconds
    return [np.asarray(s["value"]) for s in run.spans
            if s.get("counter") == name and lo <= s["t"] < hi]


def held_rows_per_layer(run) -> list | None:
    """Mean rows the held experts computed in each MoE layer of a
    dispatch (``moe.held_rows`` summed over the held experts), or None."""
    got = values(run, HELD_ROWS)
    if not got:
        return None
    return [float(r) for r in np.mean([v.sum(-1) for v in got], axis=0)]
