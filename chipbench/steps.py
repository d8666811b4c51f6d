"""Where the gated local step and its gate kernel show in a device trace.

The engine jits ``make_gated_local_step``'s function, named ``step`` when
the early-emit callback is armed (a TPU under continuous batching) and
``gate`` otherwise, so its program is ``jit_step`` or ``jit_gate``. The
fused head + gate Pallas kernel carries its kernel function's name.
"""

from __future__ import annotations

from chipbench import trace as T

STEP_MODULES = ("jit_step", "jit_gate")
HEAD_GATE_KERNEL = "head_gate"


def step_runs(run) -> list:
    """The gated step's executions on device 0 inside the window."""
    if run.trace is None or not run.trace.ops:
        return []
    lo, hi = run.trace.window
    return [e for e in T.clip(run.trace.modules[0], lo, hi)
            if any(e.name.startswith(m) for m in STEP_MODULES)]


def step_seconds(run) -> float | None:
    runs = step_runs(run)
    return T.seconds(runs) / len(runs) if runs else None
