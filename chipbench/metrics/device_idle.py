"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals / window)."""

from chipbench import trace as T


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = run.trace.window
    return 100.0 * T.idle_share(run.trace.ops[0], lo, hi)
