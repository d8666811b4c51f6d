"""Share of the traced window in which the serving thread waited for
remote answers: 100 x the union of the program's ``cascade.remote_wait``
spans, clipped to the window, over the window. None when the trace holds
no such span (a program without them)."""

from chipbench import trace as T

SPAN = "cascade.remote_wait"


def read(run):
    if run.trace is None or run.trace.window is None:
        return None
    waits = [e for e in run.trace.host if e.name == SPAN]
    if not waits:
        return None
    lo, hi = run.trace.window
    return 100.0 * T.busy_seconds(waits, lo, hi) / (hi - lo)
