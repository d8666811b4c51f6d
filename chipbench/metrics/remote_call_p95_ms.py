"""95th percentile of one transport call's duration: the program's
``transport.call`` spans (a dispatch's escalated sub-batch, all of its
windows, retries and backoff) that start inside the traced window. None
when the trace holds no such span (a program without them)."""

from chipbench.stats import percentile

SPAN = "transport.call"


def read(run):
    if run.trace is None or run.trace.window is None:
        return None
    lo, hi = run.trace.window
    p = percentile([e.end - e.start for e in run.trace.host
                    if e.name == SPAN and lo <= e.start < hi], 95)
    return None if p is None else p * 1e3
