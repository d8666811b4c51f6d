"""95th percentile of the scheduler's queue wait (``Response.queue_s``:
due time -> the dispatch of the request's window)."""

from chipbench.stats import percentile


def read(run):
    p = percentile([r["queue"] for r in run.records if r["answered"]], 95)
    return None if p is None else p * 1e3
