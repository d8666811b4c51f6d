"""95th percentile of the transport's per-window remote round trip
(``TransportStats`` latency ring, windows of this run's window)."""

from chipbench.stats import percentile


def read(run):
    p = percentile(run.remote_windows, 95)
    return None if p is None else p * 1e3
