"""95th percentile of due time -> hand-back over the requests due in the
window that the 1st-level supervisor trusted (answered locally)."""

from chipbench.stats import latency_p95_ms


def read(run):
    return latency_p95_ms(run.records, local_only=True)
