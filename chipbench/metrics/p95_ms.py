"""95th percentile of due time -> hand-back over every request due in the
window (a failed request counts as missing)."""

from chipbench.stats import latency_p95_ms


def read(run):
    return latency_p95_ms(run.records)
