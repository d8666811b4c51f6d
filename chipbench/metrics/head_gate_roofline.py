"""Share of its roofline the fused head + gate kernel reaches: the least
time the chip could take for the algorithm's operations and bytes
(``costs/fused_head_gate.py``; bound by bytes at these shapes) over the
kernel's mean device time per step, from the trace."""

from chipbench import trace as T
from chipbench.steps import HEAD_GATE_KERNEL, step_runs


def read(run):
    runs = step_runs(run)
    if not runs or run.peak is None:
        return None
    lo, hi = run.trace.window
    kernel = T.seconds(T.matching(T.clip(run.trace.ops[0], lo, hi),
                                  HEAD_GATE_KERNEL))
    if kernel <= 0:
        return None
    least = max(run.costs["head_flops"] / run.peak["bf16_flops_per_s"],
                run.costs["head_bytes"] / run.peak["hbm_bytes_per_s"])
    return 100.0 * least * len(runs) / kernel
