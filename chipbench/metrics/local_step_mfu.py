"""The gated step's model operations (trunk + head, ``chipbench/costs``)
over its mean device time, as a share of the chip's bf16 peak."""

from chipbench.steps import step_seconds


def read(run):
    s = step_seconds(run)
    if s is None or run.peak is None:
        return None
    flops = run.costs["trunk_flops"] + run.costs["head_flops"]
    return 100.0 * flops / s / run.peak["bf16_flops_per_s"]
