"""Mean device time per dispatch of the jitted gated local step (trunk +
fused head + gate), from the profiler trace."""

from chipbench.steps import step_seconds


def read(run):
    s = step_seconds(run)
    return None if s is None else s * 1e3
