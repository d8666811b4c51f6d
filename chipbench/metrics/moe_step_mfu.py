"""The gated step's model operations over its mean device time, as a share
of the chip's bf16 peak, for a MoE trunk: the trunk's operations at the
rows the held experts computed (``moe.held_rows``, mean per dispatch,
``costs/moe_decoder.py``, from the cell's configuration) plus the LM
head's (``costs/fused_head_gate.py``)."""

from chipbench import counters
from chipbench.costs import moe_decoder as C
from chipbench.steps import step_seconds


def read(run):
    s = step_seconds(run)
    rows = counters.held_rows_per_layer(run)
    if s is None or rows is None or run.peak is None:
        return None
    trunk = C.trunk_flops(run.cell.config["model"], run.batch,
                          run.cell.config["seq_len"], rows)["total"]
    flops = trunk + run.costs["head_flops"]
    return 100.0 * flops / s / run.peak["bf16_flops_per_s"]
