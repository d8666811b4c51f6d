"""Share of its roofline the expert layer's grouped product reaches: the
least time the chip could take for the algorithm's operations and bytes
at the rows the held experts computed (``moe.held_rows``, mean per
dispatch, ``costs/moe_decoder.py``), summed over the MoE layers, over the
``expert_gmm`` kernels' device time per step, from the trace (ops named
``expert_gmm``, not the ops that consume their output). The costs
come from the cell's configuration (the harness counts every
configuration as a dense decoder)."""

from chipbench import counters
from chipbench import trace as T
from chipbench.costs import moe_decoder as C
from chipbench.steps import step_runs

KERNEL = "expert_gmm"


def read(run):
    runs = step_runs(run)
    rows = counters.held_rows_per_layer(run)
    if not runs or rows is None or run.peak is None:
        return None
    lo, hi = run.trace.window
    # an op's trace name is its HLO text: the kernel's own instructions
    # start with its name, ops that read its output only mention it
    kernel = T.seconds([e for e in T.clip(run.trace.ops[0], lo, hi)
                        if e.name.lstrip("%").startswith(KERNEL)])
    if kernel <= 0:
        return None
    sizes = run.cell.config["model"]
    least = sum(max(C.routed_flops(sizes, r) / run.peak["bf16_flops_per_s"],
                    C.expert_gmm_bytes(sizes, r)
                    / run.peak["hbm_bytes_per_s"]) for r in rows)
    return 100.0 * least * len(runs) / kernel
