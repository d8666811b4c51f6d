"""Operations and bytes the fused LM-head + confidence-gate kernel needs.

The algorithm projects each row once through the head, ``2 * B * D * V``
operations, and reads the head weight once in its served type, plus the
hidden rows, the bias row (absent for an LM head) and the per-row outputs
(f32 confidence and i32 prediction). A kernel that re-reads the weight
once per row block does more traffic than this; that is its inefficiency,
not the work.
"""

from __future__ import annotations


def cost(batch: int, d_model: int, vocab: int, w_bytes: int = 2,
         h_bytes: int = 2, bias: bool = False) -> tuple[float, float]:
    flops = 2.0 * batch * d_model * vocab
    nbytes = (d_model * vocab * w_bytes + batch * d_model * h_bytes
              + (vocab * 4 if bias else 0) + batch * (4 + 4))
    return flops, float(nbytes)
