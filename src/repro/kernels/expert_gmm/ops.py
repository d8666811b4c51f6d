"""Jit-friendly wrapper for the grouped expert product.

On TPU dispatches to the Pallas kernel (``expert_gmm/kernel.py``); on any
other backend it takes the jnp oracle, or with ``force_pallas`` the kernel
body in interpret mode. Rows are padded to the kernel's row tile here;
rows at or past ``sum(group_sizes)`` come back undefined on the kernel
path (zero on the oracle's), so callers mask them.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.confidence_gate.ops import _on_tpu
from repro.kernels.expert_gmm.kernel import expert_gmm_pallas
from repro.kernels.expert_gmm.ref import expert_gmm_ref

ROW_TILE = 512


def expert_gmm(lhs: jnp.ndarray, rhs: jnp.ndarray,
               group_sizes: jnp.ndarray, up: jnp.ndarray | None = None, *,
               tm: int = ROW_TILE, force_pallas: bool = False,
               interpret: bool = False) -> jnp.ndarray:
    """lhs [m, k] sorted by group, rhs [G, k, n], group_sizes [G] int32
    -> [m, n]: row r of group g is ``lhs[r] @ rhs[g]``, or with ``up``
    ``silu(lhs[r] @ rhs[g]) * (lhs[r] @ up[g])``."""
    if not (force_pallas or _on_tpu()):
        return expert_gmm_ref(lhs, rhs, group_sizes, up)
    m = lhs.shape[0]
    tm = min(tm, -(-m // 16) * 16)          # bf16 packs 16 rows a vreg
    pad = (-m) % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = expert_gmm_pallas(lhs, rhs, group_sizes.astype(jnp.int32), up,
                            tm=tm, interpret=interpret or not _on_tpu())
    return out[:m]
