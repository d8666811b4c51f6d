"""jnp oracle for the grouped expert product: each group's rows times its
own weight, in float32 accumulation, rows past the groups' total zero."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.expert_gmm.kernel import silu


def expert_gmm_ref(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray,
                   up: jnp.ndarray | None = None) -> jnp.ndarray:
    """lhs [m, k] sorted by group, rhs [G, k, n], group_sizes [G] -> [m, n]
    in lhs's type; with ``up``, ``silu(lhs @ rhs) * (lhs @ up)``."""
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(lhs.shape[0])
    group = jnp.searchsorted(ends, row, side="right")      # G past the end
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g in range(rhs.shape[0]):
        y = jnp.dot(lhs, rhs[g], preferred_element_type=jnp.float32)
        if up is not None:
            y = silu(y) * jnp.dot(lhs, up[g],
                                  preferred_element_type=jnp.float32)
        out = jnp.where((group == g)[:, None], y, out)
    return out.astype(lhs.dtype)
