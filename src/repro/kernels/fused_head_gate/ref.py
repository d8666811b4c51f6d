"""jnp oracle for the fused local-head -> confidence-gate op.

The fused kernel is algebraically the composition "project then gate":
materialise the logits with one matmul and delegate to the gate oracle.
The Pallas kernel must match this bitwise on the prediction/idx outputs
and to float tolerance on conf (same online-softmax rescaling algebra,
different summation order only across vocab blocks).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.confidence_gate.ref import confidence_gate_ref


def head_logits_ref(hidden: jnp.ndarray, w: jnp.ndarray,
                    bias: jnp.ndarray | None = None) -> jnp.ndarray:
    """hidden [B, D], w [D, C], bias [C] or None -> f32 logits [B, C]."""
    logits = jnp.dot(hidden.astype(jnp.float32), w.astype(jnp.float32))
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)[None, :]
    return logits


def fused_head_gate_ref(hidden: jnp.ndarray, w: jnp.ndarray,
                        bias: jnp.ndarray | None = None, t_local=None,
                        n_valid=None, *, supervisor="max_softmax",
                        k: int | None = None) -> dict[str, jnp.ndarray]:
    """hidden [B, D], w [D, C], bias [C] or None -> {conf, pred, idx}."""
    return confidence_gate_ref(head_logits_ref(hidden, w, bias), t_local,
                               n_valid, supervisor=supervisor, k=k)
