"""Pure-jnp oracle for the fused confidence-gate kernel.

Semantics shared with the Pallas kernel (kernel.py):

  * score every row of a logits batch with one softmax-family supervisor
    (or any callable ``logits -> confidence``) and take its argmax;
  * select up to ``k`` escalation candidates: the lowest-confidence rows,
    ascending by confidence (ties broken by lowest row index, matching a
    stable sort), restricted to rows ``< n_valid`` (padded scheduler
    replicas are never escalated) and to ``conf < t_local`` when a
    threshold is given; unused slots are ``-1``.

Only the compact ``(conf [B], pred [B], idx [k])`` triple leaves the
device — never the full logits.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.supervisors import SOFTMAX_SUPERVISORS


def gate_scores_ref(logits: jnp.ndarray, *, supervisor="max_softmax"
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """logits [B, C] -> (conf [B] f32, pred [B] i32)."""
    sup = (supervisor if callable(supervisor)
           else SOFTMAX_SUPERVISORS[supervisor])
    return (sup(logits).astype(jnp.float32),
            jnp.argmax(logits, axis=-1).astype(jnp.int32))


def select_ref(conf: jnp.ndarray, t_local=None, n_valid=None, *,
               k: int | None = None) -> jnp.ndarray:
    """conf [B] -> idx [k] i32 (ascending-confidence escalation
    candidates, -1-padded)."""
    b = conf.shape[0]
    k = b if k is None else min(int(k), b)
    t = jnp.float32(jnp.inf) if t_local is None else \
        jnp.asarray(t_local, jnp.float32)
    n = jnp.int32(b) if n_valid is None else jnp.asarray(n_valid, jnp.int32)

    rows = jnp.arange(b, dtype=jnp.int32)
    masked = jnp.where(rows < n, conf, jnp.inf)
    order = jnp.argsort(masked).astype(jnp.int32)        # stable ascending
    # eligible rows form a prefix of the ascending order
    count = jnp.sum((masked[order[:k]] < t).astype(jnp.int32))
    return jnp.where(jnp.arange(k, dtype=jnp.int32) < count, order[:k], -1)


def confidence_gate_ref(logits: jnp.ndarray, t_local=None, n_valid=None, *,
                        supervisor="max_softmax",
                        k: int | None = None) -> dict[str, jnp.ndarray]:
    """logits [B, C] -> {conf [B] f32, pred [B] i32, idx [k] i32}."""
    conf, pred = gate_scores_ref(logits, supervisor=supervisor)
    return {"conf": conf, "pred": pred,
            "idx": select_ref(conf, t_local, n_valid, k=k)}
