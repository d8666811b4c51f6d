"""Jit'd public wrapper for the fused confidence-gate kernel.

On TPU dispatches to the Pallas kernels; on any other backend it takes
the jnp oracle (or, with ``force_pallas``, runs the kernel bodies in
Pallas interpret mode, which is how the CPU tests exercise them), so the
serving engine uses one API everywhere. Pads the batch/class dims to
block multiples when needed (class padding uses -1e30 so softmax mass
and argmax are unaffected; padded rows are cut before selection).

Callable supervisors (e.g. a bound MDSA, paper §4.2) always take the
jnp path, scoring and selection alike — the Pallas scoring kernel is
specialised to the softmax family it can compute from online
statistics. ``use_pallas`` makes that decision once per gate and both
passes follow it.

The gate is two passes: ``gate_scores`` (the per-row supervisor
confidence and argmax) and ``select_candidates`` (the thresholded
bottom-k over a ``[B]`` confidence vector). ``confidence_gate`` composes
them; its ``gather`` hook maps the local ``conf`` to the confidences
selection runs over, which is how a data-parallel step scores each row
shard on its own device and selects once over the all-gathered ``[B]``
(DESIGN.md §12). The serving engine's gated local step calls this op.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.confidence_gate.kernel import (SUPERVISORS,
                                                  gate_scores_pallas,
                                                  select_pallas)
from repro.kernels.confidence_gate.ref import gate_scores_ref, select_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas(supervisor, force_pallas: bool = False) -> bool:
    """Whether the gate runs as Pallas kernels: softmax-family supervisors
    on TPU (or when forced); callables always take jnp."""
    if callable(supervisor):
        return False
    if supervisor not in SUPERVISORS:
        raise ValueError(f"unknown supervisor {supervisor!r}; "
                         f"expected one of {SUPERVISORS}")
    return force_pallas or _on_tpu()


def gate_scores(logits: jnp.ndarray, *, supervisor="max_softmax",
                bb: int = 8, vb: int = 128, pallas: bool = False,
                interpret: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """logits [B, C] -> (conf [B] f32, pred [B] i32), the scoring pass;
    ``pallas`` picks the kernel over the jnp oracle."""
    if not pallas:
        return gate_scores_ref(logits, supervisor=supervisor)
    b, v = logits.shape
    pad_b = (-b) % bb
    pad_v = (-v) % vb
    if pad_v:
        logits = jnp.pad(logits, ((0, 0), (0, pad_v)), constant_values=-1e30)
    if pad_b:
        logits = jnp.pad(logits, ((0, pad_b), (0, 0)))
    conf, pred = gate_scores_pallas(logits, supervisor=supervisor, bb=bb,
                                    vb=vb,
                                    interpret=interpret or not _on_tpu())
    return conf[:b], pred[:b]


def select_candidates(conf: jnp.ndarray, t_local=None, n_valid=None, *,
                      k: int | None = None, pallas: bool = False,
                      interpret: bool = False) -> jnp.ndarray:
    """conf [B] -> idx [k]: up to ``k`` rows ascending by confidence, only
    rows ``< n_valid`` with ``conf < t_local``; unused slots are -1.
    ``pallas`` picks the kernel over the jnp oracle."""
    b = conf.shape[0]
    k = b if k is None else min(int(k), b)
    if not pallas:
        return select_ref(conf, t_local, n_valid, k=k)
    t = jnp.float32(jnp.inf) if t_local is None else \
        jnp.asarray(t_local, jnp.float32)
    n = jnp.int32(b) if n_valid is None else jnp.asarray(n_valid, jnp.int32)
    return select_pallas(conf, t, n, k=k,
                         interpret=interpret or not _on_tpu())


def confidence_gate(logits: jnp.ndarray, t_local=None, n_valid=None, *,
                    supervisor="max_softmax", k: int | None = None,
                    bb: int = 8, vb: int = 128, force_pallas: bool = False,
                    interpret: bool = False,
                    gather=None) -> dict[str, jnp.ndarray]:
    """logits [B, C] -> {conf [B], pred [B], idx [k]}.

    ``idx`` holds up to ``k`` escalation candidates: row indices ascending
    by confidence, only rows ``< n_valid`` with ``conf < t_local``
    (``t_local=None`` disables the threshold); unused slots are -1.
    ``t_local``/``n_valid`` may be traced values — retuning never
    recompiles. ``gather`` (``conf -> conf_all``) widens the confidences
    selection runs over; ``idx`` then indexes ``conf_all``.
    """
    pallas = use_pallas(supervisor, force_pallas)
    conf, pred = gate_scores(logits, supervisor=supervisor, bb=bb, vb=vb,
                             pallas=pallas, interpret=interpret)
    conf_all = conf if gather is None else gather(conf)
    return {"conf": conf, "pred": pred,
            "idx": select_candidates(conf_all, t_local, n_valid, k=k,
                                     pallas=pallas, interpret=interpret)}
