"""Jit-friendly public wrapper for the fused local-head -> gate kernel.

On TPU dispatches to the fused Pallas kernel (logits tiles live only in
VMEM; just ``(conf, pred, idx)`` leaves the device); on any other backend
it takes the jnp oracle (or, with ``force_pallas``, the kernel body in
Pallas interpret mode) so the serving engine uses one API everywhere.
Padding mirrors ``confidence_gate``: vocab padding adds zero weight
columns with ``-1e30`` bias (so padded logits carry no softmax mass and
never win the argmax); batch padding adds zero rows, cut before
selection.

``FusedLocalHead`` is the engine-facing carrier: a local model split as
``trunk`` (inputs -> hidden [B, D]) plus the final projection ``(w
[D, C], bias [C])``. ``CascadeEngine`` accepts it anywhere a plain
``local_apply`` is accepted and routes the gate through this fused op.
A ``counted`` trunk also returns device counters (``{name: array}``, e.g.
an expert layer's routed rows), which ride to the host with the gate's
outputs.

Selection and the ``gather`` hook are the standalone gate's (see
confidence_gate.ops); one ``use_pallas`` decision steers both passes.
"""

from __future__ import annotations

from collections.abc import Callable
from dataclasses import dataclass

import jax.numpy as jnp

from repro.kernels.confidence_gate.ops import (_on_tpu, select_candidates,
                                               use_pallas)
from repro.kernels.confidence_gate.ref import gate_scores_ref
from repro.kernels.fused_head_gate.kernel import head_gate_scores_pallas
from repro.kernels.fused_head_gate.ref import head_logits_ref

NEG = -1e30


@dataclass(frozen=True)
class FusedLocalHead:
    """Local model split for head->gate fusion: ``trunk`` maps the local
    input batch to hidden states [B, D]; ``(w, bias)`` is the final
    projection the fused kernel folds into the gate's scoring pass.

    Calling it composes the pieces (useful for oracles/tests): it is a
    drop-in ``local_apply`` that materialises full logits.
    """

    trunk: Callable[[jnp.ndarray], jnp.ndarray]
    w: jnp.ndarray                                         # [D, C]
    bias: jnp.ndarray | None = None                        # [C]
    counted: bool = False       # trunk returns (hidden, {name: counter})

    def split(self, local_batch) -> tuple[jnp.ndarray, dict | None]:
        """(hidden [B, D], the trunk's counters or None)."""
        out = self.trunk(local_batch)
        return out if self.counted else (out, None)

    def __call__(self, local_batch) -> jnp.ndarray:
        return head_logits_ref(self.split(local_batch)[0], self.w, self.bias)


def head_gate_scores(hidden: jnp.ndarray, w: jnp.ndarray,
                     bias: jnp.ndarray | None = None, *,
                     supervisor="max_softmax", bb: int = 8, vb: int = 128,
                     pallas: bool = False, interpret: bool = False
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """hidden [B, D], w [D, C], bias [C]|None -> (conf [B], pred [B]),
    the fused projection + scoring pass; ``pallas`` picks the kernel over
    the jnp oracle."""
    b, d = hidden.shape
    dw, v = w.shape
    if d != dw:
        raise ValueError(f"hidden dim {d} != head dim {dw}")
    if not pallas:
        return gate_scores_ref(head_logits_ref(hidden, w, bias),
                               supervisor=supervisor)
    bias = jnp.zeros((v,), jnp.float32) if bias is None else \
        jnp.asarray(bias, jnp.float32)
    pad_b = (-b) % bb
    pad_v = (-v) % vb
    if pad_v:                     # zero weights + NEG bias: logits = -1e30
        w = jnp.pad(w, ((0, 0), (0, pad_v)))
        bias = jnp.pad(bias, (0, pad_v), constant_values=NEG)
    if pad_b:
        hidden = jnp.pad(hidden, ((0, pad_b), (0, 0)))
    conf, pred = head_gate_scores_pallas(
        hidden, w, bias, supervisor=supervisor, bb=bb, vb=vb,
        interpret=interpret or not _on_tpu())
    return conf[:b], pred[:b]


def fused_head_gate(hidden: jnp.ndarray, w: jnp.ndarray,
                    bias: jnp.ndarray | None = None, t_local=None,
                    n_valid=None, *, supervisor="max_softmax",
                    k: int | None = None, bb: int = 8, vb: int = 128,
                    force_pallas: bool = False, interpret: bool = False,
                    gather=None) -> dict[str, jnp.ndarray]:
    """hidden [B, D], w [D, C], bias [C]|None -> {conf [B], pred [B],
    idx [k]} without materialising the [B, C] logits in HBM.

    Same contract as ``confidence_gate`` (idx: ascending-confidence
    escalation candidates below ``t_local`` among rows ``< n_valid``,
    -1-padded; ``gather`` widens the confidences selection runs over).
    """
    pallas = use_pallas(supervisor, force_pallas)
    conf, pred = head_gate_scores(hidden, w, bias, supervisor=supervisor,
                                  bb=bb, vb=vb, pallas=pallas,
                                  interpret=interpret)
    conf_all = conf if gather is None else gather(conf)
    return {"conf": conf, "pred": pred,
            "idx": select_candidates(conf_all, t_local, n_valid, k=k,
                                     pallas=pallas, interpret=interpret)}
