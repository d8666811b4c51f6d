"""Fused local-head -> confidence-gate Pallas TPU kernel.

The local tier's final projection produces ``[B, C]`` logits whose only
consumer is the confidence gate (``kernels/confidence_gate``): one
supervisor score, one argmax, one thresholded bottom-k. Materialising
those logits in HBM just to stream them back into the gate's scoring
pass doubles the hot path's HBM traffic for a tensor nothing else ever
reads. This kernel fuses the two: each grid step loads one ``[BB, D]``
hidden block and one ``[D, VB]`` slice of the head weight, computes the
``[BB, VB]`` logits tile on the MXU *in VMEM*, and folds it straight
into the same online-softmax running statistics the standalone gate
keeps (``_fold_stats`` — exact rescaling on every new running max). The
full-vocab logits never exist outside a VMEM tile; only the compact
``(conf [B], pred [B], idx [k])`` triple leaves the device.

Grid: (batch blocks, vocab blocks) with the vocab dimension innermost
("arbitrary") so the per-row scratch carries across vocab steps —
identical to the score kernel's schedule, plus one ``[BB, D] x [D, VB]``
dot per step (``preferred_element_type=f32`` keeps the MXU accumulator
in full precision). Selection is the gate's ``select_pallas``, applied
by the op (``fused_head_gate/ops.py``): thresholded ascending bottom-k
over the [B] confidences with SMEM-scalar ``t_local``/``n_valid``, so
runtime retuning (paper §4.5) never recompiles. Per-row scratch and
outputs are ``[BB, 1]`` columns and the bias is a ``[1, C]`` row (the
gate's layout rules, see ``confidence_gate/kernel.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.confidence_gate.kernel import (_fold_stats, _init_stats,
                                                  _stats_epilogue, row_outputs,
                                                  row_spec, stats_scratch)


def _head_gate_kernel(h_ref, w_ref, b_ref, conf_ref, pred_ref,
                      m1, m2, s, t, s2, a1, *, nv: int, vb: int,
                      supervisor: str):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_stats(m1, m2, s, t, s2, a1)

    h = h_ref[...].astype(jnp.float32)                     # [BB, D]
    w = w_ref[...].astype(jnp.float32)                     # [D, VB]
    x = jnp.dot(h, w, preferred_element_type=jnp.float32)  # logits tile
    x = x + b_ref[...]                                     # [1, VB]
    _fold_stats(x, j * vb, m1, m2, s, t, s2, a1)

    @pl.when(j == nv - 1)
    def _finish():
        _stats_epilogue(conf_ref, pred_ref, m1, m2, s, t, s2, a1,
                        supervisor=supervisor)


@functools.partial(jax.jit, static_argnames=("supervisor", "bb", "vb",
                                             "interpret"))
def head_gate_scores_pallas(hidden: jnp.ndarray, w: jnp.ndarray,
                            bias: jnp.ndarray, *, supervisor: str,
                            bb: int = 8, vb: int = 128,
                            interpret: bool = False
                            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """hidden [B, D] (B % bb == 0), w [D, C] (C % vb == 0), bias [C] ->
    (conf [B], pred [B])."""
    b, d = hidden.shape
    dw, v = w.shape
    assert d == dw and bias.shape == (v,), (hidden.shape, w.shape,
                                            bias.shape)
    assert b % bb == 0 and v % vb == 0, (b, v, bb, vb)
    nb, nv = b // bb, v // vb
    conf, pred = pl.pallas_call(
        functools.partial(_head_gate_kernel, nv=nv, vb=vb,
                          supervisor=supervisor),
        grid=(nb, nv),
        in_specs=[pl.BlockSpec((bb, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((d, vb), lambda i, j: (0, j)),
                  pl.BlockSpec((1, vb), lambda i, j: (0, j))],
        out_specs=(row_spec(bb), row_spec(bb)),
        out_shape=row_outputs(b),
        scratch_shapes=stats_scratch(bb),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="head_gate_scores_pallas",   # the device trace's op name
    )(hidden, w, bias.reshape(1, v))
    return conf[:, 0], pred[:, 0]
