"""Fused confidence-gate Pallas TPU kernels (sibling of kernels/maxconf).

The pipelined serving hot path (DESIGN.md §5) must decide *on device*
which rows of a local-tier logits batch escalate to the remote tier, so
that only the compact ``(conf, pred, idx)`` triple crosses the host
boundary instead of the full ``[B, C]`` logits.

Two kernels compose:

  * ``_score_kernel`` — one streaming pass over class blocks HBM->VMEM,
    maintaining online-softmax running statistics per row (exact
    rescaling on every new running max, flash-attention algebra):

        m1, a1 : running max logit + index  -> prediction, max-softmax
        m2     : running second-max logit   -> PCS
        s      : running sum exp(x - m1)    -> normaliser
        t      : running sum exp(x - m1)*x  -> entropy
        s2     : running sum exp(2(x - m1)) -> Gini (sum p^2 = s2 / s^2)

    The epilogue emits the confidence of the *one* supervisor the gate
    was built for (static arg), so a supervisor swap is a recompile, not
    a second pass.

  * ``_select_kernel`` — thresholded ascending top-k over the [B]
    confidence vector: k iterations of masked argmin (first-index tie
    break, matching a stable sort). Rows ``>= n_valid`` (padding) are
    excluded; once the running min reaches ``t_local`` every remaining
    slot is ``-1``. ``t_local``/``n_valid`` are SMEM scalars so runtime
    retuning (paper §4.5) never recompiles.

Grid: scoring is (batch blocks, class blocks) with the class dimension
innermost ("arbitrary") so per-row scratch carries across class steps;
selection is a single program over the padded row vector.

Layout: every per-row quantity (scratch, ``conf``/``pred`` outputs) is a
``[BB, 1]`` column, never a 1-D ``(BB,)`` block. Mosaic accepts a 2-D
block whose last dim equals the array's (1) and whose row dim is a
multiple of 8, so any ``bb % 8 == 0`` compiles; a 1-D block would have
to be the whole array or a multiple of 128 rows.

The op (``ops.py``) composes the two kernels, ``gate_scores_pallas``
and ``select_pallas``, so a data-parallel caller can score its row shard
per device and select over the gathered confidences (DESIGN.md §12).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30

SUPERVISORS = ("max_softmax", "pcs", "neg_entropy", "gini")


def _init_stats(m1, m2, s, t, s2, a1) -> None:
    """Reset the per-row online-softmax scratch at class block 0."""
    m1[...] = jnp.full_like(m1, NEG)
    m2[...] = jnp.full_like(m2, NEG)
    s[...] = jnp.zeros_like(s)
    t[...] = jnp.zeros_like(t)
    s2[...] = jnp.zeros_like(s2)
    a1[...] = jnp.zeros_like(a1)


def _fold_stats(x, col0, m1, m2, s, t, s2, a1) -> None:
    """Fold one ``[BB, VB]`` logits block (global column offset ``col0``)
    into the running statistics, rescaling on every new running max
    (flash-attention algebra). Shared by the logits-input score kernel
    and the fused head->gate kernel, which materialises ``x`` from the
    projection inside the same VMEM tile."""
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

    # every per-row statistic stays a [BB, 1] column (module docstring)
    bm1 = jnp.max(x, axis=1, keepdims=True)                # block max
    ba1 = jnp.argmax(x, axis=1, keepdims=True).astype(jnp.int32) + col0
    xm = jnp.where(col == ba1, NEG, x)
    bm2 = jnp.max(xm, axis=1, keepdims=True)               # block 2nd max
    e = jnp.exp(x - bm1)
    bs = jnp.sum(e, axis=1, keepdims=True)
    bt = jnp.sum(e * x, axis=1, keepdims=True)
    bs2 = jnp.sum(e * e, axis=1, keepdims=True)

    om1, om2, os, ot, os2, oa1 = (m1[...], m2[...], s[...], t[...],
                                  s2[...], a1[...])
    nm1 = jnp.maximum(om1, bm1)
    # merged 2nd max: best of (loser of the two maxes, both second maxes)
    nm2 = jnp.maximum(jnp.minimum(om1, bm1), jnp.maximum(om2, bm2))
    c_old = jnp.exp(om1 - nm1)
    c_new = jnp.exp(bm1 - nm1)
    m1[...] = nm1
    m2[...] = nm2
    s[...] = os * c_old + bs * c_new
    t[...] = ot * c_old + bt * c_new
    s2[...] = os2 * c_old * c_old + bs2 * c_new * c_new
    a1[...] = jnp.where(bm1 > om1, ba1, oa1)


def _stats_epilogue(conf_ref, pred_ref, m1, m2, s, t, s2, a1, *,
                    supervisor: str) -> None:
    """Emit the one supervisor's confidence + prediction from the final
    running statistics (static supervisor: a swap is a recompile)."""
    zf = s[...]
    pred_ref[...] = a1[...]
    if supervisor == "max_softmax":
        conf_ref[...] = 1.0 / zf                           # exp(m1-m1)/s
    elif supervisor == "pcs":
        conf_ref[...] = (1.0 - jnp.exp(m2[...] - m1[...])) / zf
    elif supervisor == "neg_entropy":
        conf_ref[...] = t[...] / zf - (m1[...] + jnp.log(zf))
    elif supervisor == "gini":
        conf_ref[...] = s2[...] / (zf * zf)
    else:  # pragma: no cover - guarded in ops.py
        raise ValueError(f"unknown supervisor {supervisor!r}")


def _score_kernel(x_ref, conf_ref, pred_ref, m1, m2, s, t, s2, a1, *,
                  nv: int, vb: int, supervisor: str):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_stats(m1, m2, s, t, s2, a1)

    x = x_ref[...].astype(jnp.float32)                     # [BB, VB]
    _fold_stats(x, j * vb, m1, m2, s, t, s2, a1)

    @pl.when(j == nv - 1)
    def _finish():
        _stats_epilogue(conf_ref, pred_ref, m1, m2, s, t, s2, a1,
                        supervisor=supervisor)


def _select_kernel(t_ref, n_ref, conf_ref, idx_ref, *, k: int, bp: int):
    t = t_ref[0]
    n = n_ref[0]
    conf = conf_ref[...]                                   # [1, BP]
    cols = jax.lax.broadcasted_iota(jnp.int32, conf.shape, 1)
    conf = jnp.where(cols < n, conf, jnp.inf)              # mask padding

    def body(i, c):
        mv = jnp.min(c)
        sel = jnp.min(jnp.where(c == mv, cols, bp))        # first-index tie
        take = mv < t
        idx_ref[i] = jnp.where(take, sel, -1)
        return jnp.where((cols == sel) & take, jnp.inf, c)

    jax.lax.fori_loop(0, k, body, conf)


def row_spec(bb: int) -> pl.BlockSpec:
    """Block of a per-row ``[B, 1]`` output on the (batch, vocab) grid."""
    return pl.BlockSpec((bb, 1), lambda i, j: (i, 0))


def row_outputs(b: int) -> tuple[jax.ShapeDtypeStruct, jax.ShapeDtypeStruct]:
    """``(conf [B, 1] f32, pred [B, 1] i32)`` output shapes."""
    return (jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32))


def stats_scratch(bb: int) -> list:
    """VMEM for the running statistics (m1, m2, s, t, s2; a1)."""
    return ([pltpu.VMEM((bb, 1), jnp.float32)] * 5
            + [pltpu.VMEM((bb, 1), jnp.int32)])


@functools.partial(jax.jit, static_argnames=("supervisor", "bb", "vb",
                                             "interpret"))
def gate_scores_pallas(logits: jnp.ndarray, *, supervisor: str,
                       bb: int = 8, vb: int = 128,
                       interpret: bool = False
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """logits [B, C] (B % bb == 0, C % vb == 0) -> (conf [B], pred [B])."""
    b, v = logits.shape
    assert b % bb == 0 and v % vb == 0, (b, v, bb, vb)
    assert supervisor in SUPERVISORS, supervisor
    nb, nv = b // bb, v // vb
    conf, pred = pl.pallas_call(
        functools.partial(_score_kernel, nv=nv, vb=vb, supervisor=supervisor),
        grid=(nb, nv),
        in_specs=[pl.BlockSpec((bb, vb), lambda i, j: (i, j))],
        out_specs=(row_spec(bb), row_spec(bb)),
        out_shape=row_outputs(b),
        scratch_shapes=stats_scratch(bb),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="gate_scores_pallas",        # the device trace's op name
    )(logits)
    return conf[:, 0], pred[:, 0]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def select_pallas(conf: jnp.ndarray, t_local: jnp.ndarray,
                  n_valid: jnp.ndarray, *, k: int,
                  interpret: bool = False) -> jnp.ndarray:
    """conf [B], t_local f32 scalar (+inf = no threshold), n_valid i32
    scalar -> idx [k]: ascending-confidence rows below ``t_local`` among
    rows ``< n_valid``, -1-padded."""
    b = conf.shape[0]
    bp = b + (-b) % 128                                    # lane-align rows
    conf_row = jnp.full((1, bp), jnp.inf, jnp.float32).at[0, :b].set(conf)
    return pl.pallas_call(
        functools.partial(_select_kernel, k=k, bp=bp),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((k,), jnp.int32),
        interpret=interpret,
        name="select_pallas",             # the device trace's op name
    )(jnp.asarray(t_local, jnp.float32).reshape(1),
      jnp.asarray(n_valid, jnp.int32).reshape(1), conf_row)
