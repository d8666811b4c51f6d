"""Fused supervisor-confidence Pallas TPU kernel.

The 1st/2nd-level supervisors need (argmax, max-softmax, PCS, entropy) of
an LM-head output whose vocab runs to 152k. Done naively that is four
passes over the logits in HBM (softmax + top-k + entropy). This kernel
streams vocab blocks HBM->VMEM once, maintaining online-softmax style
running statistics per row:

    m1, a1 : running max logit + its index      -> prediction, max-softmax
    m2     : running second-max logit           -> PCS
    s      : running sum exp(x - m1)            -> normaliser
    t      : running sum exp(x - m1) * x        -> entropy via
             H = (m1 + log s) - t / s  ... with exact rescaling on every
             new m1 (identical algebra to flash-attention's online update).

Grid: (batch blocks, vocab blocks); vocab is the innermost ("arbitrary")
dimension so the per-row scratch carries across vocab steps. Block shapes
are (BB, VB) = (8, 2048) by default — 64 KiB of VMEM per logits tile,
MXU-independent (pure VPU reductions).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _kernel(x_ref, pred_ref, ms_ref, pcs_ref, ent_ref,
            m1, m2, s, t, a1, *, nv: int, vb: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m1[...] = jnp.full_like(m1, NEG)
        m2[...] = jnp.full_like(m2, NEG)
        s[...] = jnp.zeros_like(s)
        t[...] = jnp.zeros_like(t)
        a1[...] = jnp.zeros_like(a1)

    x = x_ref[...].astype(jnp.float32)                     # [BB, VB]
    col = j * vb + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

    bm1 = jnp.max(x, axis=1)                               # block max
    ba1 = jnp.argmax(x, axis=1).astype(jnp.int32) + j * vb
    x2 = jnp.where(col == ba1[:, None] , NEG, x)
    bm2 = jnp.max(x2, axis=1)                              # block 2nd max
    bs = jnp.sum(jnp.exp(x - bm1[:, None]), axis=1)
    bt = jnp.sum(jnp.exp(x - bm1[:, None]) * x, axis=1)

    om1, om2, os, ot, oa1 = m1[...], m2[...], s[...], t[...], a1[...]
    nm1 = jnp.maximum(om1, bm1)
    # merged second max: best of (loser of the two maxes, both second maxes)
    nm2 = jnp.maximum(jnp.minimum(om1, bm1), jnp.maximum(om2, bm2))
    c_old = jnp.exp(om1 - nm1)
    c_new = jnp.exp(bm1 - nm1)
    m1[...] = nm1
    m2[...] = nm2
    s[...] = os * c_old + bs * c_new
    t[...] = ot * c_old + bt * c_new
    a1[...] = jnp.where(bm1 > om1, ba1, oa1)

    @pl.when(j == nv - 1)
    def _finish():
        zf = s[...]
        pred_ref[...] = a1[...]
        ms_ref[...] = 1.0 / zf                               # exp(m1-m1)/s
        pcs_ref[...] = (1.0 - jnp.exp(m2[...] - m1[...])) / zf
        ent_ref[...] = (m1[...] + jnp.log(zf)) - t[...] / zf


@functools.partial(jax.jit, static_argnames=("bb", "vb", "interpret"))
def maxconf_pallas(logits: jnp.ndarray, *, bb: int = 8, vb: int = 2048,
                   interpret: bool = False) -> dict[str, jnp.ndarray]:
    b, v = logits.shape
    assert b % bb == 0 and v % vb == 0, (b, v, bb, vb)
    nb, nv = b // bb, v // vb
    grid = (nb, nv)
    out_shapes = (
        jax.ShapeDtypeStruct((b,), jnp.int32),    # prediction
        jax.ShapeDtypeStruct((b,), jnp.float32),  # max_softmax
        jax.ShapeDtypeStruct((b,), jnp.float32),  # pcs
        jax.ShapeDtypeStruct((b,), jnp.float32),  # entropy
    )
    row_spec = pl.BlockSpec((bb,), lambda i, j: (i,))
    pred, ms, pcs, ent = pl.pallas_call(
        functools.partial(_kernel, nv=nv, vb=vb),
        grid=grid,
        in_specs=[pl.BlockSpec((bb, vb), lambda i, j: (i, j))],
        out_specs=(row_spec, row_spec, row_spec, row_spec),
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((bb,), jnp.float32)] * 4
                       + [pltpu.VMEM((bb,), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(logits)
    return {"prediction": pred, "max_softmax": ms, "pcs": pcs,
            "entropy": ent}
