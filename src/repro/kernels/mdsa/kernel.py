"""MDSA Mahalanobis-distance Pallas TPU kernel.

Surprise adequacy is the paper's recommended 1st-level supervisor for
non-softmax local models; its hot spot is d(x) = sqrt((x-mu)^T P (x-mu))
over a batch of activation traces. The quadratic form is evaluated as two
MXU matmuls per (batch-block, feature-block) tile:

    z_j  += y_i @ P[i, j]        (accumulated over feature blocks i)
    d2   += rowsum(z_j * y_j)    (accumulated over feature blocks j)

Grid: (batch blocks, D blocks j, D blocks i) with i innermost; z lives in
VMEM scratch [BB, DB]; d2 in scratch [BB]. Block sizes are multiples of
128 to align the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(y_i_ref, p_ref, y_j_ref, out_ref, z, d2, *, nd: int):
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(j == 0, i == 0))
    def _init_row():
        d2[...] = jnp.zeros_like(d2)

    @pl.when(i == 0)
    def _init_z():
        z[...] = jnp.zeros_like(z)

    y_i = y_i_ref[...].astype(jnp.float32)          # [BB, DB] (block i)
    z[...] += jax.lax.dot(y_i, p_ref[...].astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)

    @pl.when(i == nd - 1)
    def _accumulate():
        y_j = y_j_ref[...].astype(jnp.float32)      # [BB, DB] (block j)
        d2[...] += jnp.sum(z[...] * y_j, axis=1)

    @pl.when(jnp.logical_and(j == nd - 1, i == nd - 1))
    def _finish():
        out_ref[...] = jnp.sqrt(jnp.maximum(d2[...], 0.0))


@functools.partial(jax.jit, static_argnames=("bb", "db", "interpret"))
def mdsa_pallas(x: jnp.ndarray, mean: jnp.ndarray, prec: jnp.ndarray, *,
                bb: int = 128, db: int = 128,
                interpret: bool = False) -> jnp.ndarray:
    b, d = x.shape
    assert b % bb == 0 and d % db == 0, (b, d, bb, db)
    y = x.astype(jnp.float32) - mean.astype(jnp.float32)
    nb, nd = b // bb, d // db
    return pl.pallas_call(
        functools.partial(_kernel, nd=nd),
        grid=(nb, nd, nd),
        in_specs=[
            pl.BlockSpec((bb, db), lambda b_, j, i: (b_, i)),   # y block i
            pl.BlockSpec((db, db), lambda b_, j, i: (i, j)),    # P[i, j]
            pl.BlockSpec((bb, db), lambda b_, j, i: (b_, j)),   # y block j
        ],
        out_specs=pl.BlockSpec((bb,), lambda b_, j, i: (b_,)),
        out_shape=jax.ShapeDtypeStruct((b,), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, db), jnp.float32),
                        pltpu.VMEM((bb,), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(y, prec, y)
