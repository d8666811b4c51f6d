"""RWKV6 time-mix recurrence Pallas TPU kernel (chunked scan).

The Finch recurrence is sequential in T but embarrassingly parallel over
(batch, head): grid = (B*H, time chunks) with the [M, M] state resident in
VMEM scratch across chunks — HBM sees each input element exactly once and
the state never spills (M=64 -> 16 KiB fp32). Inside a chunk a
`fori_loop` applies the per-token update:

    y_t = r_t S + (r_t . (u o k_t)) v_t ;  S <- w_t o_rows S + k_t v_t^T

This is the TPU-native analogue of the paper-adjacent CUDA kernels RWKV
ships: the (M x M) outer products map to VPU/MXU ops and the chunk length
trades VMEM residency against grid overhead (long_500k path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref,
            state, *, tb: int, nt: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        state[...] = s0_ref[...].reshape(state.shape)

    def step(t, _):
        r_t = r_ref[0, t, :].astype(jnp.float32)            # [M]
        k_t = k_ref[0, t, :].astype(jnp.float32)
        v_t = v_ref[0, t, :].astype(jnp.float32)
        w_t = w_ref[0, t, :].astype(jnp.float32)
        u = u_ref[0, :].astype(jnp.float32)
        s = state[...]
        y = (r_t[None, :] @ s)[0] + jnp.sum(r_t * u * k_t) * v_t
        y_ref[0, t, :] = y
        state[...] = w_t[:, None] * s + k_t[:, None] * v_t[None, :]
        return 0

    jax.lax.fori_loop(0, tb, step, 0)

    @pl.when(it == nt - 1)
    def _finish():
        sT_ref[...] = state[...].reshape(sT_ref.shape)


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def rwkv6_scan(r, k, v, w, u, s0, *, tb: int = 128,
               interpret: bool = False):
    """r,k,v,w: [B,T,H,M]; u: [H,M]; s0: [B,H,M,M] fp32.
    Returns (y [B,T,H,M] fp32, s_T [B,H,M,M] fp32)."""
    b, t, h, m = r.shape
    tb = min(tb, t)
    assert t % tb == 0
    nt = t // tb

    def to_bh(z):
        return z.transpose(0, 2, 1, 3).reshape(b * h, t, m)

    rr, kk, vv, ww = map(to_bh, (r, k, v, w))
    uu = jnp.broadcast_to(u[None], (b, h, m)).reshape(b * h, m)
    ss = s0.reshape(b * h, m, m).astype(jnp.float32)

    y, s_t = pl.pallas_call(
        functools.partial(_kernel, tb=tb, nt=nt),
        grid=(b * h, nt),
        in_specs=[
            pl.BlockSpec((1, tb, m), lambda bh, it: (bh, it, 0)),
            pl.BlockSpec((1, tb, m), lambda bh, it: (bh, it, 0)),
            pl.BlockSpec((1, tb, m), lambda bh, it: (bh, it, 0)),
            pl.BlockSpec((1, tb, m), lambda bh, it: (bh, it, 0)),
            pl.BlockSpec((1, m), lambda bh, it: (bh, 0)),
            pl.BlockSpec((1, m, m), lambda bh, it: (bh, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, tb, m), lambda bh, it: (bh, it, 0)),
            pl.BlockSpec((1, m, m), lambda bh, it: (bh, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, t, m), jnp.float32),
            jax.ShapeDtypeStruct((b * h, m, m), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((m, m), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(rr, kk, vv, ww, uu, ss)
    y = y.reshape(b, h, t, m).transpose(0, 2, 1, 3)
    return y, s_t.reshape(b, h, m, m)
