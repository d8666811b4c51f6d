"""Flash attention (prefill) Pallas TPU kernel — GQA + causal + SWA.

The remote tier's 32k prefill is the cascade's single most expensive
compute step. This kernel streams KV blocks through VMEM with the online-
softmax recurrence so the [T, S] score matrix never exists in HBM:

  grid = (batch*kv-head, q blocks, kv blocks), kv innermost;
  per (q-block) scratch: acc [G*QB, hd], m and l [G*QB] rows;
  causal + sliding-window handled by masking inside the block (blocks
  fully outside the mask are skipped via `pl.when` on block indices).

Q blocks carry the G query heads of the kv group fused into rows
(GQA-native layout: [G*QB, hd] tiles keep the MXU fed at kv-head
granularity with no head broadcast in HBM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m, l, *,
            scale: float, causal: bool, window: int,
            qb: int, kb: int, nk: int, g: int):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG)
        l[...] = jnp.zeros_like(l)

    q_start = iq * qb
    k_start = ik * kb
    # skip blocks fully masked out (causal: kv entirely after q;
    # SWA: kv entirely before the window)
    run = True
    if causal:
        run = k_start <= q_start + qb - 1
    if window:
        run = jnp.logical_and(run, k_start + kb - 1 > q_start - window)

    @pl.when(run)
    def _block():
        hd = q_ref.shape[-1]
        q = q_ref[...].astype(jnp.float32).reshape(g * qb, hd)
        k = k_ref[...].astype(jnp.float32).reshape(kb, hd)
        v = v_ref[...].astype(jnp.float32).reshape(kb, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST)
        s = s * scale                                # [G*QB, KB]
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % qb
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = q_start + rows
        kpos = k_start + cols
        mask = jnp.ones(s.shape, bool)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG)

        m_prev, l_prev = m[...], l[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l[...] = l_prev * alpha + jnp.sum(p, axis=1)
        acc[...] = acc[...] * alpha[:, None] + jax.lax.dot(
            p, v, precision=jax.lax.Precision.HIGHEST)
        m[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        out = acc[...] / jnp.maximum(l[...], 1e-30)[:, None]
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "qb", "kb",
                                    "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0, qb: int = 256,
                    kb: int = 256, interpret: bool = False) -> jnp.ndarray:
    """q: [B, T, H, hd]; k, v: [B, S, K, hd]. Returns [B, T, H, hd]."""
    b, t, h, hd = q.shape
    s, kh = k.shape[1], k.shape[2]
    g = h // kh
    qb = min(qb, t)
    kb = min(kb, s)
    assert t % qb == 0 and s % kb == 0
    nq, nk = t // qb, s // kb
    scale = 1.0 / (hd ** 0.5)

    # GQA-native layout: [B*K, T*G?]. We fuse G into the row dim per
    # q block: rows = g * qb. Rearrange q -> [B*K, nq, G*QB, hd].
    qr = (q.reshape(b, t, kh, g, hd).transpose(0, 2, 3, 1, 4)
          .reshape(b * kh, g, t, hd))
    kr = k.transpose(0, 2, 1, 3).reshape(b * kh, s, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kh, s, hd)

    def q_map(bh, iq, ik):
        return (bh, 0, iq, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          window=window, qb=qb, kb=kb, nk=nk, g=g),
        grid=(b * kh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, g, qb, hd), q_map),
            pl.BlockSpec((1, kb, hd), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, kb, hd), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, qb, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((b * kh, g, t, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((g * qb, hd), jnp.float32),
                        pltpu.VMEM((g * qb,), jnp.float32),
                        pltpu.VMEM((g * qb,), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qr, kr, vr)
    return (out.reshape(b, kh, g, t, hd).transpose(0, 3, 1, 2, 4)
            .reshape(b, t, h, hd))
