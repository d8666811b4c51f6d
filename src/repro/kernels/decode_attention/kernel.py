"""Single-token GQA decode-attention Pallas TPU kernel.

decode_32k / long_500k are *memory-bound*: each step streams the whole KV
cache (up to 500k tokens) from HBM for one query token. The kernel keeps
the full query head block resident in VMEM and streams KV in blocks with
the online-softmax recurrence; per-sequence `kv_len` masks invalid slots
(ring buffers / partially-filled caches).

Grid: (batch * kv-head, kv blocks), kv innermost; scratch acc [G, hd],
m/l [G]. The [G, KB] score tile is one MXU matmul per block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc, m, l, *,
            scale: float, kb: int, nk: int, kh: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG)
        l[...] = jnp.zeros_like(l)

    kv_len = len_ref[0]
    k_start = ik * kb

    @pl.when(k_start < kv_len)
    def _block():
        g, hd = q_ref.shape[-2], q_ref.shape[-1]
        q = q_ref[...].astype(jnp.float32).reshape(g, hd)
        k = k_ref[...].astype(jnp.float32).reshape(kb, hd)
        v = v_ref[...].astype(jnp.float32).reshape(kb, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST) * scale
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < kv_len, s, NEG)
        m_prev, l_prev = m[...], l[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
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


@functools.partial(jax.jit, static_argnames=("kb", "interpret"))
def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, kv_len: jnp.ndarray, *,
                     kb: int = 512, interpret: bool = False) -> jnp.ndarray:
    """q: [B, H, hd]; caches: [B, S, K, hd]; kv_len: [B] int32."""
    b, h, hd = q.shape
    s, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    kb = min(kb, s)
    assert s % kb == 0
    nk = s // kb
    scale = 1.0 / (hd ** 0.5)

    qr = q.reshape(b, kh, g, hd).reshape(b * kh, g, hd)
    kr = k_cache.transpose(0, 2, 1, 3).reshape(b * kh, s, hd)
    vr = v_cache.transpose(0, 2, 1, 3).reshape(b * kh, s, hd)
    lens = jnp.repeat(kv_len.astype(jnp.int32), kh)          # [B*K]

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, kb=kb, nk=nk, kh=kh),
        grid=(b * kh, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda bh, ik: (bh,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, g, hd), lambda bh, ik: (bh, 0, 0)),
            pl.BlockSpec((1, kb, hd), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec((1, kb, hd), lambda bh, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, hd), lambda bh, ik: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kh, g, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, hd), jnp.float32),
                        pltpu.VMEM((g,), jnp.float32),
                        pltpu.VMEM((g,), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(lens, qr, kr, vr)
    return out.reshape(b, kh, g, hd).reshape(b, h, hd)
