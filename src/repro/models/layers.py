"""Shared transformer building blocks (pure-jnp path).

All functions are pure; parameters are plain dict pytrees. The jnp path is
the portable reference used for training, the multi-pod dry-run and CPU
tests; Pallas kernels (repro.kernels) are drop-in accelerations of the same
math, validated against these implementations.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, YarnRope
from repro.models.scan_config import scan_unroll

Params = dict


# --------------------------------------------------------------------------
# initialisation helpers
# --------------------------------------------------------------------------

def _dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def dense_params(key, d_in: int, d_out: int, dtype, bias: bool = False,
                 scale: float | None = None) -> Params:
    kw, kb = jax.random.split(key)
    p = {"w": _dense_init(kw, (d_in, d_out), dtype, scale)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def layer_norm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
               eps: float) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def group_norm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, num_groups: int,
               eps: float) -> jnp.ndarray:
    """GroupNorm over the last dim (used by RWKV6 output norm)."""
    dt = x.dtype
    *lead, d = x.shape
    x = x.astype(jnp.float32).reshape(*lead, num_groups, d // num_groups)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = ((x - mu) * jax.lax.rsqrt(var + eps)).reshape(*lead, d)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature factor for a context ``scale``."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_freqs(head_dim: int, theta: float, y: YarnRope) -> np.ndarray:
    """YaRN inverse frequencies [hd/2] (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``): dimensions that turn fewer than
    ``beta_slow`` times over the original context are interpolated by
    ``factor``, those that turn more than ``beta_fast`` times keep their
    frequency, and a linear ramp joins the two."""
    def turns_dim(turns):
        return (head_dim * math.log(y.original_max_position_embeddings
                                    / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(turns_dim(y.beta_fast)), 0)
    high = min(math.ceil(turns_dim(y.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp
    return (extra / y.factor * (1 - keep) + extra * keep).astype(np.float32)


def yarn_softmax_scale(q_head_dim: int, y: YarnRope | None) -> float:
    """``q_head_dim^-0.5``, times ``mscale(factor, mscale_all_dim)^2``
    under YaRN."""
    scale = q_head_dim ** -0.5
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float, scaling: YarnRope | None = None) -> jnp.ndarray:
    """x: [..., T, H, hd]; positions: broadcastable to [..., T]. Rotate-half
    layout; ``scaling`` applies YaRN's frequencies and cos/sin scale."""
    hd = x.shape[-1]
    if scaling is None:
        freqs = rope_freqs(hd, theta)                   # [hd/2]
    else:
        freqs = jnp.asarray(yarn_freqs(hd, theta, scaling))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, hd/2]
    angles = angles[..., None, :]                       # [..., T, 1, hd/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention (GQA, optional sliding window / bias), chunked for long seqs
# --------------------------------------------------------------------------

def attention_params(key, cfg: ModelConfig, dtype) -> Params:
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_params(ks[0], cfg.d_model, cfg.num_heads * hd, dtype,
                           bias=cfg.attn_bias),
        "wk": dense_params(ks[1], cfg.d_model, cfg.num_kv_heads * hd, dtype,
                           bias=cfg.attn_bias),
        "wv": dense_params(ks[2], cfg.d_model, cfg.num_kv_heads * hd, dtype,
                           bias=cfg.attn_bias),
        "wo": dense_params(ks[3], cfg.num_heads * hd, cfg.d_model, dtype),
    }


_SCORES_FP32 = False    # ablation: paper-era fp32 attention math


def set_scores_fp32(value: bool) -> None:
    """Toggle the pre-optimization fp32 attention-score path (used by the
    perf harness to measure the SPerf A2/C1 baseline)."""
    global _SCORES_FP32
    _SCORES_FP32 = value


def _sdpa(q, k, v, mask, scale):
    """q:[B,Tq,K,G,hd] k,v:[B,S,K,hd] mask:[Tq,S] bool -> [B,Tq,K,G,hd]."""
    # Dots run at the INPUT dtype (bf16 MXU for bf16 models) with fp32
    # accumulation; softmax stays fp32. The former fp32 upcast of K/V
    # materialised an fp32 copy of the whole KV cache per decode step AND
    # pushed every attention dot onto the ~4x slower fp32 MXU path
    # (EXPERIMENTS.md SPerf iteration A2/C1).
    if _SCORES_FP32:            # ablation baseline
        logits = jnp.einsum("btkgh,bskh->bkgts", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkgts,bskh->btkgh", w, v.astype(jnp.float32))
        return out.astype(q.dtype)
    logits = jnp.einsum("btkgh,bskh->bkgts", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def gqa_attention(q, k, v, *, causal: bool, q_offset, window: int = 0,
                  kv_len_valid=None, q_chunk: int = 1024):
    """Grouped-query attention, scanned over query chunks so [Tq,S] score
    tensors never exceed q_chunk rows (keeps 32k prefill in memory budget).

    q: [B, Tq, H, hd]; k, v: [B, S, K, hd]. q_offset: absolute position of
    q[0] (array or int). kv_len_valid: number of valid cache slots (decode).
    """
    b, tq, h, hd = q.shape
    s = k.shape[1]
    kh = k.shape[2]
    g = h // kh
    q = q.reshape(b, tq, kh, g, hd)
    scale = 1.0 / np.sqrt(hd)
    kv_pos = jnp.arange(s)

    def mask_for(q_pos):
        # q_pos: [tc] absolute positions
        m = jnp.ones((q_pos.shape[0], s), bool)
        if causal:
            m &= kv_pos[None, :] <= q_pos[:, None]
        if window:
            m &= kv_pos[None, :] > q_pos[:, None] - window
        if kv_len_valid is not None:
            m &= kv_pos[None, :] < kv_len_valid
        return m

    if tq <= q_chunk:
        q_pos = q_offset + jnp.arange(tq)
        out = _sdpa(q, k, v, mask_for(q_pos), scale)
        return out.reshape(b, tq, h, hd)

    assert tq % q_chunk == 0, (tq, q_chunk)
    nchunk = tq // q_chunk
    qc = q.reshape(b, nchunk, q_chunk, kh, g, hd)

    def body(_, args):
        i, qi = args
        q_pos = q_offset + i * q_chunk + jnp.arange(q_chunk)
        return None, _sdpa(qi, k, v, mask_for(q_pos), scale)

    _, out = jax.lax.scan(
        body, None, (jnp.arange(nchunk), jnp.moveaxis(qc, 1, 0)),
        unroll=scan_unroll())
    out = jnp.moveaxis(out, 0, 1).reshape(b, tq, h, hd)
    return out


def attn_forward(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                 positions: jnp.ndarray, *, causal: bool) -> jnp.ndarray:
    """Full-sequence attention (train / prefill / encoder)."""
    b, t, _ = x.shape
    hd = cfg.resolved_head_dim
    q = dense(p["wq"], x).reshape(b, t, cfg.num_heads, hd)
    k = dense(p["wk"], x).reshape(b, t, cfg.num_kv_heads, hd)
    v = dense(p["wv"], x).reshape(b, t, cfg.num_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    out = gqa_attention(q, k, v, causal=causal, q_offset=0,
                        window=cfg.sliding_window)
    return dense(p["wo"], out.reshape(b, t, cfg.num_heads * hd))


def make_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                  layers: int | None = None) -> Params:
    """Contiguous KV cache. SWA caches only the window (ring buffer)."""
    slots = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    n_l = cfg.num_layers if layers is None else layers
    hd = cfg.resolved_head_dim
    shape = (n_l, batch, slots, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attn_prefill(cfg: ModelConfig, p: Params, x, positions):
    """Returns (out, (k, v)) — caller stores k/v into the layer cache."""
    b, t, _ = x.shape
    hd = cfg.resolved_head_dim
    q = dense(p["wq"], x).reshape(b, t, cfg.num_heads, hd)
    k = dense(p["wk"], x).reshape(b, t, cfg.num_kv_heads, hd)
    v = dense(p["wv"], x).reshape(b, t, cfg.num_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    out = gqa_attention(q, k, v, causal=True, q_offset=0,
                        window=cfg.sliding_window)
    out = dense(p["wo"], out.reshape(b, t, cfg.num_heads * hd))
    if cfg.sliding_window and t > cfg.sliding_window:
        # Keep only the window, ROLLED so position p lands at ring slot
        # p % window — the convention attn_decode writes with
        # (slot = pos % slots); without the roll, decode would evict the
        # wrong key whenever t % window != 0.
        w = cfg.sliding_window
        k = jnp.roll(k[:, -w:], shift=t % w, axis=1)
        v = jnp.roll(v[:, -w:], shift=t % w, axis=1)
    return out, (k, v)


def attn_decode(cfg: ModelConfig, p: Params, x, k_cache, v_cache, pos):
    """One-token decode. x: [B,1,D]; caches [B,slots,K,hd]; pos: [] int32
    absolute position of the new token. Returns (out, new_k, new_v, slot)."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    slots = k_cache.shape[1]
    q = dense(p["wq"], x).reshape(b, 1, cfg.num_heads, hd)
    k = dense(p["wk"], x).reshape(b, 1, cfg.num_kv_heads, hd)
    v = dense(p["wv"], x).reshape(b, 1, cfg.num_kv_heads, hd)
    posv = jnp.full((1,), pos)
    q = apply_rope(q, posv, cfg.rope_theta)
    k = apply_rope(k, posv, cfg.rope_theta)
    slot = pos % slots if cfg.sliding_window else pos
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, slot, axis=1)
    if cfg.sliding_window:
        # ring buffer: every stored slot is within the window -> all valid
        kv_valid = jnp.minimum(pos + 1, slots)
        out = gqa_attention(q, k_cache, v_cache, causal=False, q_offset=pos,
                            kv_len_valid=kv_valid)
    else:
        out = gqa_attention(q, k_cache, v_cache, causal=False, q_offset=pos,
                            kv_len_valid=pos + 1)
    out = dense(p["wo"], out.reshape(b, 1, cfg.num_heads * hd))
    return out, k_cache, v_cache


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def swiglu_params(key, d_model: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_params(ks[0], d_model, d_ff, dtype),
        "w_up": dense_params(ks[1], d_model, d_ff, dtype),
        "w_down": dense_params(ks[2], d_ff, d_model, dtype),
    }


def swiglu(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return dense(p["w_down"],
                 jax.nn.silu(dense(p["w_gate"], x)) * dense(p["w_up"], x))


def gelu_mlp_params(key, d_model: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 2)
    return {"w_in": dense_params(ks[0], d_model, d_ff, dtype, bias=True),
            "w_out": dense_params(ks[1], d_ff, d_model, dtype, bias=True)}


def gelu_mlp(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return dense(p["w_out"], jax.nn.gelu(dense(p["w_in"], x)))
