"""Multi-head Latent Attention (DeepSeek-V2) — compressed-KV attention.

Training / prefill use the naive ("up-projected") formulation; decode uses
the *absorbed* formulation (W_uk folded into the query, W_uv folded into the
output projection) so the cache is only the kv_lora latent + the shared rope
key: cache bytes per token = kv_lora_rank + qk_rope_head_dim, a ~14x
reduction vs. vanilla GQA for deepseek-v2-lite. This mirrors DeepSeek-V2's
serving optimisation and is the arch where the paper's "expensive remote
model" tier benefits most from cache compression.

Rotary positions use the rotate-half layout (the published checkpoint's
interleaved rope columns map onto it by a fixed permutation) and, where
the config has ``rope_scaling``, YaRN's frequencies, with the softmax scale
``q_head_dim^-0.5 * mscale^2``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.scan_config import scan_unroll
from repro.models.layers import (Params, apply_rope, dense, dense_params,
                                 rms_norm, yarn_softmax_scale)


def mla_params(key, cfg: ModelConfig, dtype) -> Params:
    h = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    ks = jax.random.split(key, 6)
    return {
        # queries are full-rank in V2-Lite (q_lora_rank = None)
        "wq": dense_params(ks[0], cfg.d_model, h * (dn + dr), dtype),
        # compressed kv path
        "w_dkv": dense_params(ks[1], cfg.d_model, r, dtype),
        "kv_norm": jnp.ones((r,), dtype),
        "w_uk": dense_params(ks[2], r, h * dn, dtype),
        "w_uv": dense_params(ks[3], r, h * dv, dtype),
        "w_kr": dense_params(ks[4], cfg.d_model, dr, dtype),
        "wo": dense_params(ks[5], h * dv, cfg.d_model, dtype),
    }


def _split_q(cfg: ModelConfig, q):
    b, t, _ = q.shape
    h, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = q.reshape(b, t, h, dn + dr)
    return q[..., :dn], q[..., dn:]


def _latents(cfg: ModelConfig, p: Params, x, positions):
    """Returns (c_kv [B,T,r], k_rope [B,T,1,dr]) — exactly what is cached."""
    c_kv = rms_norm(dense(p["w_dkv"], x), p["kv_norm"], cfg.norm_eps)
    k_r = dense(p["w_kr"], x)[:, :, None, :]  # single shared rope head
    k_r = apply_rope(k_r, positions, cfg.rope_theta, cfg.rope_scaling)
    return c_kv, k_r


def mla_forward(cfg: ModelConfig, p: Params, x, positions, *,
                causal: bool = True, q_chunk: int = 1024):
    """Naive full-sequence MLA (train / prefill compute path)."""
    b, t, _ = x.shape
    h, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    q_n, q_r = _split_q(cfg, dense(p["wq"], x))
    q_r = apply_rope(q_r, positions, cfg.rope_theta, cfg.rope_scaling)
    c_kv, k_r = _latents(cfg, p, x, positions)
    k_n = dense(p["w_uk"], c_kv).reshape(b, t, h, dn)
    v = dense(p["w_uv"], c_kv).reshape(b, t, h, dv)

    scale = yarn_softmax_scale(dn + dr, cfg.rope_scaling)
    kv_pos = jnp.arange(t)

    @jax.named_scope("mla.attend")
    def chunk(qn_i, qr_i, q_pos):
        from repro.models.layers import _SCORES_FP32
        if _SCORES_FP32:        # ablation baseline
            lg = (jnp.einsum("btnd,bsnd->bnts", qn_i.astype(jnp.float32),
                             k_n.astype(jnp.float32))
                  + jnp.einsum("btnd,bsod->bnts", qr_i.astype(jnp.float32),
                               k_r.astype(jnp.float32))) * scale
        else:
            # bf16 dots + fp32 accumulation (SPerf iteration C1)
            lg = (jnp.einsum("btnd,bsnd->bnts", qn_i, k_n,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("btnd,bsod->bnts", qr_i, k_r,
                               preferred_element_type=jnp.float32)) * scale
        if causal:
            m = kv_pos[None, :] <= q_pos[:, None]
            lg = jnp.where(m[None, None], lg, -1e30)
        w = jax.nn.softmax(lg, axis=-1)
        if _SCORES_FP32:
            return jnp.einsum("bnts,bsnd->btnd", w,
                              v.astype(jnp.float32)).astype(x.dtype)
        return jnp.einsum("bnts,bsnd->btnd", w.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(x.dtype)

    if t <= q_chunk:
        out = chunk(q_n, q_r, jnp.arange(t))
    else:
        assert t % q_chunk == 0
        n = t // q_chunk
        qn_c = jnp.moveaxis(q_n.reshape(b, n, q_chunk, h, dn), 1, 0)
        qr_c = jnp.moveaxis(q_r.reshape(b, n, q_chunk, h, dr), 1, 0)

        def body(_, args):
            i, qn_i, qr_i = args
            return None, chunk(qn_i, qr_i, i * q_chunk + jnp.arange(q_chunk))

        _, out = jax.lax.scan(body, None, (jnp.arange(n), qn_c, qr_c),
                              unroll=scan_unroll())
        out = jnp.moveaxis(out, 0, 1).reshape(b, t, h, dv)

    out = dense(p["wo"], out.reshape(b, t, h * dv))
    return out, (c_kv, k_r[:, :, 0, :])


def make_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
                   layers: int | None = None) -> Params:
    n_l = cfg.num_layers if layers is None else layers
    return {
        "c_kv": jnp.zeros((n_l, batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((n_l, batch, max_len, cfg.qk_rope_head_dim), dtype),
    }


def mla_decode(cfg: ModelConfig, p: Params, x, c_kv_cache, kr_cache, pos):
    """Absorbed one-token decode.

    x: [B,1,D]; c_kv_cache: [B,S,r]; kr_cache: [B,S,dr]; pos: [] int32.
    score_nope = (q_n W_uk^T) . c_kv  — attention runs in latent space.
    out = (attn-weighted c_kv) W_uv  — value up-projection after weighting.
    """
    b = x.shape[0]
    h, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    r = cfg.kv_lora_rank
    q_n, q_r = _split_q(cfg, dense(p["wq"], x))          # [B,1,h,dn/dr]
    posv = jnp.full((1,), pos)
    q_r = apply_rope(q_r, posv, cfg.rope_theta, cfg.rope_scaling)
    c_kv, k_r = _latents(cfg, p, x, posv)                # [B,1,r], [B,1,1,dr]
    c_kv_cache = jax.lax.dynamic_update_slice_in_dim(c_kv_cache, c_kv, pos, 1)
    kr_cache = jax.lax.dynamic_update_slice_in_dim(
        kr_cache, k_r[:, :, 0, :], pos, 1)

    w_uk = p["w_uk"]["w"].reshape(r, h, dn)
    # absorb: q_lat [B,1,h,r] = q_n @ W_uk^T (per head); dots stay in the
    # cache dtype (bf16 MXU) with fp32 accumulation (SPerf iteration A2)
    q_lat = jnp.einsum("bthd,rhd->bthr", q_n, w_uk,
                       preferred_element_type=jnp.float32)
    scale = yarn_softmax_scale(dn + dr, cfg.rope_scaling)
    lg = (jnp.einsum("bthr,bsr->bhts", q_lat.astype(c_kv_cache.dtype),
                     c_kv_cache, preferred_element_type=jnp.float32)
          + jnp.einsum("bthd,bsd->bhts", q_r, kr_cache,
                       preferred_element_type=jnp.float32)) * scale
    s = c_kv_cache.shape[1]
    valid = jnp.arange(s)[None, None, None, :] < pos + 1
    lg = jnp.where(valid, lg, -1e30)
    w = jax.nn.softmax(lg, axis=-1)
    ctx = jnp.einsum("bhts,bsr->bthr", w.astype(c_kv_cache.dtype),
                     c_kv_cache,
                     preferred_element_type=jnp.float32)    # [B,1,h,r]
    w_uv = p["w_uv"]["w"].reshape(r, h, dv)
    out = jnp.einsum("bthr,rhd->bthd", ctx.astype(w_uv.dtype), w_uv,
                     preferred_element_type=jnp.float32)
    out = dense(p["wo"], out.reshape(b, 1, h * dv).astype(x.dtype))
    return out, c_kv_cache, kr_cache
