"""Plain float32 reference of a DeepSeek-V2 decoder (MLA, YaRN, shared-
expert MoE) read as a classifier, at one device's share of the experts.

Written from the published description (arXiv:2405.04434 and the
checkpoint's ``modeling_deepseek.py``), not from the program: token
embedding; per layer a pre-norm multi-head latent attention in its naive
form (full-rank queries, a compressed key/value latent with its own RMS
norm, up-projected per head, and one rope key shared by every head) with
the published interleaved rope layout, YaRN's frequencies and cos/sin
scale, and the softmax scale ``q_head_dim^-0.5 * mscale(factor,
mscale_all_dim)^2``; then a pre-norm MLP: dense SwiGLU in the first
``first_k_dense_replace`` layers, else the expert layer (float32 router
logits, softmax over every routed expert, greedy top-k, the top-k
probabilities as weights, renormalised only under ``norm_topk_prob``,
times ``routed_scaling_factor``) plus the shared experts; each added to
the residual stream; a final RMS norm; the LM head at the last position.
Every product runs in float32 at ``Precision.HIGHEST``. The weights are
the configuration's seeded draws in its served type (``torch_dtype``,
``chipbench/weights/moe_decoder.py``), widened to float32.

Departures from the published model, each one the configuration states:

* only the held experts (``experts_held`` from ``first_expert``) add to
  the routed sum, as on one device of the expert-parallel deployment:
  the router still chooses among all of them, and what the absent
  experts would add is left out here as in the program;
* each held expert is computed over every token and weighted by its
  routing weight, zero for a token not routed to it: the same sum as
  running it over its routed tokens alone;
* weights are seeded draws, not the checkpoint.

The pass runs layer by layer, drawing each layer's weights on the device
as it goes, over row chunks. ``quant`` is the control (``"int8"`` or
``"fp8"``, e4m3): both operands of every product with a weight matrix,
the router's too, rounded to that type first, the weight with one scale
per output column and the activations with one per row.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references.dense_decoder import (HI, ROW_BLOCK, _mm, _quant,
                                                _rms)
from chipbench.weights import moe_decoder as W


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _cos_sin(sizes: dict, s: int):
    """[S, dr] cos and sin of the rope part at positions 0..S-1, with
    ``rope_scaling`` (YaRN) where the configuration has it, float64."""
    dr, base = sizes["qk_rope_head_dim"], sizes["rope_theta"]
    freq = 1.0 / base ** (np.arange(0, dr, 2, dtype=np.float64) / dr)
    scale = 1.0
    y = sizes.get("rope_scaling")
    if y:
        if y["type"] != "yarn":
            raise ValueError(f"rope_scaling {y['type']!r} is not YaRN")
        factor, orig = y["factor"], y["original_max_position_embeddings"]

        def dim(rot):           # the dimension that turns rot times
            return (dr * math.log(orig / (rot * 2 * math.pi))
                    / (2 * math.log(base)))
        low = max(math.floor(dim(y["beta_fast"])), 0)
        high = min(math.ceil(dim(y["beta_slow"])), dr - 1)
        ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3),
                       0, 1)
        freq = freq / factor * ramp + freq * (1 - ramp)
        scale = (_yarn_mscale(factor, y["mscale"])
                 / _yarn_mscale(factor, y["mscale_all_dim"]))
    ang = np.arange(s, dtype=np.float64)[:, None] * freq[None, :]
    ang = np.concatenate([ang, ang], -1)
    return np.cos(ang) * scale, np.sin(ang) * scale


def _rope(x, cos, sin):
    """x [n, S, heads, dr] in the published interleaved layout: pairs
    (2i, 2i+1) are gathered into halves, then rotated by halves."""
    n, s, h, dr = x.shape
    x = x.reshape(n, s, h, dr // 2, 2).swapaxes(3, 4).reshape(n, s, h, dr)
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def softmax_scale(sizes: dict) -> float:
    scale = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]) ** -0.5
    y = sizes.get("rope_scaling")
    if y and y.get("mscale_all_dim"):
        scale *= _yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _attention(h, p, sizes, quant):
    n, s, _ = h.shape
    nh = sizes["num_attention_heads"]
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv = sizes["v_head_dim"]
    cos, sin = _cos_sin(sizes, s)
    cos = jnp.asarray(cos, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(sin, jnp.float32)[None, :, None, :]
    q = _mm(h, p["wq"], quant).reshape(n, s, nh, dn + dr)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], cos, sin)
    latent = _rms(_mm(h, p["w_dkv"], quant), p["kv_norm"],
                  sizes["rms_norm_eps"])
    k_pe = _rope(_mm(h, p["w_kr"], quant)[:, :, None, :], cos, sin)
    k_nope = _mm(latent, p["w_uk"], quant).reshape(n, s, nh, dn)
    v = _mm(latent, p["w_uv"], quant).reshape(n, s, nh, dv)
    scores = (jnp.einsum("nqhd,nkhd->nhqk", q_nope, k_nope, precision=HI)
              + jnp.einsum("nqhd,nkod->nhqk", q_pe, k_pe, precision=HI)
              ) * softmax_scale(sizes)
    allowed = np.arange(s)[None, :] <= np.arange(s)[:, None]
    scores = jnp.where(jnp.asarray(allowed), scores, -jnp.inf)
    out = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(scores, -1), v,
                     precision=HI)
    return _mm(out.reshape(n, s, nh * dv), p["wo"], quant)


def _swiglu(h, m, quant):
    return _mm(jax.nn.silu(_mm(h, m["w_gate"], quant))
               * _mm(h, m["w_up"], quant), m["w_down"], quant)


def _experts(h, p, sizes, quant):
    """The held experts' part of the routed sum, and the shared experts."""
    first, _ = W.held(sizes)
    k = sizes["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm(h, p["router"], quant), -1)   # [n, S, E]
    top_p, top_e = jax.lax.top_k(probs, k)
    if sizes["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p * sizes["routed_scaling_factor"]

    def one(y, expert):
        e, w = expert
        gate = jnp.sum(jnp.where(top_e == first + e, top_p, 0.0), -1)
        return y + gate[..., None] * _swiglu(h, w, quant), None

    count = p["experts"]["w_gate"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(count), p["experts"]))
    return y + _swiglu(h, p["shared"], quant)


def _block(x, p, sizes, quant):
    eps = sizes["rms_norm_eps"]
    x = x + _attention(_rms(x, p["norm1"], eps), p["attn"], sizes, quant)
    h = _rms(x, p["norm2"], eps)
    if "mlp" in p:
        return x + _swiglu(h, p["mlp"], quant)
    return x + _experts(h, p, sizes, quant)


def _widen(tree, quant):
    """Norm weights to float32; weight matrices to float32, then to the
    control's precision."""
    return jax.tree.map(lambda a: (_quant(a, quant) if a.ndim >= 2
                                   else a.astype(jnp.float32)), tree)


def _layer_weights(mkey, l, sizes, quant, moe: bool):
    p = (W.moe_layer if moe else W.dense_layer)(mkey, l, sizes,
                                                _served(sizes))
    if not moe:
        return _widen(p, quant)
    experts = p.pop("experts")
    out = _widen(p, quant)
    # per output column of each expert's own matrices
    out["experts"] = jax.vmap(lambda t: _widen(t, quant))(experts)
    return out


def _served(sizes: dict):
    return jnp.dtype(sizes.get("torch_dtype", "bfloat16"))


def _freeze(v):
    return tuple(sorted(v.items())) if isinstance(v, dict) else v


@functools.partial(jax.jit, static_argnames=("sizes_items", "quant",
                                             "chunk", "moe"))
def _layer(x, mkey, l, *, sizes_items, quant, chunk, moe):
    sizes = {k: dict(v) if isinstance(v, tuple) else v
             for k, v in sizes_items}
    p = _layer_weights(mkey, l, sizes, quant, moe)
    n = x.shape[0]
    xs = x.reshape((n // chunk, chunk) + x.shape[1:])
    return jax.lax.map(lambda xc: _block(xc, p, sizes, quant),
                       xs).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("sizes_items",))
def _embed(tokens, mkey, *, sizes_items):
    # the embedding is a gather: kept exact under the control
    sizes = dict(sizes_items)
    return W.embed(mkey, sizes, _served(sizes))[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("sizes_items", "quant"))
def _head(x_last, mkey, *, sizes_items, quant):
    sizes = dict(sizes_items)
    dtype = _served(sizes)
    h = _rms(x_last, W.final_norm(mkey, sizes, dtype).astype(jnp.float32),
             sizes["rms_norm_eps"])
    return _mm(h, _quant(W.head(mkey, sizes, dtype), quant), quant)


KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_hidden_layers", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "vocab_size",
        "rope_theta", "rope_scaling", "rms_norm_eps", "n_routed_experts",
        "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
        "routed_scaling_factor", "first_k_dense_replace", "experts_held",
        "first_expert", "torch_dtype")


def _items(sizes: dict) -> tuple:
    return tuple((k, _freeze(sizes[k])) for k in KEYS
                 if sizes.get(k) is not None)


def logits(sizes: dict, seed: int, tokens: np.ndarray, *,
           quant: str | None = None, chunk: int = 8) -> np.ndarray:
    """tokens [n, S] int -> last-position logits [n, V] float32. The rows
    are padded to a multiple of ``ROW_BLOCK``, so that runs compile the
    pass for few row counts, which the persistent cache then holds."""
    tokens = np.asarray(tokens, np.int32)
    n = tokens.shape[0]
    pad = (-n) % ROW_BLOCK
    if pad:
        tokens = np.concatenate([tokens, np.repeat(tokens[-1:], pad, 0)])
    items = _items(sizes)
    mkey = W.model_key(seed)
    x = _embed(jnp.asarray(tokens), mkey, sizes_items=items)
    for l in range(sizes["num_hidden_layers"]):
        x = _layer(x, mkey, jnp.int32(l), sizes_items=items, quant=quant,
                   chunk=chunk, moe=l >= sizes["first_k_dense_replace"])
    out = _head(x[:, -1], mkey, sizes_items=items, quant=quant)
    return np.asarray(jax.device_get(out))[:n]
