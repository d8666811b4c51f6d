"""Plain float32 reference of a dense decoder LM read as a classifier.

Written from the published Llama / Mistral description, not from the
program: token embedding, then per layer a pre-norm grouped-query
attention with rotary positions (rotate-half form, causal, and a sliding
window where the configuration has one) and a pre-norm SwiGLU MLP, each
added to the residual stream; a final RMS norm; the LM head applied to the
last position. Every product runs in float32 at ``Precision.HIGHEST``.
The weights are the configuration's seeded bf16 draws
(``chipbench/weights``), widened to float32.

The pass runs layer by layer, drawing each layer's weights on the device
as it goes, over row chunks, so a full-width model fits beside nothing
else on one chip. ``quant`` is the control, the reference computed in
``"int8"`` (or ``"fp8"``, e4m3): both operands of every product with a
weight matrix rounded to that type first, the weight with one scale per
output column and the activations with one per row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import dense_decoder as W

HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 32          # a multiple of every ``chunk`` the callers use


def _round(a, quant, axis):
    """``a`` rounded to ``quant`` with one scale per slice along ``axis``
    (the largest magnitude maps to the type's largest value)."""
    top = {"int8": 127.0, "fp8": 448.0}.get(quant)
    if top is None:
        raise ValueError(f"unknown control precision {quant!r}")
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if quant == "int8":
        return jnp.clip(jnp.round(a / scale), -127, 127) * scale
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant=None):
    """x @ w; under a control precision both operands are rounded to it
    first: x per row, w per output column."""
    if quant is not None:
        x = _round(x, quant, -1)
    return jnp.einsum("...i,io->...o", x, w, precision=HI)


def _quant(w, quant):
    w = w.astype(jnp.float32)
    return w if quant is None else _round(w, quant, 0)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [n, S, heads, hd]: rotate-half rotary embedding at positions
    0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], -1)                    # [S, hd]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(h, p, sizes, quant):
    n, s, _ = h.shape
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = W.head_dim(sizes)
    theta = sizes.get("rope_theta", 10000.0)
    q = _rope(_mm(h, p["wq"], quant).reshape(n, s, nh, hd), theta)
    k = _rope(_mm(h, p["wk"], quant).reshape(n, s, nkv, hd), theta)
    v = _mm(h, p["wv"], quant).reshape(n, s, nkv, hd)
    rep = nh // nkv                     # query head i reads kv head i // rep
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HI) / np.sqrt(hd)
    i = np.arange(s)[:, None]
    j = np.arange(s)[None, :]
    allowed = j <= i
    window = sizes.get("sliding_window") or 0
    if window:
        allowed &= j > i - window
    scores = jnp.where(jnp.asarray(allowed), scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nhqk,nkhd->nqhd", probs, v, precision=HI)
    return _mm(out.reshape(n, s, nh * hd), p["wo"], quant)


def _block(x, p, sizes, quant):
    eps = sizes["rms_norm_eps"]
    x = x + _attention(_rms(x, p["norm1"], eps), p["attn"], sizes, quant)
    h = _rms(x, p["norm2"], eps)
    m = p["mlp"]
    return x + _mm(jax.nn.silu(_mm(h, m["w_gate"], quant))
                   * _mm(h, m["w_up"], quant), m["w_down"], quant)


def _layer_weights(mkey, l, sizes, quant):
    p = W.layer(mkey, l, sizes)
    out = {"norm1": p["norm1"].astype(jnp.float32),
           "norm2": p["norm2"].astype(jnp.float32),
           "attn": {k: _quant(v["w"], quant) for k, v in p["attn"].items()},
           "mlp": {k: _quant(v["w"], quant) for k, v in p["mlp"].items()}}
    return out


@functools.partial(jax.jit, static_argnames=("sizes_items", "quant",
                                             "chunk"))
def _layer(x, mkey, l, *, sizes_items, quant, chunk):
    sizes = dict(sizes_items)
    p = _layer_weights(mkey, l, sizes, quant)
    n = x.shape[0]
    xs = x.reshape((n // chunk, chunk) + x.shape[1:])
    return jax.lax.map(lambda xc: _block(xc, p, sizes, quant),
                       xs).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("sizes_items", "quant"))
def _embed(tokens, mkey, *, sizes_items, quant):
    del quant                      # the embedding is a gather: kept exact
    return W.embed(mkey, dict(sizes_items))[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("sizes_items", "quant"))
def _head(x_last, mkey, *, sizes_items, quant):
    sizes = dict(sizes_items)
    h = _rms(x_last, W.final_norm(mkey, sizes).astype(jnp.float32),
             sizes["rms_norm_eps"])
    return _mm(h, _quant(W.head(mkey, sizes), quant), quant)


def _items(sizes: dict) -> tuple:
    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "vocab_size",
            "rope_theta", "rms_norm_eps", "sliding_window", "head_dim")
    return tuple((k, sizes[k]) for k in keys if sizes.get(k) is not None)


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
    x = _embed(jnp.asarray(tokens), mkey, sizes_items=items, quant=quant)
    for l in range(sizes["num_hidden_layers"]):
        x = _layer(x, mkey, jnp.int32(l), sizes_items=items, quant=quant,
                   chunk=chunk)
    out = _head(x[:, -1], mkey, sizes_items=items, quant=quant)
    return np.asarray(jax.device_get(out))[:n]
