"""Unified model API over all assigned architecture families.

Pure functions over params pytrees:

    init_params(cfg, key)                       -> params
    forward(cfg, params, batch)                 -> full-seq hidden/logits
    loss_fn(cfg, params, batch)                 -> (loss, metrics)
    prefill(cfg, params, batch)                 -> (last_logits, cache)
    decode_step(cfg, params, token, cache, pos) -> (logits, cache)

Layer stacks are scanned (`lax.scan` over stacked params) so 95-layer
models lower to compact HLO; the scan body is `jax.checkpoint`-wrapped for
training. Cross-entropy is computed in sequence chunks so [B,T,V] logits
are never materialised (V up to 152k).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.scan_config import scan_unroll
from repro.models import mla as mla_mod
from repro.models import mamba2 as m2
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rwkv
from repro.models.layers import (Params, attention_params, attn_decode,
                                 attn_forward, attn_prefill, dense,
                                 dense_params, make_kv_cache, rms_norm,
                                 swiglu, swiglu_params)

Batch = dict[str, Any]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _stack_init(fn, key, n: int) -> Params:
    return jax.vmap(fn)(jax.random.split(key, n))


def _attn_block_params(key, cfg: ModelConfig, dtype, moe: bool) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"norm1": jnp.ones((cfg.d_model,), dtype),
         "norm2": jnp.ones((cfg.d_model,), dtype)}
    if cfg.use_mla:
        p["attn"] = mla_mod.mla_params(k1, cfg, dtype)
    else:
        p["attn"] = attention_params(k1, cfg, dtype)
    if moe:
        p["moe"] = moe_mod.moe_params(k2, cfg, dtype)
    else:
        p["mlp"] = swiglu_params(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def _rwkv_block_params(key, cfg: ModelConfig, dtype) -> Params:
    p = {"norm1": jnp.ones((cfg.d_model,), dtype),
         "norm2": jnp.ones((cfg.d_model,), dtype)}
    p.update(rwkv.rwkv6_params(key, cfg, dtype))
    return p


def _mamba_block_params(key, cfg: ModelConfig, dtype) -> Params:
    return {"norm": jnp.ones((cfg.d_model,), dtype),
            "mixer": m2.mamba2_params(key, cfg, dtype)}


def _zamba_groups(cfg: ModelConfig) -> tuple[int, int]:
    """(num_groups, mamba layers per group). Requires divisibility."""
    period = cfg.shared_attn_period
    assert cfg.num_layers % period == 0, (cfg.num_layers, period)
    return cfg.num_layers // period, period


def init_params(cfg: ModelConfig, key) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    p: Params = {}
    if not cfg.takes_embeddings or cfg.name.startswith("pixtral"):
        p["embed"] = (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model))
                      * 0.02).astype(dtype)
    p["final_norm"] = jnp.ones((cfg.d_model,), dtype)
    out_dim = cfg.num_classes or cfg.vocab_size
    p["head"] = dense_params(ks[1], cfg.d_model, out_dim, dtype)

    if cfg.block_type == "attn":
        n_dense = cfg.first_dense_layers
        n_main = cfg.num_layers - n_dense
        if n_dense:
            p["dense_blocks"] = _stack_init(
                lambda k: _attn_block_params(k, cfg, dtype, moe=False),
                ks[2], n_dense)
        p["blocks"] = _stack_init(
            lambda k: _attn_block_params(k, cfg, dtype, moe=cfg.is_moe),
            ks[3], n_main)
    elif cfg.block_type == "rwkv6":
        p["blocks"] = _stack_init(
            lambda k: _rwkv_block_params(k, cfg, dtype), ks[3],
            cfg.num_layers)
    elif cfg.block_type == "mamba2":
        p["blocks"] = _stack_init(
            lambda k: _mamba_block_params(k, cfg, dtype), ks[3],
            cfg.num_layers)
        if cfg.shared_attn_period:
            p["shared_attn"] = _attn_block_params(ks[4], cfg, dtype,
                                                  moe=False)
    else:
        raise ValueError(cfg.block_type)
    return p


# --------------------------------------------------------------------------
# full-sequence forward (train / encoder / prefill compute)
# --------------------------------------------------------------------------

def _embed_in(cfg: ModelConfig, params: Params, batch: Batch) -> jnp.ndarray:
    """Token / frontend-embedding input. VLMs (pixtral) interleave: the
    patch-embedding prefix (frontend stub) is concatenated before the text
    tokens' embeddings."""
    parts = []
    if "embeds" in batch:
        parts.append(batch["embeds"].astype(jnp.dtype(cfg.dtype)))
    if "tokens" in batch and "embed" in params:
        parts.append(params["embed"][batch["tokens"]])
    assert parts, "batch needs 'tokens' and/or 'embeds'"
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _attn_body(cfg: ModelConfig, lp: Params, x, positions, *, causal, moe,
               dropless: bool = False):
    """One block. Returns (x, aux loss, the held experts' row counts: only
    from a dropless MoE block, else None)."""
    h = rms_norm(x, lp["norm1"], cfg.norm_eps)
    if cfg.use_mla:
        a, _ = mla_mod.mla_forward(cfg, lp["attn"], h, positions,
                                   causal=causal)
    else:
        a = attn_forward(cfg, lp["attn"], h, positions, causal=causal)
    x = x + a
    h = rms_norm(x, lp["norm2"], cfg.norm_eps)
    rows = None
    if moe and dropless:
        y, rows = moe_mod.moe_dropless(cfg, lp["moe"], h)
        aux = jnp.float32(0.0)
    elif moe:
        y, aux = moe_mod.moe_forward(cfg, lp["moe"], h)
    else:
        y, aux = swiglu(lp["mlp"], h), jnp.float32(0.0)
    return x + y, aux, rows


def _rwkv_body(cfg: ModelConfig, lp: Params, x, st):
    """st: per-layer {"wkv","tm_prev","cm_prev"}; returns (x, new st)."""
    h = rms_norm(x, lp["norm1"], cfg.norm_eps)
    out, wkv, tm_last = rwkv.time_mix(cfg, lp, h, st["wkv"], st["tm_prev"])
    x = x + out
    h = rms_norm(x, lp["norm2"], cfg.norm_eps)
    out, cm_last = rwkv.channel_mix(cfg, lp, h, st["cm_prev"])
    return x + out, {"wkv": wkv, "tm_prev": tm_last, "cm_prev": cm_last}


def _run_attn_stack(cfg, params, x, positions, *, causal, remat: bool,
                    dropless: bool = False):
    """Returns (x, aux loss, held-expert row counts [MoE layers, held] of a
    dropless MoE stack, else None)."""
    aux_total = jnp.float32(0.0)

    def mk_body(moe):
        def body(carry, lp):
            x, aux = carry
            x, a, rows = _attn_body(cfg, lp, x, positions, causal=causal,
                                    moe=moe, dropless=dropless)
            return (x, aux + a), rows
        return jax.checkpoint(body) if remat else body

    if "dense_blocks" in params:
        (x, aux_total), _ = jax.lax.scan(mk_body(False), (x, aux_total),
                                         params["dense_blocks"],
                                         unroll=scan_unroll())
    (x, aux_total), rows = jax.lax.scan(mk_body(cfg.is_moe), (x, aux_total),
                                        params["blocks"],
                                        unroll=scan_unroll())
    return x, aux_total, rows


def _run_rwkv_stack(cfg, params, x, state, *, remat: bool):
    """state: stacked [L,...] rwkv6_state. Returns (x, new_state)."""
    def body(x, inp):
        lp, st = inp
        x, st = _rwkv_body(cfg, lp, x, st)
        return x, st
    body = jax.checkpoint(body) if remat else body
    x, new_state = jax.lax.scan(body, x, (params["blocks"], state),
                                unroll=scan_unroll())
    return x, new_state


def _run_zamba_stack(cfg, params, x, positions, mamba_state, attn_fn,
                     attn_xs, *, remat: bool):
    """Scan groups: [shared attn] + per-group inner scan of mamba layers.

    attn_fn(x, group_attn_xs) -> (x, group_attn_ys) abstracts full-seq vs
    decode attention; attn_xs has leading dim G (e.g. per-group KV caches,
    or None placeholders for training).
    """
    g, per = _zamba_groups(cfg)

    def leaves_regroup(t):
        return jax.tree.map(lambda a: a.reshape((g, per) + a.shape[1:]), t)

    blocks = leaves_regroup(params["blocks"])
    mamba_state = leaves_regroup(mamba_state) if mamba_state is not None \
        else None

    def inner(x, inp):
        lp, st = inp
        h = rms_norm(x, lp["norm"], cfg.norm_eps)
        out, new_st = m2.mamba2_forward(cfg, lp["mixer"], h, state=st)
        return x + out, new_st

    inner = jax.checkpoint(inner) if remat else inner

    def group(x, inp):
        gblocks, gstate, gattn = inp
        x, attn_ys = attn_fn(x, gattn)
        x, new_state = jax.lax.scan(inner, x, (gblocks, gstate),
                                    unroll=scan_unroll())
        return x, (new_state, attn_ys)

    x, (new_mamba, attn_ys) = jax.lax.scan(
        group, x, (blocks, mamba_state, attn_xs), unroll=scan_unroll())
    flatten = lambda t: jax.tree.map(
        lambda a: a.reshape((g * per,) + a.shape[2:]), t)
    return x, flatten(new_mamba), attn_ys


def forward(cfg: ModelConfig, params: Params, batch: Batch, *,
            remat: bool = False, dropless: bool = False):
    """Full-sequence hidden states [B,T,D] (+ aux dict). ``dropless`` runs
    MoE layers as served (``moe.moe_dropless``); the aux dict then holds
    ``moe_held_rows`` [MoE layers, held experts], the rows each held
    expert computed."""
    x = _embed_in(cfg, params, batch)
    b, t, _ = x.shape
    positions = jnp.arange(t)
    causal = not cfg.is_encoder

    if cfg.block_type == "attn":
        x, aux, rows = _run_attn_stack(cfg, params, x, positions,
                                       causal=causal, remat=remat,
                                       dropless=dropless)
        extras = {"moe_aux": aux}
        if rows is not None:
            extras["moe_held_rows"] = rows
    elif cfg.block_type == "rwkv6":
        state = rwkv.rwkv6_state(cfg, b)
        x, _ = _run_rwkv_stack(cfg, params, x, state, remat=remat)
        extras = {"moe_aux": jnp.float32(0.0)}
    else:  # mamba2 / zamba hybrid
        g, _ = _zamba_groups(cfg)
        state = m2.mamba2_state(cfg, b)
        sp = params.get("shared_attn")

        def attn_fn(x, _):
            if sp is None:
                return x, 0.0
            h = rms_norm(x, sp["norm1"], cfg.norm_eps)
            a = attn_forward(cfg, sp["attn"], h, positions, causal=causal)
            x = x + a
            h = rms_norm(x, sp["norm2"], cfg.norm_eps)
            return x + swiglu(sp["mlp"], h), 0.0

        x, _, _ = _run_zamba_stack(cfg, params, x, positions, state, attn_fn,
                                   jnp.zeros((g,)), remat=remat)
        extras = {"moe_aux": jnp.float32(0.0)}

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, extras


# --------------------------------------------------------------------------
# loss (chunked cross-entropy — never materialises [B,T,V])
# --------------------------------------------------------------------------

def _chunked_ce(head: Params, x, labels, mask, chunk: int = 512):
    """x: [B,T,D] final hidden; labels/mask: [B,T]. Mean CE over mask."""
    b, t, d = x.shape
    chunk = min(chunk, t)
    assert t % chunk == 0
    n = t // chunk
    xs = (jnp.moveaxis(x.reshape(b, n, chunk, d), 1, 0),
          jnp.moveaxis(labels.reshape(b, n, chunk), 1, 0),
          jnp.moveaxis(mask.reshape(b, n, chunk), 1, 0))

    @jax.checkpoint
    def body(carry, inp):
        xc, yc, mc = inp
        logits = dense(head, xc).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        ce = (lse - gold) * mc
        ncorrect = jnp.sum((jnp.argmax(logits, -1) == yc) * mc)
        return (carry[0] + jnp.sum(ce), carry[1] + jnp.sum(mc),
                carry[2] + ncorrect), None

    (tot, cnt, ncorr), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0)), xs,
        unroll=scan_unroll())
    return tot / jnp.maximum(cnt, 1.0), ncorr / jnp.maximum(cnt, 1.0)


def loss_fn(cfg: ModelConfig, params: Params, batch: Batch, *,
            remat: bool = True):
    """Next-token LM loss (decoders) or per-frame classification (encoders)."""
    x, extras = forward(cfg, params, batch, remat=remat)
    if cfg.is_encoder:
        labels = batch["labels"]
        mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
        loss, acc = _chunked_ce(params["head"], x, labels, mask)
    else:
        if "labels" in batch:
            labels = batch["labels"]
            mask = batch.get("mask", jnp.ones(labels.shape, jnp.float32))
        elif "embeds" in batch and "tokens" in batch:
            # VLM: image-patch prefix emits no labels; next-token loss over
            # the text region only (last text position zero-masked).
            toks = batch["tokens"]
            b, t_img = batch["embeds"].shape[:2]
            labels = jnp.concatenate(
                [jnp.zeros((b, t_img), toks.dtype),
                 toks[:, 1:], jnp.zeros_like(toks[:, :1])], axis=1)
            mask = jnp.concatenate(
                [jnp.zeros((b, t_img), jnp.float32),
                 jnp.ones(toks[:, 1:].shape, jnp.float32),
                 jnp.zeros(toks[:, :1].shape, jnp.float32)], axis=1)
        else:
            # next-token: shift left, zero-mask the final position so the
            # time axis stays chunk-divisible.
            toks = batch["tokens"]
            labels = jnp.concatenate(
                [toks[:, 1:], jnp.zeros_like(toks[:, :1])], axis=1)
            mask = jnp.concatenate(
                [jnp.ones(toks[:, 1:].shape, jnp.float32),
                 jnp.zeros(toks[:, :1].shape, jnp.float32)], axis=1)
        loss, acc = _chunked_ce(params["head"], x, labels, mask)
    total = loss + cfg.router_aux_loss_coef * extras["moe_aux"]
    return total, {"ce": loss, "acc": acc, "moe_aux": extras["moe_aux"]}


# --------------------------------------------------------------------------
# prefill / decode (serving path)
# --------------------------------------------------------------------------

def make_cache(cfg: ModelConfig, batch: int, max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    if cfg.block_type == "attn":
        n_dense = cfg.first_dense_layers
        n_main = cfg.num_layers - n_dense
        if cfg.use_mla:
            cache = {"main": mla_mod.make_mla_cache(cfg, batch, max_len,
                                                    dtype, layers=n_main)}
            if n_dense:
                cache["dense"] = mla_mod.make_mla_cache(cfg, batch, max_len,
                                                        dtype, layers=n_dense)
        else:
            cache = {"main": make_kv_cache(cfg, batch, max_len, dtype,
                                           layers=n_main)}
            if n_dense:
                cache["dense"] = make_kv_cache(cfg, batch, max_len, dtype,
                                               layers=n_dense)
        return cache
    if cfg.block_type == "rwkv6":
        return {"rwkv": rwkv.rwkv6_state(cfg, batch)}
    # zamba hybrid: mamba state + per-group shared-attn KV cache
    g, _ = _zamba_groups(cfg)
    hd = cfg.resolved_head_dim
    return {
        "mamba": m2.mamba2_state(cfg, batch),
        "attn_k": jnp.zeros((g, batch, max_len, cfg.num_kv_heads, hd), dtype),
        "attn_v": jnp.zeros((g, batch, max_len, cfg.num_kv_heads, hd), dtype),
    }


def _head_logits(cfg: ModelConfig, params: Params, x_last):
    """x_last: [B, D] -> logits [B, V or C] fp32."""
    return dense(params["head"], x_last).astype(jnp.float32)


def prefill(cfg: ModelConfig, params: Params, batch: Batch):
    """Run the full prompt; return (last-position logits, cache)."""
    x = _embed_in(cfg, params, batch)
    b, t, _ = x.shape
    positions = jnp.arange(t)

    if cfg.block_type == "attn":
        def body(x, lp):
            h = rms_norm(x, lp["norm1"], cfg.norm_eps)
            if cfg.use_mla:
                a, (c_kv, k_r) = mla_mod.mla_forward(cfg, lp["attn"], h,
                                                     positions, causal=True)
                kv = {"c_kv": c_kv, "k_rope": k_r}
            else:
                a, (k, v) = attn_prefill(cfg, lp["attn"], h, positions)
                kv = {"k": k, "v": v}
            x = x + a
            h = rms_norm(x, lp["norm2"], cfg.norm_eps)
            if "moe" in lp:
                y, _ = moe_mod.moe_dropless(cfg, lp["moe"], h)
            else:
                y = swiglu(lp["mlp"], h)
            return x + y, kv

        cache = {}
        if "dense_blocks" in params:
            x, cache["dense"] = jax.lax.scan(body, x, params["dense_blocks"],
                                             unroll=scan_unroll())
        x, cache["main"] = jax.lax.scan(body, x, params["blocks"],
                                        unroll=scan_unroll())
    elif cfg.block_type == "rwkv6":
        state = rwkv.rwkv6_state(cfg, b)
        x, state = _run_rwkv_stack(cfg, params, x, state, remat=False)
        cache = {"rwkv": state}
    else:
        g, _ = _zamba_groups(cfg)
        state = m2.mamba2_state(cfg, b)
        sp = params.get("shared_attn")

        def attn_fn(x, _):
            h = rms_norm(x, sp["norm1"], cfg.norm_eps)
            a, (k, v) = attn_prefill(cfg, sp["attn"], h, positions)
            x = x + a
            h = rms_norm(x, sp["norm2"], cfg.norm_eps)
            return x + swiglu(sp["mlp"], h), (k, v)

        x, new_mamba, (ks, vs) = _run_zamba_stack(
            cfg, params, x, positions, state, attn_fn, jnp.zeros((g,)),
            remat=False)
        cache = {"mamba": new_mamba, "attn_k": ks, "attn_v": vs}

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _head_logits(cfg, params, x[:, -1]), cache


def decode_step(cfg: ModelConfig, params: Params, token, cache, pos):
    """One new token. token: [B] int32 (or [B,D] embeds); pos: [] int32.
    Returns (logits [B,V], new cache)."""
    assert cfg.supports_decode, f"{cfg.name} is encoder-only"
    if token.ndim == 1:
        x = params["embed"][token][:, None, :]
    else:
        x = token.astype(jnp.dtype(cfg.dtype))[:, None, :]
    if cfg.block_type == "attn":
        def body(x, inp):
            lp, kv = inp
            h = rms_norm(x, lp["norm1"], cfg.norm_eps)
            if cfg.use_mla:
                a, ck, kr = mla_mod.mla_decode(cfg, lp["attn"], h,
                                               kv["c_kv"], kv["k_rope"], pos)
                new_kv = {"c_kv": ck, "k_rope": kr}
            else:
                a, kc, vc = attn_decode(cfg, lp["attn"], h, kv["k"], kv["v"],
                                        pos)
                new_kv = {"k": kc, "v": vc}
            x = x + a
            h = rms_norm(x, lp["norm2"], cfg.norm_eps)
            if "moe" in lp:
                y, _ = moe_mod.moe_dropless(cfg, lp["moe"], h)
            else:
                y = swiglu(lp["mlp"], h)
            return x + y, new_kv

        new_cache = {}
        if "dense" in cache:
            x, new_cache["dense"] = jax.lax.scan(
                body, x, (params["dense_blocks"], cache["dense"]),
                unroll=scan_unroll())
        x, new_cache["main"] = jax.lax.scan(
            body, x, (params["blocks"], cache["main"]),
            unroll=scan_unroll())
    elif cfg.block_type == "rwkv6":
        x, state = _run_rwkv_stack(cfg, params, x, cache["rwkv"],
                                   remat=False)
        new_cache = {"rwkv": state}
    else:
        sp = params.get("shared_attn")

        def attn_fn(x, gattn):
            kc, vc = gattn
            h = rms_norm(x, sp["norm1"], cfg.norm_eps)
            a, kc, vc = attn_decode(cfg, sp["attn"], h, kc, vc, pos)
            x = x + a
            h = rms_norm(x, sp["norm2"], cfg.norm_eps)
            return x + swiglu(sp["mlp"], h), (kc, vc)

        x, new_mamba, (ks, vs) = _run_zamba_stack(
            cfg, params, x, None, cache["mamba"], attn_fn,
            (cache["attn_k"], cache["attn_v"]), remat=False)
        new_cache = {"mamba": new_mamba, "attn_k": ks, "attn_v": vs}

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _head_logits(cfg, params, x[:, 0]), new_cache
