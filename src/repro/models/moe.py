"""Mixture-of-Experts layer: top-k router + capacity-based dispatch.

TPU-native formulation, GShard/Switch-style: the token stream is split
into G dispatch GROUPS (G = the ambient mesh's `data` size, 1 on a single
device), each group gets its own capacity and a group-LOCAL cumsum for
slot assignment, so dispatch never needs cross-shard prefix sums and the
expert-major buffer [G, E, C_g, D] shards cleanly as
P("data", "model", None, None) — experts over `model` (EP), groups over
`data` (DP). Expert compute is one batched einsum over stacked expert
weights (MXU friendly). Tokens beyond an expert's per-group capacity are
dropped (classic GShard semantics); capacity_factor controls the rate.

§Perf history: the original single-group global-cumsum dispatch forced
XLA SPMD to REPLICATE the expert einsum on every chip (the scatter with
global indices could not be partitioned) — 256x redundant expert compute
on the production mesh. The grouped formulation is iteration C3 in
EXPERIMENTS.md.

A load-balance auxiliary loss (Switch-style, computed over ALL tokens) is
returned alongside.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Params, dense_params, swiglu, swiglu_params


from repro.models.shard_hints import constrain as _constrain


def _dispatch_groups(n: int) -> int:
    """Number of dispatch groups = ambient `data` axis size (1 if absent
    or indivisible)."""
    mesh = jax.sharding.get_abstract_mesh()
    if "data" not in mesh.axis_names:
        return 1
    g = mesh.shape["data"]
    return g if n % g == 0 else 1


def moe_params(key, cfg: ModelConfig, dtype) -> Params:
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    scale = 1.0 / jnp.sqrt(d)
    p = {
        "router": dense_params(ks[0], d, e, jnp.float32),
        "w_gate": jax.random.normal(ks[1], (e, d, f)).astype(dtype) * scale,
        "w_up": jax.random.normal(ks[2], (e, d, f)).astype(dtype) * scale,
        "w_down": jax.random.normal(ks[3], (e, f, d)).astype(dtype)
                  / jnp.sqrt(f),
    }
    if cfg.num_shared_experts:
        p["shared"] = swiglu_params(
            ks[4], d, cfg.num_shared_experts * cfg.moe_d_ff, dtype)
    return p


def _group_dispatch(xg, top_e, top_p, e: int, k: int, cap: int):
    """Per-group dispatch. xg: [M, D]; top_e/top_p: [M, k].
    Returns (xe [E, cap, D], flat_idx [M*k], weight [M*k])."""
    m, d = xg.shape
    flat_e = top_e.reshape(m * k)                           # slot-major
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)     # [M*k, E]
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot          # group-LOCAL
    pos = jnp.sum(pos_in_e * onehot, axis=-1)               # [M*k]
    keep = pos < cap
    flat_idx = jnp.where(keep, flat_e * cap + pos, e * cap)  # drop slot
    tok_idx = jnp.tile(jnp.arange(m)[:, None], (1, k)).reshape(m * k)
    buf = jnp.zeros((e * cap + 1, d), xg.dtype)
    buf = buf.at[flat_idx].set(xg[tok_idx], mode="drop",
                               unique_indices=False)
    xe = buf[: e * cap].reshape(e, cap, d)
    weight = (top_p.reshape(m * k) * keep)
    return xe, flat_idx, weight


def _group_combine(ye, flat_idx, weight, m: int, k: int):
    """ye: [E, cap, D] -> y [M, D] (router-prob weighted)."""
    e, cap, d = ye.shape
    ye_flat = jnp.concatenate(
        [ye.reshape(e * cap, d), jnp.zeros((1, d), ye.dtype)], axis=0)
    gathered = ye_flat[flat_idx]                            # [M*k, D]
    w = weight.astype(gathered.dtype)
    return jnp.sum((gathered * w[:, None]).reshape(m, k, d), axis=1)


def moe_forward(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                capacity_factor: float | None = None,
                dropless: bool = False):
    """x: [B, T, D] -> (y [B, T, D], aux_loss scalar).

    ``dropless=True`` sizes the per-expert capacity so no token can be
    dropped (each token occupies at most one slot per expert, so cap = m
    suffices). Serving paths use it: capacity dropping is a training
    throughput tradeoff, and it breaks prefill/decode equivalence — the
    same token drops in a crowded prefill but not in a 1-token decode."""
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    n = b * t
    g = _dispatch_groups(n)
    m = n // g                                              # tokens/group
    if dropless:
        cap = m
    else:
        cf = (cfg.capacity_factor if capacity_factor is None
              else capacity_factor)
        cap = max(int(m * k * cf / e), 1)
    # round capacity to a lane-friendly multiple of 8
    cap = (cap + 7) // 8 * 8

    xf = x.reshape(n, d)
    router_logits = (xf.astype(jnp.float32)
                     @ p["router"]["w"].astype(jnp.float32))      # [N, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                        # [N, k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)        # renormalise

    # ---- load-balance aux loss (Switch): E * sum_e f_e * P_e ----
    me = jnp.mean(probs, axis=0)                                   # [E]
    onehot_any = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=1)
    ce = jnp.mean(onehot_any, axis=0) / k                          # [E]
    aux = e * jnp.sum(me * ce)

    # ---- grouped dispatch: G groups, group-local capacity + cumsum ----
    xg = _constrain(xf.reshape(g, m, d), "data", None, None)
    te = top_e.reshape(g, m, k)
    tp = top_p.reshape(g, m, k)
    xe, flat_idx, weight = jax.vmap(
        lambda xi, ei, pi: _group_dispatch(xi, ei, pi, e, k, cap))(
        xg, te, tp)                                 # xe: [G, E, cap, D]
    xe = _constrain(xe, "data", "model", None, None)

    # ---- expert compute: stacked swiglu, batched over groups ----
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])) \
        * jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])       # [G, E, cap, D]
    ye = _constrain(ye, "data", "model", None, None)

    # ---- combine: per-group gather, router-prob weighted ----
    y = jax.vmap(lambda yi, fi, wi: _group_combine(yi, fi, wi, m, k))(
        ye, flat_idx, weight)                               # [G, M, D]
    y = _constrain(y, "data", None, None).reshape(n, d)

    if cfg.num_shared_experts:
        y = y + swiglu(p["shared"], xf)
    return y.reshape(b, t, d), aux.astype(jnp.float32)
