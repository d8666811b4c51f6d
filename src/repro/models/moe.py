"""Mixture-of-Experts layer: a top-k router and two ways to run the experts.

Serving runs **dropless** (``moe_dropless``): every (token, expert)
assignment to an expert this device holds is computed, so a row's answer
never depends on which rows share its dispatch. The assignments are sorted
by expert and each projection is one grouped matrix product over the held
experts' stacked weights (``kernels/expert_gmm``, the gate and up
projections in one kernel), whose work follows the routed rows: there is
no capacity and no ``[E, m, D]`` buffer. The layer may hold only a
contiguous share of the experts (``ModelConfig.experts_held`` from
``first_expert``), as one device of an expert-parallel
deployment does: the router still scores every expert, assignments to
absent experts add nothing here (their devices add them), and the shared
experts are added once. The held experts' row counts, the product's group
sizes, come back with the output for the device trace's counter.

Training runs the **capacity** path (``moe_forward``), TPU-native and
GShard/Switch-style: the token stream is split into G dispatch GROUPS (G
= the ambient mesh's `data` size, 1 on a single device), each group gets
its own capacity and a group-LOCAL cumsum for slot assignment, so
dispatch never needs cross-shard prefix sums and the expert-major buffer
[G, E, C_g, D] shards cleanly as P("data", "model", None, None) — experts
over `model` (EP), groups over `data` (DP). Expert compute is one batched
einsum over stacked expert weights (MXU friendly). Tokens beyond an
expert's per-group capacity are dropped (classic GShard semantics);
capacity_factor controls the rate.

§Perf history: the original single-group global-cumsum dispatch forced
XLA SPMD to REPLICATE the expert einsum on every chip (the scatter with
global indices could not be partitioned) — 256x redundant expert compute
on the production mesh. The grouped formulation is iteration C3 in
EXPERIMENTS.md.

Both paths route alike: f32 router logits, softmax over all experts,
greedy top-k; the top-k weights are renormalised only where the model
does so (``norm_topk_prob``), then scaled by ``routed_scaling_factor``. A
load-balance auxiliary loss (Switch-style, computed over ALL tokens) is
returned alongside on the training path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.expert_gmm.ops import expert_gmm
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
    """The router over all ``num_experts``; stacked weights of the held
    experts only."""
    _, e = cfg.held_experts
    d, f = cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    scale = 1.0 / jnp.sqrt(d)
    p = {
        "router": dense_params(ks[0], d, cfg.num_experts, jnp.float32),
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


def route(cfg: ModelConfig, router: Params, xf: jnp.ndarray):
    """xf [N, D] -> (probs [N, E] over every expert, top-k weights [N, k],
    top-k experts [N, k])."""
    logits = (xf.astype(jnp.float32)
              @ router["w"].astype(jnp.float32))             # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if cfg.routed_scaling_factor != 1.0:
        top_p = top_p * cfg.routed_scaling_factor
    return probs, top_p, top_e


def moe_dropless(cfg: ModelConfig, p: Params, x: jnp.ndarray):
    """Serving path. x [B, T, D] -> (y [B, T, D], held_rows [held] int32):
    the held experts' part of the routed sum plus the shared experts, and
    how many assignments each held expert computed."""
    b, t, d = x.shape
    n, k = b * t, cfg.num_experts_per_tok
    first, held = cfg.held_experts
    xf = x.reshape(n, d)
    with jax.named_scope("moe.route"):
        _, top_p, top_e = route(cfg, p["router"], xf)
    with jax.named_scope("moe.dispatch"):
        local = top_e.reshape(n * k) - first             # token-major
        mine = (local >= 0) & (local < held)
        group = jnp.where(mine, local, held)             # absent: last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
        xs = xf[order // k]                              # [N*k, D]
    with jax.named_scope("moe.expert_gmm"):
        h = expert_gmm(xs, p["w_gate"], sizes, p["w_up"])  # silu(g) * u
        ys = expert_gmm(h, p["w_down"], sizes)           # [N*k, D]
    with jax.named_scope("moe.combine"):
        # each assignment's row in the sorted order, token-major [N, k]
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * k, dtype=order.dtype)).reshape(n, k)
        mine = mine.reshape(n, k)
        # one gather per slot, summed in one fusion; rows past the held
        # experts' total are undefined: select, never multiply, them away
        y = jnp.zeros((n, d), jnp.float32)
        for j in range(k):
            y = y + jnp.where(mine[:, j:j + 1],
                              ys[back[:, j]].astype(jnp.float32)
                              * top_p[:, j:j + 1], 0.0)
        y = y.astype(x.dtype)
        if cfg.num_shared_experts:
            y = y + swiglu(p["shared"], xf)
    return y.reshape(b, t, d), sizes


def moe_forward(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                capacity_factor: float | None = None):
    """Training path. x: [B, T, D] -> (y [B, T, D], aux_loss scalar).
    Tokens past an expert's per-group capacity are dropped, so a token's
    output depends on its batch: serving uses ``moe_dropless``."""
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    if cfg.held_experts != (0, e):
        raise ValueError("the capacity path needs every expert; a share "
                         "of them serves through moe_dropless")
    n = b * t
    g = _dispatch_groups(n)
    m = n // g                                              # tokens/group
    cf = (cfg.capacity_factor if capacity_factor is None
          else capacity_factor)
    cap = max(int(m * k * cf / e), 1)
    # round capacity to a lane-friendly multiple of 8
    cap = (cap + 7) // 8 * 8

    xf = x.reshape(n, d)
    probs, top_p, top_e = route(cfg, p["router"], xf)      # [N, k]

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
