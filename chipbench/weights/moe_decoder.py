"""Seeded weights of a DeepSeek-V2 decoder (MLA + shared-expert MoE), drawn
on the device.

Every tensor is a function of ``(seed, name, layer[, expert])`` alone:
``dense_layer`` / ``moe_layer`` draw one decoder layer in the published
(Hugging Face) semantics, with the routed experts a device holds stacked
``[held, ...]`` by their global ids, and ``program_params`` stacks the
same draws over the layer axis (``vmap`` of a threefry draw equals the
per-key draws), so the served model and the layer-by-layer reference read
identical numbers. Two layout facts separate the two: the published
checkpoint keeps each rope part (a query head's last ``qk_rope_head_dim``
columns, and the shared rope key) interleaved, pairs ``(2i, 2i+1)``
turning together, where the program rotates halves ``(i, i + dr/2)``, so
``program_params`` permutes those columns (``ROPE_PERM``); and the
published ``kv_a_proj_with_mqa`` / ``kv_b_proj`` are drawn here as their
parts (``w_dkv``, ``w_kr``; ``w_uk``, ``w_uv``), which is the same product.

``sizes`` is the configuration's published sizes under their Hugging Face
names, plus ``experts_held`` (and ``first_expert``, default 0): the
contiguous share of ``n_routed_experts`` this device holds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights.dense_decoder import (_dense, _norm, embed,
                                             final_norm, head, model_key)

__all__ = ["model_key", "embed", "final_norm", "head", "held",
           "dense_layer", "moe_layer", "program_params", "program_shapes",
           "rope_perm"]


def held(sizes: dict) -> tuple[int, int]:
    """(first, count) of the routed experts held here."""
    return (sizes.get("first_expert", 0),
            sizes.get("experts_held") or sizes["n_routed_experts"])


def rope_perm(dr: int) -> np.ndarray:
    """Program column j of a rope part reads published column
    ``rope_perm(dr)[j]``: the even columns, then the odd ones."""
    return np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])


def _layer_keys(mkey, l):
    return jax.random.split(jax.random.fold_in(jax.random.fold_in(mkey, 1),
                                               l), 14)


def _attn(ks, sizes, dtype) -> dict:
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, r = sizes["v_head_dim"], sizes["kv_lora_rank"]
    return {"wq": _dense(ks[2], (d, h * (dn + dr)), dtype),
            "w_dkv": _dense(ks[3], (d, r), dtype),
            "kv_norm": _norm(ks[4], r, dtype),
            "w_kr": _dense(ks[5], (d, dr), dtype),
            "w_uk": _dense(ks[6], (r, h * dn), dtype),
            "w_uv": _dense(ks[7], (r, h * dv), dtype),
            "wo": _dense(ks[8], (h * dv, d), dtype)}


def _mlp(ks, d, f, dtype) -> dict:
    return {"w_gate": _dense(ks[0], (d, f), dtype),
            "w_up": _dense(ks[1], (d, f), dtype),
            "w_down": _dense(ks[2], (f, d), dtype)}


def dense_layer(mkey, l, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """Layer ``l`` with a dense MLP (the first ``first_k_dense_replace``
    layers), published layout."""
    ks = _layer_keys(mkey, l)
    d = sizes["hidden_size"]
    return {"norm1": _norm(ks[0], d, dtype), "norm2": _norm(ks[1], d, dtype),
            "attn": _attn(ks, sizes, dtype),
            "mlp": _mlp(ks[9:12], d, sizes["intermediate_size"], dtype)}


def moe_layer(mkey, l, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """Layer ``l`` with the expert layer: the router over every routed
    expert, the shared experts as one MLP of their summed width, and the
    held experts ``[held, ...]``, expert ``e`` drawn from its global id."""
    ks = _layer_keys(mkey, l)
    d, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    first, count = held(sizes)

    def expert(e):
        return _mlp(jax.random.split(jax.random.fold_in(ks[13], e), 3),
                    d, f, dtype)

    return {"norm1": _norm(ks[0], d, dtype), "norm2": _norm(ks[1], d, dtype),
            "attn": _attn(ks, sizes, dtype),
            "router": _dense(ks[12], (d, sizes["n_routed_experts"]), dtype),
            "shared": _mlp(ks[9:12], d, sizes["n_shared_experts"] * f, dtype),
            "experts": jax.vmap(expert)(first + jnp.arange(count))}


def _program_layer(p: dict, sizes: dict) -> dict:
    """One published layer in the program's pytree layout."""
    h, dn = sizes["num_attention_heads"], sizes["qk_nope_head_dim"]
    dr = sizes["qk_rope_head_dim"]
    a = p["attn"]
    perm = rope_perm(dr)
    wq = a["wq"].reshape(a["wq"].shape[0], h, dn + dr)
    wq = jnp.concatenate([wq[..., :dn], wq[..., dn:][..., perm]], -1)
    out = {"norm1": p["norm1"], "norm2": p["norm2"],
           "attn": {"wq": {"w": wq.reshape(a["wq"].shape)},
                    "w_dkv": {"w": a["w_dkv"]}, "kv_norm": a["kv_norm"],
                    "w_kr": {"w": a["w_kr"][:, perm]},
                    "w_uk": {"w": a["w_uk"]}, "w_uv": {"w": a["w_uv"]},
                    "wo": {"w": a["wo"]}}}
    if "mlp" in p:
        out["mlp"] = {k: {"w": v} for k, v in p["mlp"].items()}
    else:
        out["moe"] = {"router": {"w": p["router"]},
                      **p["experts"],
                      "shared": {k: {"w": v} for k, v in p["shared"].items()}}
    return out


def _program_params(mkey, sizes, dtype):
    n_dense = sizes["first_k_dense_replace"]
    out = {"embed": embed(mkey, sizes, dtype),
           "final_norm": final_norm(mkey, sizes, dtype),
           "head": {"w": head(mkey, sizes, dtype)},
           "blocks": jax.vmap(lambda l: _program_layer(
               moe_layer(mkey, l, sizes, dtype), sizes))(
               jnp.arange(n_dense, sizes["num_hidden_layers"]))}
    if n_dense:
        out["dense_blocks"] = jax.vmap(lambda l: _program_layer(
            dense_layer(mkey, l, sizes, dtype), sizes))(jnp.arange(n_dense))
    return out


def program_params(seed: int, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """All weights in the program's pytree layout, drawn in one jitted
    call on the default device."""
    fn = jax.jit(lambda k: _program_params(k, sizes, dtype))
    return jax.block_until_ready(fn(model_key(seed)))


def program_shapes(sizes: dict, dtype=jnp.bfloat16) -> dict:
    """``program_params``'s shapes, with nothing allocated."""
    return jax.eval_shape(lambda k: _program_params(k, sizes, dtype),
                          jax.random.PRNGKey(0))
