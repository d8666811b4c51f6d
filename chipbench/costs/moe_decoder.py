"""Operations and bytes a DeepSeek-V2 trunk (MLA + shared-expert MoE) needs
for one forward pass, at one device's share of the experts.

Counted from the algorithm, not from an implementation, two operations a
multiply-add:

* MLA projections, per token and layer: the query ``d x h(dn+dr)``, the
  latent ``d x r`` and the shared rope key ``d x dr``, the per-head key
  and value up-projections ``r x h dn`` and ``r x h dv``, the output
  ``h dv x d``;
* the causal half of the attention products, ``2 h (dn + dr)`` for the
  scores and ``2 h dv`` for the values per visible (query, key) pair;
* the dense MLP of the first ``first_k_dense_replace`` layers, ``3 d F``
  a token;
* per MoE layer and token the router ``d x E`` and the shared experts
  ``3 d (n_shared f)``;
* the routed rows each held expert computes, ``2 x 3 d f`` a row: a
  number the device counts (``moe.held_rows``), or the uniform router's
  expectation ``tokens x k x held / E``.

The expert product's bytes (``expert_gmm_bytes``) are each held expert's
three matrices read once a layer, and the routed rows read in and written
out once, in the served type. The LM head is counted with its kernel
(``costs/fused_head_gate.py``), the embedding is a gather.
"""

from __future__ import annotations

from chipbench.costs.dense_decoder import visible_pairs


def _held(sizes: dict) -> int:
    return sizes.get("experts_held") or sizes["n_routed_experts"]


def moe_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - sizes["first_k_dense_replace"]


def expected_rows(sizes: dict, tokens: int) -> float:
    """Routed rows a MoE layer's held experts compute under a uniform
    router."""
    return (tokens * sizes["num_experts_per_tok"] * _held(sizes)
            / sizes["n_routed_experts"])


def mla_params(sizes: dict) -> int:
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, r = sizes["v_head_dim"], sizes["kv_lora_rank"]
    return d * h * (dn + dr) + d * r + d * dr + r * h * dn + r * h * dv \
        + h * dv * d


def expert_params(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def held_params(sizes: dict) -> dict:
    """Parameters this device holds, by part."""
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    n_moe = moe_layers(sizes)
    return {"experts": n_moe * _held(sizes) * expert_params(sizes),
            "shared": n_moe * sizes["n_shared_experts"] * expert_params(
                sizes),
            "router": n_moe * d * sizes["n_routed_experts"],
            "mla": sizes["num_hidden_layers"] * mla_params(sizes),
            "dense_mlp": sizes["first_k_dense_replace"] * 3 * d * sizes[
                "intermediate_size"],
            "embed_head": 2 * d * v,
            "norms": sizes["num_hidden_layers"] * (2 * d + sizes[
                "kv_lora_rank"]) + d}


def routed_flops(sizes: dict, rows: float) -> float:
    return 2.0 * rows * expert_params(sizes)


def expert_gmm_bytes(sizes: dict, rows: float, w_bytes: int = 2) -> float:
    """One MoE layer's grouped products: the held experts' weights once,
    the routed rows in and out once."""
    return float(w_bytes * (_held(sizes) * expert_params(sizes)
                            + 2 * rows * sizes["hidden_size"]))


def trunk_flops(sizes: dict, batch: int, seq_len: int,
                rows: list | None = None) -> dict:
    """Operations of one dispatch by part. ``rows`` is the routed rows of
    each MoE layer (the device's count); without it, the uniform router's
    expectation."""
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    tokens = batch * seq_len
    n_layers, n_moe = sizes["num_hidden_layers"], moe_layers(sizes)
    if rows is None:
        rows = [expected_rows(sizes, tokens)] * n_moe
    pairs = visible_pairs(seq_len) * batch
    head_width = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
                  + sizes["v_head_dim"])
    out = {"mla_proj": 2.0 * mla_params(sizes) * tokens * n_layers,
           "attention": 2.0 * h * head_width * pairs * n_layers,
           "dense_mlp": 2.0 * 3 * d * sizes["intermediate_size"] * tokens
           * sizes["first_k_dense_replace"],
           "shared": 2.0 * sizes["n_shared_experts"] * expert_params(sizes)
           * tokens * n_moe,
           "router": 2.0 * d * sizes["n_routed_experts"] * tokens * n_moe,
           "routed": sum(routed_flops(sizes, r) for r in rows)}
    out["total"] = sum(out.values())
    return out
