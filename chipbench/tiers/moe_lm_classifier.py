"""Local tier: a DeepSeek-V2 decoder (MLA + shared-expert MoE) read as a
classifier over its vocabulary, holding one device's share of the experts.

The served answer is the token the LM head puts first at the last prompt
position. ``build`` returns the program's own ``FusedLocalHead``: the trunk
is ``models.transformer.forward`` with its expert layers dropless (as
served) up to the last position's normed hidden state, and it counts the
rows each held expert computed (``moe.held_rows``, ``[MoE layers,
held]``), which the gated step carries to the host beside its outputs.

The configuration file's published sizes are what runs: they replace the
program's registry entry (``repro_id``) field by field, and
``experts_held`` sets the share. A published setting this tier does not
implement (another attention, router or rope) is refused, never run as
something else.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

from chipbench.weights import moe_decoder as W

COUNTER = "moe.held_rows"

# published (Hugging Face) size -> program ModelConfig field
FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
          "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "kv_lora_rank": "kv_lora_rank",
          "qk_nope_head_dim": "qk_nope_head_dim",
          "qk_rope_head_dim": "qk_rope_head_dim",
          "v_head_dim": "v_head_dim", "n_routed_experts": "num_experts",
          "num_experts_per_tok": "num_experts_per_tok",
          "n_shared_experts": "num_shared_experts",
          "moe_intermediate_size": "moe_d_ff",
          "first_k_dense_replace": "first_dense_layers",
          "norm_topk_prob": "norm_topk_prob",
          "routed_scaling_factor": "routed_scaling_factor",
          "experts_held": "experts_held", "first_expert": "first_expert"}

# published settings whose other values this tier does not implement
IMPLEMENTED = {"model_type": "deepseek_v2", "hidden_act": "silu",
               "attention_bias": False, "q_lora_rank": None,
               "scoring_func": "softmax", "topk_method": "greedy",
               "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
               "tie_word_embeddings": False}


@dataclass
class Tier:
    local_apply: Any            # FusedLocalHead
    params: Any                 # device pytree, freed with the tier
    vocab: int
    seq_len: int


def model_config(config: dict):
    from repro.configs import YarnRope, get_config
    sizes = config["model"]
    for key, want in IMPLEMENTED.items():
        if sizes.get(key, want) != want:
            raise ValueError(f"{config['name']}: moe_lm_classifier runs "
                             f"{key}={want!r}, not {sizes[key]!r}")
    rope = sizes.get("rope_scaling")
    if rope and rope.get("type") != "yarn":
        raise ValueError(f"{config['name']}: rope_scaling "
                         f"{rope.get('type')!r} is not implemented")
    base = get_config(config["repro_id"])
    changes = {FIELDS[k]: sizes[k] for k in FIELDS if sizes.get(k) is not None}
    changes["dtype"] = sizes["torch_dtype"]
    changes["rope_scaling"] = None if not rope else YarnRope(
        factor=rope["factor"],
        original_max_position_embeddings=rope[
            "original_max_position_embeddings"],
        beta_fast=rope["beta_fast"], beta_slow=rope["beta_slow"],
        mscale=rope["mscale"], mscale_all_dim=rope["mscale_all_dim"])
    mcfg = dataclasses.replace(base, **changes)
    if not (mcfg.block_type == "attn" and mcfg.use_mla and mcfg.is_moe):
        raise ValueError(f"{config['name']}: moe_lm_classifier runs MLA "
                         f"MoE decoders, not {mcfg.family}/"
                         f"{mcfg.block_type}")
    return mcfg


def build(config: dict, seed: int) -> Tier:
    import jax.numpy as jnp
    from repro.kernels.fused_head_gate.ops import FusedLocalHead
    from repro.models import transformer as T

    mcfg = model_config(config)
    sizes = config["model"]
    params = W.program_params(seed, sizes, jnp.dtype(sizes["torch_dtype"]))

    def trunk(tokens):
        x, extras = T.forward(mcfg, params, {"tokens": tokens},
                              dropless=True)
        return x[:, -1], {COUNTER: extras["moe_held_rows"]}

    return Tier(FusedLocalHead(trunk, params["head"]["w"], None,
                               counted=True),
                params, vocab=sizes["vocab_size"], seq_len=config["seq_len"])
