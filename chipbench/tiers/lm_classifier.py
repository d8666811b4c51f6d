"""Local tier: a dense decoder LM read as a classifier over its vocabulary.

The served answer is the token the LM head puts first at the last prompt
position. ``build`` returns the program's own ``FusedLocalHead``: the trunk
is ``models.transformer.forward`` up to the last position's normed hidden
state, and the head is the model's LM head ``[d_model, vocab]`` with no
bias, which the engine folds into the ``fused_head_gate`` kernel.

The configuration file's published sizes are what runs: they replace the
program's registry entry (``repro_id``) field by field, so a file whose
sizes the registry does not hold still runs as written.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

from chipbench.weights import dense_decoder as W

# published (Hugging Face) size -> program ModelConfig field
FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
          "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "sliding_window": "sliding_window", "head_dim": "head_dim"}


@dataclass
class Tier:
    local_apply: Any            # FusedLocalHead
    params: Any                 # device pytree, freed with the tier
    vocab: int
    seq_len: int


def model_config(config: dict):
    from repro.configs import get_config
    base = get_config(config["repro_id"])
    sizes = config["model"]
    changes = {FIELDS[k]: sizes[k] for k in FIELDS if sizes.get(k) is not None}
    changes["dtype"] = sizes["torch_dtype"]
    mcfg = dataclasses.replace(base, **changes)
    if mcfg.family != "dense" or mcfg.block_type != "attn" or mcfg.is_moe:
        raise ValueError(f"{config['name']}: lm_classifier runs dense "
                         f"decoders, not {mcfg.family}/{mcfg.block_type}")
    return mcfg


def build(config: dict, seed: int) -> Tier:
    import jax.numpy as jnp
    from repro.kernels.fused_head_gate.ops import FusedLocalHead
    from repro.models import transformer as T

    mcfg = model_config(config)
    sizes = config["model"]
    params = W.program_params(seed, sizes, jnp.dtype(sizes["torch_dtype"]))

    def trunk(tokens):
        return T.forward(mcfg, params, {"tokens": tokens})[0][:, -1]

    return Tier(FusedLocalHead(trunk, params["head"]["w"], None), params,
                vocab=sizes["vocab_size"], seq_len=config["seq_len"])
