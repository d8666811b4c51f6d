"""Operations the trunk of a dense decoder needs for one forward pass.

Counted from the algorithm, not from an implementation: two operations per
weight of every matrix in the decoder layers per token (the embedding is a
gather and the LM head is counted with its kernel, so neither is here),
plus the causal half of the attention score and value products, ``2 *
heads * head_dim`` each per visible (query, key) pair. A sliding window
caps the keys a query sees.
"""

from __future__ import annotations

import numpy as np


def matrix_params(shapes: dict) -> int:
    """Weights of the stacked layer matrices in a ``program_shapes`` tree
    (every ``[layers, in, out]`` leaf under ``blocks``)."""
    total = 0

    def walk(node):
        nonlocal total
        if isinstance(node, dict):
            for v in node.values():
                walk(v)
        elif len(node.shape) == 3:
            total += int(np.prod(node.shape))

    walk(shapes["blocks"])
    return total


def visible_pairs(seq_len: int, window: int = 0) -> int:
    """(query, key) pairs a causal mask, and window, leaves per sequence."""
    q = np.arange(seq_len)
    seen = q + 1 if not window else np.minimum(q + 1, window)
    return int(seen.sum())


def trunk_flops(shapes: dict, sizes: dict, batch: int, seq_len: int) -> float:
    hd = sizes.get("head_dim") or sizes["hidden_size"] // sizes[
        "num_attention_heads"]
    tokens = batch * seq_len
    attn = (2 * 2 * sizes["num_attention_heads"] * hd
            * visible_pairs(seq_len, sizes.get("sliding_window") or 0)
            * batch * sizes["num_hidden_layers"])
    return 2.0 * matrix_params(shapes) * tokens + attn
