"""Seeded weights of a dense decoder (Llama / Mistral layout), drawn on the device.

Every tensor is a function of ``(seed, name, layer)`` alone: ``layer``
draws one decoder layer, and ``program_params`` stacks the same draws over
the layer axis (``vmap`` of a threefry draw equals the per-key draws), so
the served model and the layer-by-layer reference read identical numbers.
Weights are drawn in float32 and rounded to the served type in the same
jitted call, never materialised leaf by leaf on the host.

``sizes`` is the configuration's published sizes under their Hugging Face
names (``hidden_size``, ``num_attention_heads`` ...).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
NORM_STD = 0.1


def head_dim(sizes: dict) -> int:
    return sizes.get("head_dim") or sizes["hidden_size"] // sizes[
        "num_attention_heads"]


def model_key(seed: int) -> jax.Array:
    """A threefry key from a seed of up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def _dense(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            / np.sqrt(shape[0])).astype(dtype)


def _norm(key, d, dtype):
    return (1.0 + NORM_STD * jax.random.normal(key, (d,), jnp.float32)
            ).astype(dtype)


def layer(mkey, l, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """Decoder layer ``l`` (traced or static) in the program's layout."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = head_dim(sizes)
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(mkey, 1), l),
                          9)
    return {
        "norm1": _norm(ks[0], d, dtype),
        "norm2": _norm(ks[1], d, dtype),
        "attn": {"wq": {"w": _dense(ks[2], (d, h * hd), dtype)},
                 "wk": {"w": _dense(ks[3], (d, kv * hd), dtype)},
                 "wv": {"w": _dense(ks[4], (d, kv * hd), dtype)},
                 "wo": {"w": _dense(ks[5], (h * hd, d), dtype)}},
        "mlp": {"w_gate": {"w": _dense(ks[6], (d, f), dtype)},
                "w_up": {"w": _dense(ks[7], (d, f), dtype)},
                "w_down": {"w": _dense(ks[8], (f, d), dtype)}},
    }


def embed(mkey, sizes: dict, dtype=jnp.bfloat16) -> jax.Array:
    shape = (sizes["vocab_size"], sizes["hidden_size"])
    return (EMBED_STD * jax.random.normal(jax.random.fold_in(mkey, 2), shape,
                                          jnp.float32)).astype(dtype)


def final_norm(mkey, sizes: dict, dtype=jnp.bfloat16) -> jax.Array:
    return _norm(jax.random.fold_in(mkey, 3), sizes["hidden_size"], dtype)


def head(mkey, sizes: dict, dtype=jnp.bfloat16) -> jax.Array:
    return _dense(jax.random.fold_in(mkey, 4),
                  (sizes["hidden_size"], sizes["vocab_size"]), dtype)


def _program_params(mkey, sizes, dtype):
    layers = jnp.arange(sizes["num_hidden_layers"])
    return {"embed": embed(mkey, sizes, dtype),
            "final_norm": final_norm(mkey, sizes, dtype),
            "head": {"w": head(mkey, sizes, dtype)},
            "blocks": jax.vmap(lambda l: layer(mkey, l, sizes, dtype))(layers)}


def program_params(seed: int, sizes: dict, dtype=jnp.bfloat16) -> dict:
    """All weights in the program's pytree layout, drawn in one jitted
    call on the default device."""
    fn = jax.jit(lambda k: _program_params(k, sizes, dtype))
    return jax.block_until_ready(fn(model_key(seed)))


def program_shapes(sizes: dict, dtype=jnp.bfloat16) -> dict:
    """``program_params``'s shapes, with nothing allocated."""
    return jax.eval_shape(lambda k: _program_params(k, sizes, dtype),
                          jax.random.PRNGKey(0))
