"""Jit'd public wrapper for the fused supervisor-confidence kernel.

On TPU dispatches to the Pallas kernel; elsewhere (e.g. the CPU tests)
falls back to the jnp oracle, so callers use one API everywhere. Pads the
batch to the block multiple when needed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.maxconf.kernel import maxconf_pallas
from repro.kernels.maxconf.ref import maxconf_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def maxconf(logits: jnp.ndarray, *, bb: int = 8, vb: int = 2048,
            force_pallas: bool = False, interpret: bool = False):
    """logits [B, V] -> {prediction, max_softmax, pcs, entropy} per row."""
    b, v = logits.shape
    if not (force_pallas or _on_tpu()):
        return maxconf_ref(logits)
    pad_b = (-b) % bb
    pad_v = (-v) % vb
    if pad_v:
        logits = jnp.pad(logits, ((0, 0), (0, pad_v)),
                         constant_values=-1e30)
    if pad_b:
        logits = jnp.pad(logits, ((0, pad_b), (0, 0)))
    out = maxconf_pallas(logits, bb=bb, vb=vb,
                         interpret=interpret or not _on_tpu())
    if pad_b:
        out = {k: a[:b] for k, a in out.items()}
    return out
