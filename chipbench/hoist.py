"""Make the weights a compiled step closes over its arguments, not constants.

The engine jits the gated local step over ``(batch, t_local, n_valid)``
alone, so the local tier's weights reach it by closure, and JAX folds
closed-over arrays into the program as constants: 12 GB of them cannot be
compiled. JAX's ``jax_use_simplified_jaxpr_constants`` mode passes such
arrays as hidden arguments instead, and the compiled program, and so the
persistent cache's key, no longer depends on their values. In JAX 0.9 that
mode fails on small host literals that tracing makes (``jnp.arange`` over
static bounds in the rotary embedding): they carry no sharding.
``enable`` turns the mode on and places each such literal on the device
once. It must run before JAX is first imported.
"""

from __future__ import annotations

import os
import sys


def enable() -> None:
    if "jax" in sys.modules:
        raise RuntimeError("chipbench.hoist.enable() must run before JAX is "
                           "imported")
    os.environ["JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS"] = "true"
    import jax
    from jax._src import core, literals

    original = core.jaxpr_const_args
    placed: dict[int, tuple] = {}       # id(literal) -> (literal, array)

    def const_args(jaxpr):
        out = []
        for c, aval in original(jaxpr):
            if isinstance(c, literals.TypedNdArray):
                if id(c) not in placed:
                    placed[id(c)] = (c, jax.device_put(c.val))
                c = placed[id(c)][1]
            out.append((c, aval))
        return out

    core.jaxpr_const_args = const_args
