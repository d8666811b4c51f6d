"""Grouped matrix product over the experts a device holds, as a Pallas TPU
kernel (the design of JAX's ``megablox`` gmm, cut to the served expert
layer).

The rows of ``lhs [m, k]`` are sorted by expert: the first
``group_sizes[0]`` rows belong to expert 0, the next ``group_sizes[1]`` to
expert 1, and so on; rows at or past ``sum(group_sizes)`` belong to no
expert and are never computed. Each grid step multiplies one ``[tm, k]``
row tile by its expert's ``[k, tn]`` weight block on the MXU and stores
the rows of the tile that belong to that expert. A tile that two experts
share is visited once by each, consecutively, so its output block stays
in VMEM between the visits. The number of row tiles visited is a traced
value (scalar-prefetched group metadata), so the work follows the routed
rows and not the static row count: a layer whose held experts receive a
quarter of the assignments computes a quarter of the tiles.

The gate and up projections run as one kernel (``up``): each row tile
meets both weight matrices and leaves as ``silu(gate) * up``, so neither
half is written out. Blocks take whole rows of both operands: the
contraction dimension (``k`` = d_model 2048 for the gate and up
projections, the expert width 1408 = 11 x 128 for the down projection)
and the output width, so an expert's weight matrix is fetched once per
run of its tiles, not once per tile and ``k`` step (5.8 MB blocks,
double-buffered, under ``VMEM_LIMIT``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT = 64 * 1024 * 1024       # v5e has 128 MiB of VMEM per core


def group_metadata(group_sizes: jnp.ndarray, m: int, tm: int):
    """Which row tile and which group each grid step works on.

    Returns ``(offsets [G+1], group_ids [T], tile_ids [T], num_tiles)``
    with ``T = m // tm + G - 1`` (the most visits any sizes need):
    ``offsets`` are the groups' first rows, the i-th step multiplies row
    tile ``tile_ids[i]`` by group ``group_ids[i]``'s weights, and only the
    first ``num_tiles`` steps run. Empty groups get no step."""
    g = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    starts = offsets[:-1]
    tiles = jnp.where(group_sizes == 0, 0,
                      (ends + tm - 1) // tm - starts // tm)
    group_ids = jnp.repeat(jnp.arange(g, dtype=jnp.int32), tiles,
                           total_repeat_length=tiles_m + g - 1)
    # every tile is visited once by the group that owns its first row, and
    # once more by each group that starts inside it
    inside = jnp.where((starts % tm == 0) | (group_sizes == 0), tiles_m,
                       starts // tm)
    visits = jnp.zeros((tiles_m + 1,), jnp.int32).at[inside].add(1)
    tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32),
                          visits[:tiles_m] + 1,
                          total_repeat_length=tiles_m + g - 1)
    return offsets, group_ids, tile_ids, jnp.sum(tiles)


def silu(y: jnp.ndarray) -> jnp.ndarray:
    return y / (1.0 + jnp.exp(-y))


def _expert_gmm_kernel(offsets, group_ids, tile_ids, lhs_ref, *refs,
                       tm: int):
    *rhs_refs, out_ref = refs
    i = pl.program_id(0)
    g = group_ids[i]
    rows = (tile_ids[i] * tm
            + jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0))
    mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
    x = lhs_ref[...]
    y = [jnp.dot(x, r[...], preferred_element_type=jnp.float32)
         for r in rhs_refs]
    y = y[0] if len(y) == 1 else silu(y[0]) * y[1]
    out_ref[...] = jnp.where(mine, y, out_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def expert_gmm_pallas(lhs: jnp.ndarray, rhs: jnp.ndarray,
                      group_sizes: jnp.ndarray, up: jnp.ndarray | None = None,
                      *, tm: int = 512,
                      interpret: bool = False) -> jnp.ndarray:
    """lhs [m, k] (m % tm == 0), rhs [G, k, n], group_sizes [G] int32 ->
    [m, n] in lhs's type; rows at or past the groups' total are left
    undefined. With ``up`` [G, k, n], a SwiGLU's first half:
    ``silu(lhs @ rhs) * (lhs @ up)``, both products on the same row tile
    and the gating in float32 before the one store."""
    m, k = lhs.shape
    g, kw, n = rhs.shape
    assert k == kw and group_sizes.shape == (g,), (lhs.shape, rhs.shape,
                                                   group_sizes.shape)
    assert up is None or up.shape == rhs.shape, (rhs.shape, up.shape)
    assert m % tm == 0, (m, tm)
    offsets, group_ids, tile_ids, num_tiles = group_metadata(
        group_sizes.astype(jnp.int32), m, tm)
    weights = (rhs,) if up is None else (rhs, up)
    weight_spec = pl.BlockSpec((None, k, n),
                               lambda i, off, gid, tid: (gid[i], 0, 0))
    return pl.pallas_call(
        functools.partial(_expert_gmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(num_tiles,),
            in_specs=[pl.BlockSpec((tm, k),
                                   lambda i, off, gid, tid: (tid[i], 0))]
            + [weight_spec] * len(weights),
            out_specs=pl.BlockSpec((tm, n),
                                   lambda i, off, gid, tid: (tid[i], 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="expert_gmm",                 # the device trace's op name
    )(offsets, group_ids, tile_ids, lhs, *weights)
