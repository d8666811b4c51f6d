"""Production mesh builders (assignment §Multi-pod dry-run).

Functions, not module-level constants, so importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    """Every mesh axis in Auto mode: the SPMD partitioner places
    activations, steered by the sharding hints (models/shard_hints.py)."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _auto(len(axes)))


def batch_axes(mesh) -> tuple[str, ...]:
    """Axes the global batch is sharded over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def make_host_mesh():
    """Single-device mesh with the same axis names (CPU tests/examples)."""
    return jax.make_mesh((1, 1), ("data", "model"), _auto(2))


def make_serving_mesh():
    """Data-parallel mesh over every local device (DESIGN.md §12).

    The serving engine shards the *batch* axis of the local forward over
    all addressable devices and keeps parameters replicated — the right
    first shape for cascade replicas, where throughput scales with rows
    and the local model is small by construction. On a single-device
    host this degenerates to ``make_host_mesh`` and the sharded forward
    is numerically identical to the unsharded one.
    """
    n = jax.local_device_count()
    return jax.make_mesh((n, 1), ("data", "model"), _auto(2))
