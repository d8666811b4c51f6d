"""Best-effort activation-sharding hints.

`constrain(x, *axes)` applies jax.lax.with_sharding_constraint using only
the mesh axes that (a) exist in the ambient abstract mesh and (b) divide
the corresponding dim — so model code can pin the sharding the SPMD
partitioner should pick on the production mesh while remaining a no-op in
CPU tests and single-device runs.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def constrain(x, *axes):
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    spec = []
    for want, dim in zip(axes, x.shape):
        ok = (want is not None and want in mesh.axis_names
              and dim % mesh.shape[want] == 0)
        spec.append(want if ok else None)
    if not any(spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))
