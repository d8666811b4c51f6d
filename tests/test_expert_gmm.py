"""The grouped expert product (``kernels/expert_gmm``): the Pallas kernel
body in interpret mode against the jnp oracle, for uneven groups, empty
groups and row counts that are not a multiple of the row tile, in float32
and bfloat16; and the group metadata that decides which tiles run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.expert_gmm.kernel import group_metadata
from repro.kernels.expert_gmm.ops import expert_gmm
from repro.kernels.expert_gmm.ref import expert_gmm_ref

CASES = [
    ([100, 0, 60, 37], 300),        # uneven, one empty, rows past the total
    ([0, 0, 5, 0], 40),             # one small group between empty ones
    ([64, 64, 64, 64], 256),        # tile-aligned, every row routed
    ([129, 1, 0, 126], 257),        # groups that share tiles; m % tile != 0
    ([0, 0, 0, 0], 32),             # nothing routed
]


@pytest.mark.parametrize("glu", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sizes,m", CASES)
def test_kernel_matches_oracle(sizes, m, dtype, glu):
    rng = np.random.default_rng(sum(sizes) + m)
    k, n = 128, 256
    lhs = jnp.asarray(rng.normal(size=(m, k)), dtype)
    rhs, up = (jnp.asarray(rng.normal(size=(len(sizes), k, n)) / np.sqrt(k),
                           dtype) for _ in range(2))
    up = up if glu else None
    gs = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(lambda a, b, c, u: expert_gmm(
        a, b, c, u, tm=128, force_pallas=True, interpret=True))(
            lhs, rhs, gs, up)
    want = expert_gmm_ref(lhs, rhs, gs, up)
    total = sum(sizes)
    assert got.shape == (m, n) and got.dtype == dtype
    # both accumulate in float32 over the same k; bf16 rounds the output
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(got[:total], np.float32),
                               np.asarray(want[:total], np.float32),
                               rtol=tol, atol=tol)
    # the oracle's rows past the total are zero (the kernel leaves them)
    assert not np.asarray(want[total:], np.float32).any()


def test_expert_rows_match_a_plain_product():
    """Row r of group g is lhs[r] @ rhs[g], and with ``up`` a SwiGLU's
    first half, as a loop over rows says."""
    rng = np.random.default_rng(7)
    sizes = [3, 0, 5]
    lhs = rng.normal(size=(10, 16)).astype(np.float32)
    rhs, up = (rng.normal(size=(3, 16, 8)).astype(np.float32)
               for _ in range(2))
    args = (jnp.asarray(lhs), jnp.asarray(rhs), jnp.asarray(sizes, jnp.int32))
    kw = dict(tm=16, force_pallas=True, interpret=True)
    got = np.asarray(expert_gmm(*args, **kw))
    glu = np.asarray(expert_gmm(*args, jnp.asarray(up), **kw))
    group = [0, 0, 0, 2, 2, 2, 2, 2]
    for r, g in enumerate(group):
        np.testing.assert_allclose(got[r], lhs[r] @ rhs[g], rtol=1e-5,
                                   atol=1e-5)
        gate = lhs[r] @ rhs[g]
        np.testing.assert_allclose(
            glu[r], gate / (1 + np.exp(-gate)) * (lhs[r] @ up[g]),
            rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("sizes,tm,tiles", [
    ([100, 0, 60, 37], 128, 4),     # tile 0 by groups 0, 2; tile 1 by 2, 3
    ([0, 0, 5, 0], 128, 1),
    ([0, 0, 0, 0], 128, 0),         # empty groups get no tile
    ([512, 512], 256, 4),
])
def test_group_metadata_visits_only_routed_tiles(sizes, tm, tiles):
    m = -(-max(sum(sizes), 1) // tm) * tm
    offsets, gids, tids, num = group_metadata(
        jnp.asarray(sizes, jnp.int32), m, tm)
    num = int(num)
    assert num == tiles
    assert list(np.asarray(offsets)) == list(np.cumsum([0] + sizes))
    # every step's tile overlaps its group's rows, and each routed row is
    # covered by exactly one step of its own group
    covered = np.zeros(m, int)
    for g, t in zip(np.asarray(gids)[:num], np.asarray(tids)[:num]):
        lo, hi = max(offsets[g], t * tm), min(offsets[g + 1], (t + 1) * tm)
        assert lo < hi
        covered[lo:hi] += 1
    assert (covered[:sum(sizes)] == 1).all() and not covered[sum(sizes):].any()
