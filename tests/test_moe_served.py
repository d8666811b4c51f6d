"""The served expert layer (``models/moe.moe_dropless``) and DeepSeek-V2's
rope, at a small size on the CPU.

* share sum: the routed parts four devices compute, each holding a
  quarter of the experts, with the shared experts counted once, add up to
  the uncut layer;
* row independence: a row's output does not move when the rows beside it
  in the dispatch change (capacity dropping would move it);
* the router weighs by the top-k probabilities themselves where the model
  does not renormalise them, times ``routed_scaling_factor``;
* the counter: the held experts' row counts are the assignments routed to
  them;
* the Pallas grouped product (interpret mode) serves the same layer as
  the oracle;
* YaRN: the published frequencies and attention scale, and configurations
  without ``rope_scaling`` rotate as before.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import YarnRope, get_config
from repro.kernels.expert_gmm.ops import expert_gmm
from repro.models import layers as L
from repro.models import moe as M

E, HELD = 8, 2


def _cfg(**over):
    base = get_config("deepseek-v2-lite-16b").reduced()
    return dataclasses.replace(base, num_experts=E, num_experts_per_tok=3,
                               num_shared_experts=1, moe_d_ff=32,
                               d_model=64, **over)


def _params(cfg, key=0):
    return M.moe_params(jax.random.PRNGKey(key), cfg, jnp.float32)


def _x(b=3, t=16, d=64, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(b, t, d)),
                       jnp.float32)


def _share(p, first, count):
    q = dict(p)
    for k in ("w_gate", "w_up", "w_down"):
        q[k] = p[k][first:first + count]
    return q


def _routed(cfg, p, x):
    """The layer's output without its shared experts."""
    q = {k: v for k, v in p.items() if k != "shared"}
    c = dataclasses.replace(cfg, num_shared_experts=0)
    return M.moe_dropless(c, q, x)[0]


def test_shares_add_up_to_the_uncut_layer():
    cfg = _cfg()
    p, x = _params(cfg), _x()
    whole, rows = M.moe_dropless(cfg, p, x)
    parts = []
    for first in range(0, E, HELD):
        c = dataclasses.replace(cfg, experts_held=HELD, first_expert=first)
        parts.append(_routed(c, _share(p, first, HELD), x))
    shared = L.swiglu(p["shared"], x.reshape(-1, x.shape[-1])).reshape(
        x.shape)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    assert int(rows.sum()) == x.shape[0] * x.shape[1] * 3


def test_a_share_adds_only_its_own_experts():
    cfg = _cfg(experts_held=HELD, first_expert=2)
    p = _params(dataclasses.replace(cfg, experts_held=0, first_expert=0))
    x = _x()
    part = _routed(cfg, _share(p, 2, HELD), x)
    # by hand: each token's routing weight on experts 2 and 3 only
    xf = x.reshape(-1, 64)
    _, top_p, top_e = M.route(cfg, p["router"], xf)
    want = np.zeros(xf.shape, np.float32)
    for e in (2, 3):
        w = np.asarray(jnp.sum(jnp.where(top_e == e, top_p, 0.0), -1))
        y = L.swiglu({k: {"w": p[k][e]} for k in ("w_gate", "w_up",
                                                    "w_down")}, xf)
        want += w[:, None] * np.asarray(y)
    np.testing.assert_allclose(np.asarray(part).reshape(want.shape), want,
                               rtol=1e-5, atol=1e-5)


def test_a_row_does_not_depend_on_its_batch_companions():
    cfg = _cfg(experts_held=HELD)
    p = _params(cfg)
    x = _x(b=4)
    other = x.at[1:].set(_x(b=3, seed=5))     # row 0 kept, the rest new
    y0 = M.moe_dropless(cfg, p, x)[0][0]
    y1 = M.moe_dropless(cfg, p, other)[0][0]
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))


def test_capacity_dropping_would_move_a_row():
    """What the row-independence test guards against: the training path
    at a tight capacity gives the last row another output once the rows
    before it crowd its experts."""
    cfg = _cfg()
    p = _params(cfg)
    x = _x(b=4)
    crowd = x.at[:3].set(jnp.broadcast_to(x[3:], (3,) + x.shape[1:]))
    y0 = M.moe_forward(cfg, p, x, capacity_factor=0.5)[0][3]
    y1 = M.moe_forward(cfg, p, crowd, capacity_factor=0.5)[0][3]
    assert not np.allclose(np.asarray(y0), np.asarray(y1))


@pytest.mark.parametrize("norm,scale", [(False, 1.0), (True, 1.0),
                                        (False, 2.5)])
def test_router_weights(norm, scale):
    cfg = _cfg(norm_topk_prob=norm, routed_scaling_factor=scale)
    p = _params(cfg)
    xf = _x().reshape(-1, 64)
    probs, top_p, top_e = M.route(cfg, p["router"], xf)
    assert probs.shape == (xf.shape[0], E)
    raw = jnp.take_along_axis(probs, top_e, -1)
    want = raw / raw.sum(-1, keepdims=True) if norm else raw
    np.testing.assert_allclose(np.asarray(top_p), np.asarray(want * scale),
                               rtol=1e-6)


def test_registry_routers():
    assert get_config("qwen3-moe-235b-a22b").norm_topk_prob is True
    v2 = get_config("deepseek-v2-lite-16b")
    assert v2.norm_topk_prob is False and v2.routed_scaling_factor == 1.0
    assert v2.norm_eps == 1e-6 and v2.rope_scaling.factor == 40


def test_held_row_counts_are_the_routed_assignments():
    cfg = _cfg(experts_held=HELD, first_expert=4)
    p = _params(dataclasses.replace(cfg, experts_held=0, first_expert=0))
    x = _x()
    _, rows = M.moe_dropless(cfg, _share(p, 4, HELD), x)
    _, _, top_e = M.route(cfg, p["router"], x.reshape(-1, 64))
    want = [int((np.asarray(top_e) == e).sum()) for e in (4, 5)]
    assert list(np.asarray(rows)) == want


def test_the_pallas_product_serves_the_same_layer(monkeypatch):
    cfg = _cfg(experts_held=4)
    p, x = _params(cfg), _x()
    want, rows = M.moe_dropless(cfg, p, x)
    monkeypatch.setattr(M, "expert_gmm", functools.partial(
        expert_gmm, tm=16, force_pallas=True, interpret=True))
    got, got_rows = M.moe_dropless(cfg, p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert list(np.asarray(got_rows)) == list(np.asarray(rows))


def test_the_capacity_path_refuses_a_share():
    cfg = _cfg(experts_held=HELD)
    with pytest.raises(ValueError, match="every expert"):
        M.moe_forward(cfg, _params(cfg), _x())


YARN = YarnRope(factor=40.0, original_max_position_embeddings=4096,
                beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                mscale_all_dim=0.707)


def test_yarn_frequencies_of_deepseek_v2_lite():
    """Rope dims 0-10 of 32 keep their frequency, 23-31 are divided by
    the factor 40, and 11-22 ramp linearly between the two."""
    plain = np.asarray(L.rope_freqs(64, 10000.0))
    ratio = plain / L.yarn_freqs(64, 10000.0, YARN)
    np.testing.assert_allclose(ratio[:11], 1.0, rtol=1e-6)
    np.testing.assert_allclose(ratio[23:], 40.0, rtol=1e-5)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(1 / ratio[11:23], 1 - ramp + ramp / 40,
                               rtol=1e-5)


def test_yarn_attention_scale():
    mscale = 0.1 * 0.707 * np.log(40) + 1
    assert L.yarn_softmax_scale(192, YARN) == pytest.approx(
        192 ** -0.5 * mscale ** 2)
    assert mscale ** 2 == pytest.approx(1.5896, abs=1e-4)
    assert L.yarn_softmax_scale(192, None) == pytest.approx(192 ** -0.5)


def test_rope_without_scaling_is_unchanged():
    x = _x(b=1, t=8, d=32).reshape(1, 8, 2, 16)
    pos = jnp.arange(8)
    got = L.apply_rope(x, pos, 10000.0)
    ang = np.arange(8)[:, None] * np.asarray(L.rope_freqs(16, 10000.0))
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    x1, x2 = np.asarray(x)[..., :8], np.asarray(x)[..., 8:]
    want = np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    # under YaRN at mscale == mscale_all_dim the cos/sin scale is 1
    scaled = L.apply_rope(x, pos, 10000.0, YARN)
    assert np.allclose(np.linalg.norm(np.asarray(scaled), axis=-1),
                       np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
