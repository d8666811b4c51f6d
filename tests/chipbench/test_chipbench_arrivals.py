"""Open-loop schedules and request plans: fixed by the seed, the same
work for every seed."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import arrivals, spec, traffic  # noqa: E402

MIXES = ["steady", "burst", "escalate"]
BIG = 2 ** 31 + 977


def _mix(name):
    return spec.load_json(spec.HERE / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = arrivals.schedule(_mix(name)["arrivals"], 14.0, 30.0, BIG)
    b = arrivals.schedule(_mix(name)["arrivals"], 14.0, 30.0, BIG)
    assert np.array_equal(a, b)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_reorder_the_same_gaps(name):
    a = arrivals.schedule(_mix(name)["arrivals"], 14.0, 30.0, BIG)
    b = arrivals.schedule(_mix(name)["arrivals"], 14.0, 30.0, BIG + 1)
    assert not np.array_equal(a, b)
    assert len(a) == len(b)
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(b, prepend=0)))
    assert a[-1] == pytest.approx(b[-1]) and a[-1] < 30.0
    assert np.all(np.diff(a) >= 0) and a[0] > 0


def test_poisson_rate_is_kept():
    a = arrivals.schedule({"pattern": "poisson", "shape_seed": 1}, 40.0,
                          100.0, 5)
    assert len(a) / 100.0 == pytest.approx(40.0, rel=0.05)


def test_unknown_pattern_refused():
    with pytest.raises(ValueError):
        arrivals.arrival_times(np.random.default_rng(0), "diurnal", 1.0, 1.0)


@pytest.mark.parametrize("name,share", [("steady", 0.0), ("escalate", 0.5)])
def test_plan_repeats_exactly_its_share(name, share):
    p = traffic.plan(_mix(name), 40.0, 1000, 16, 32, 20.0, BIG)
    n = len(p)
    distinct = len(np.unique(p.content))
    assert n - distinct == round(share * n)
    assert p.tokens.shape == (distinct, 16)
    assert p.warm.shape == (32, 16)
    assert all(p.content[i] <= i for i in range(n))
    # warm-up content is none of the traffic's
    assert not any((p.warm[:, None] == p.tokens[None]).all(-1).any(-1))


def test_plan_tokens_follow_the_seed():
    m = _mix("steady")
    a = traffic.plan(m, 40.0, 1000, 16, 32, 5.0, 1)
    b = traffic.plan(m, 40.0, 1000, 16, 32, 5.0, 1)
    c = traffic.plan(m, 40.0, 1000, 16, 32, 5.0, 2)
    assert np.array_equal(a.tokens, b.tokens)
    assert a.tokens.shape == c.tokens.shape
    assert not np.array_equal(a.tokens, c.tokens)
