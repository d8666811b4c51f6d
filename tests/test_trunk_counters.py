"""Device counters of a counted local tier (``FusedLocalHead(counted=
True)``): they ride to the host with the gated step's outputs, in the
early-emit callback or the gate fetch, and land once per window on the
engine's observability trace beside the per-request spans. An uncounted
head compiles the step it did before: no extra output, no extra
callback argument."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_head_gate.ops import FusedLocalHead
from repro.runtime import RemoteTransport, TransportConfig
from repro.runtime.observability import Observability
from repro.serving.engine import CascadeEngine, make_gated_local_step
from repro.serving.scheduler import MicrobatchScheduler, Request

BATCH, D, C = 8, 4, 16
W = jnp.asarray(np.random.default_rng(0).normal(size=(D, C)), jnp.float32)


def positives(x):
    return jnp.sum(x > 0, axis=0).astype(jnp.int32)        # [D]


def head(counted: bool) -> FusedLocalHead:
    def trunk(x):
        return (x, {"demo.positives": positives(x)}) if counted else x
    return FusedLocalHead(trunk, W, None, counted=counted)


def rows(n, seed=0):
    return np.random.default_rng(seed).normal(size=(n, D)).astype(
        np.float32)


def build(emit: bool, obs):
    transport = RemoteTransport(lambda x: 5.0 * np.asarray(x)[:, :1]
                                * np.ones((1, C), np.float32),
                                TransportConfig(retry_backoff_s=0.0,
                                                max_retries=0))
    engine = CascadeEngine(head(True), batch_size=BATCH,
                           remote_fraction_budget=0.25, t_remote=0.0,
                           transport=transport, early_emit=emit,
                           observability=obs)
    sched = MicrobatchScheduler(engine, fallback=lambda r: -1,
                                pipeline_depth=1,
                                completion_mode="streaming",
                                batching="continuous")
    return sched, engine


@pytest.mark.parametrize("emit", [False, True])
def test_counters_land_once_a_window_on_the_trace(emit):
    obs = Observability.enabled()
    sched, engine = build(emit, obs)
    xs = rows(3 * BATCH)
    t0 = time.perf_counter()
    for i, row in enumerate(xs):
        sched.submit(Request(uid=i, local_input=row, remote_input=row))
    out = sched.flush()
    engine.close()
    assert len(out) == 3 * BATCH
    recs = [s for s in obs.trace.spans() if "counter" in s]
    assert sorted(r["window"] for r in recs) == [1, 2, 3]
    for r in recs:
        assert r["counter"] == "demo.positives" and r["stages"] == []
        assert t0 <= r["t"] <= time.perf_counter()
    # each window's count is of its own rows (windows are full cohorts in
    # submission order)
    by_window = {r["window"]: r["value"] for r in recs}
    for w in (1, 2, 3):
        want = np.asarray(positives(xs[(w - 1) * BATCH:w * BATCH]))
        assert by_window[w] == want.tolist()
    # the per-request spans still read as before
    assert sum("uid" in s for s in obs.trace.spans()) == 3 * BATCH


def test_counters_without_observability_cost_nothing():
    sched, engine = build(True, None)
    for i, row in enumerate(rows(BATCH)):
        sched.submit(Request(uid=i, local_input=row, remote_input=row))
    assert len(sched.flush()) == BATCH
    engine.close()


@pytest.mark.parametrize("emit", [False, True])
def test_only_a_counted_head_adds_outputs(emit):
    args = (rows(BATCH), np.float32(np.inf), np.int32(BATCH))
    args += (np.int32(1),) if emit else ()
    seen = []
    for counted in (False, True):
        step = jax.jit(make_gated_local_step(
            head(counted), emit=(lambda *a: seen.append(len(a)))
            if emit else None))
        out = jax.block_until_ready(step(*args))
        keys = {"conf", "pred", "idx"} | ({"counters"} if counted else set())
        assert set(out) == keys
    if emit:
        assert seen == [4, 5]          # seq, conf, pred, idx (+ counters)


def test_calling_a_counted_head_gives_logits():
    x = jnp.asarray(rows(BATCH))
    np.testing.assert_allclose(np.asarray(head(True)(x)),
                               np.asarray(head(False)(x)))
    assert head(True).split(x)[1]["demo.positives"].shape == (D,)
