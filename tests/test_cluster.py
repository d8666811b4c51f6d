"""Cluster runtime (DESIGN.md §12, ISSUE 9): the single-fill shared
response cache never fetches one content key remotely twice (concurrent
same-key misses block on the owner's fill and inherit its backend
attribution), adversarial replica merge-order permutations leave the
reconciled budget state and fleet billing bitwise identical, a replica
blackout degrades that replica to its base budget without silently
dropping it, and a full two-replica ``ClusterHarness`` run replays bit
for bit on a virtual clock."""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import (ClusterBudgetConfig, ClusterBudgetController,
                           ClusterHarness, RemoteBackend, RemoteRouter,
                           SharedResponseCache, TransportConfig,
                           VirtualClock, cluster_billing)
from repro.runtime.controller import AdaptiveController, ControllerConfig
from repro.serving import ServeConfig
from repro.serving.engine import BILLING_FIELDS
from repro.serving.scheduler import Request


def local_apply(x):
    return x + 0.3 * jnp.sin(17.0 * x)


def remote_fn(x):
    return 5.0 * np.asarray(x)


def make_stream(rng, n, c=4, hard_frac=0.5):
    labels = rng.integers(0, c, n)
    x = rng.normal(0, 0.05, (n, c))
    margin = np.where(rng.random(n) < hard_frac, 0.1, 3.0)
    x[np.arange(n), labels] += margin
    return np.float32(x), labels


def fresh_controller(*, window=32, target=0.25) -> AdaptiveController:
    return AdaptiveController(ControllerConfig(
        target_remote_fraction=target, window=window,
        drift_threshold=10.0, history=4096))


def feed(ctrl: AdaptiveController, scores) -> AdaptiveController:
    """Push ``scores`` through the controller's rolling buffer. Traffic
    must land AFTER ``register()`` — the reconciler weighs replicas by
    the eligible-request delta since the last reconcile (or since
    registration), so pre-registration traffic reads as blackout."""
    scores = np.asarray(scores, np.float64)
    ctrl.observe(scores, escalated=int((scores < 0.5).sum()),
                 requests=scores.size)
    return ctrl


# ----------------------------------------------------- shared cache

def test_shared_cache_single_fill_and_attribution():
    sc = SharedResponseCache(capacity=8)
    a, b = sc.view("a"), sc.view("b")
    val = np.arange(4.0)
    key = sc.key_fn(val)
    # first miss claims; the owner's own re-lookup misses again (dupe
    # rows inside one window), it does NOT deadlock on its own claim
    assert a.lookup(key) is None and a.lookup(key) is None
    a.put(key, val, source="primary")
    hit = b.lookup(key)
    assert hit is not None
    np.testing.assert_array_equal(hit[0], val)
    assert hit[1] == "primary"              # filler's attribution
    assert b.stats.cross_hits == 1 and a.stats.cross_hits == 0
    assert sc.stats.fills == 1 and sc.stats.duplicate_fills == 0
    # a duplicate fill is discarded: first value keeps being served
    b.put(key, val * 10, source="secondary")
    assert sc.stats.duplicate_fills == 1
    np.testing.assert_array_equal(a.lookup(key)[0], val)


def test_shared_cache_concurrent_misses_one_owner():
    sc = SharedResponseCache(capacity=8, wait_s=10.0)
    owner = sc.view("owner")
    val = np.float32([1.0, 2.0])
    key = sc.key_fn(val)
    assert owner.lookup(key) is None        # claim taken
    results = {}

    def peer(name):
        results[name] = sc.view(name).lookup(key)

    threads = [threading.Thread(target=peer, args=(f"p{i}",))
               for i in range(3)]
    for t in threads:
        t.start()
    # peers are parked on the condition variable until the fill lands
    owner.put(key, val, source="primary")
    for t in threads:
        t.join(timeout=10.0)
        assert not t.is_alive()
    for name in ("p0", "p1", "p2"):
        got = results[name]
        np.testing.assert_array_equal(got[0], val)
        assert got[1] == "primary"
    assert sc.stats.fills == 1              # exactly one remote fetch
    assert sc.stats.duplicate_fills == 0
    assert sc.stats.waits >= 3
    assert sum(sc.view(f"p{i}").stats.cross_hits for i in range(3)) == 3


def test_shared_cache_release_unfilled_hands_claim_over():
    sc = SharedResponseCache(capacity=8, wait_s=10.0)
    val = np.float32([3.0])
    key = sc.key_fn(val)
    assert sc.view("dead").lookup(key) is None      # claim, then die
    got = {}

    def peer():
        got["hit"] = sc.view("heir").lookup(key)

    t = threading.Thread(target=peer)
    t.start()
    while sc.stats.waits == 0:              # peer reached the wait
        pass
    assert sc.release_unfilled("dead") == 1
    t.join(timeout=10.0)
    assert got["hit"] is None               # heir now owns the claim
    sc.view("heir").put(key, val, source="s")
    assert sc.stats.fills == 1 and sc.stats.releases == 1


def test_shared_cache_materialize_is_permutation_invariant():
    sc = SharedResponseCache(capacity=32)
    for i in range(6):
        v = np.float32([i, i + 1])
        k = sc.key_fn(v)
        assert sc.view(f"r{i % 2}").lookup(k) is None
        sc.view(f"r{i % 2}").put(k, v, source=f"b{i % 3}")
    feed = list(sc.feed)
    base = SharedResponseCache.materialize(feed)
    rng = np.random.default_rng(0)
    for _ in range(5):
        perm = [feed[j] for j in rng.permutation(len(feed))]
        assert SharedResponseCache.materialize(perm) == base


# ------------------------------------------------- budget reconcile

def test_reconcile_pooled_holds_global_budget_under_skew():
    rng = np.random.default_rng(1)
    # r0 sees hard traffic (low scores), r1 easy — same volume
    hard = rng.uniform(0.0, 0.5, 200)
    easy = rng.uniform(0.5, 1.0, 200)
    cl = ClusterBudgetController(ClusterBudgetConfig(
        target_remote_fraction=0.25, min_pooled_scores=64))
    r0, r1 = fresh_controller(), fresh_controller()
    cl.register("r0", r0)
    cl.register("r1", r1)
    feed(r0, hard)
    feed(r1, easy)
    st = cl.reconcile(now=1.0)
    assert st.mode == "pooled" and st.tau is not None
    # skewed targets: hard replica far above target, easy far below
    assert st.targets["r0"] > 0.4 and st.targets["r1"] < 0.1
    # traffic-weighted mean of pushed targets == global target, up to
    # the per-replica target floor the easy replica clips to (0.02)
    mean = (st.targets["r0"] * 200 + st.targets["r1"] * 200) / 400
    assert mean == pytest.approx(0.25, abs=0.021)
    # targets were pushed down into the per-replica controllers
    assert (cl._replicas["r0"].config.target_remote_fraction
            == st.targets["r0"])
    # shed rule: squeezed replica sheds earlier, spender gets headroom
    assert cl.admission_scale("r1") < 1.0 < cl.admission_scale("r0")
    assert 0.25 <= cl.admission_scale("r1") <= 4.0


def test_reconcile_blackout_replica_degrades_to_base_budget():
    rng = np.random.default_rng(2)
    cl = ClusterBudgetController(ClusterBudgetConfig(
        target_remote_fraction=0.3, min_pooled_scores=64))
    up0, up1 = (fresh_controller(target=0.3) for _ in range(2))
    dead = fresh_controller(target=0.3)
    cl.register("up0", up0)
    cl.register("up1", up1)
    cl.register("dead", dead)               # never observes traffic
    feed(up0, rng.uniform(0, 1, 150))
    feed(up1, rng.uniform(0, 1, 150))
    st = cl.reconcile(now=1.0)
    assert st.mode == "pooled"
    assert st.stale == ("dead",)
    # the blackout replica is excluded from the pool but NOT dropped:
    # it is reset to the base per-replica budget
    assert st.targets["dead"] == 0.3
    assert dead.config.target_remote_fraction == 0.3
    # fewer than two live replicas -> everyone degrades to base
    cl2 = ClusterBudgetController(ClusterBudgetConfig(
        target_remote_fraction=0.3))
    solo = fresh_controller(target=0.3)
    cl2.register("solo", solo)
    feed(solo, rng.uniform(0, 1, 150))
    st2 = cl2.reconcile(now=1.0)
    assert st2.mode == "degraded" and st2.targets["solo"] == 0.3


def test_reconcile_is_registration_order_invariant():
    rng = np.random.default_rng(3)
    pools = {f"r{i}": rng.uniform(0, 1, 100 + 40 * i) for i in range(4)}
    states = []
    for order in ([0, 1, 2, 3], [3, 1, 0, 2], [2, 3, 1, 0]):
        cl = ClusterBudgetController(ClusterBudgetConfig(
            target_remote_fraction=0.25, min_pooled_scores=64))
        ctrls = {}
        for i in order:
            ctrls[i] = fresh_controller()
            cl.register(f"r{i}", ctrls[i])
        for i in order:
            feed(ctrls[i], pools[f"r{i}"])
        states.append(cl.reconcile(now=1.0))
    for st in states[1:]:
        assert st.mode == states[0].mode
        assert st.tau == states[0].tau                  # bitwise
        assert st.targets == states[0].targets          # bitwise
        assert st.global_ema_fraction == states[0].global_ema_fraction


def test_cluster_billing_is_merge_order_invariant():
    class U:
        def __init__(self, c):
            self.remote_calls, self.cache_hits = c, c + 1
            self.transport_failures, self.cost = c % 2, 0.1 * c + 0.007
            self.remote_latency_s = 0.003 * c

    class St:
        def __init__(self, c):
            for i, f in enumerate(BILLING_FIELDS):
                setattr(self, f, c + 0.1 * i if f == "total_cost" else
                        c + i)
            self.per_backend = {"a": U(c), "b": U(c + 3)}

    stats = {f"r{i}": St(i) for i in range(5)}
    base = cluster_billing(stats)
    for order in ([4, 2, 0, 3, 1], [1, 0, 4, 2, 3]):
        shuffled = {f"r{i}": stats[f"r{i}"] for i in order}
        assert cluster_billing(shuffled) == base        # bitwise


# --------------------------------------------------------- harness

def make_router(clock):
    tconf = TransportConfig(max_in_flight=16, max_retries=0,
                            retry_backoff_s=0.0, timeout_s=10.0,
                            breaker_failures=10**6)
    return RemoteRouter(
        [RemoteBackend("primary", remote_fn, tconf,
                       cost_per_request=0.002, latency_s=0.01,
                       clock=clock, sleep=clock.sleep)])


def drive_harness(seed=0, replicas=2, n=96):
    clock = VirtualClock()
    cfg = ServeConfig(batch_size=8, remote_fraction_budget=0.25,
                      t_remote=0.0, pipeline_depth=1, cache_size=256,
                      adaptive=True, control_window=16,
                      replicas=replicas, observability=True)
    h = ClusterHarness(cfg, local_apply, transport=make_router(clock),
                       fallback=lambda r: -1, clock=clock, seed=seed,
                       reconcile_interval_s=0.5)
    rng = np.random.default_rng(7)
    xs, labels = make_stream(rng, n)
    proto = xs[rng.integers(0, 24, n)]      # repeats -> cache traffic
    responses = []
    for i in range(n):
        clock.advance_to(0.05 * i)
        h.submit(h.names[i % replicas],
                 Request(uid=i, local_input=proto[i],
                         remote_input=proto[i]))
        if (i + 1) % (8 * replicas) == 0:
            for batch in h.flush().values():
                responses.extend(batch)
    for batch in h.flush().values():
        responses.extend(batch)
    digest = {
        "responses": [(r.uid, int(r.prediction), r.source,
                       r.disposition, r.backend, round(r.cost, 12))
                      for r in sorted(responses, key=lambda r: r.uid)],
        "billing": h.global_billing(),
        "feed": [(u.key.hex(), u.source, u.replica)
                 for u in h.shared_cache.feed],
        "reconciles": h.cluster.state.reconciles,
        "targets": dict(h.cluster.state.targets),
        "events": dict(sorted(h.events.counts().items())),
        "cross_hits": {name: h.replica(name).cache.stats.cross_hits
                       for name in h.names},
    }
    h.close()
    return h, digest, n


def test_harness_double_run_is_bit_identical():
    h1, d1, n = drive_harness(seed=3)
    h2, d2, _ = drive_harness(seed=3)
    assert d1 == d2
    # zero silent drops: every uid answered exactly once across the fleet
    uids = [r[0] for r in d1["responses"]]
    assert sorted(uids) == list(range(n))
    # single-fill: no content key fetched remotely twice
    assert h1.shared_cache.stats.duplicate_fills == 0
    keys = [k for k, _, _ in d1["feed"]]
    assert len(keys) == len(set(keys))
    # the prototype stream actually exercised cross-replica sharing
    assert sum(d1["cross_hits"].values()) > 0
    assert d1["reconciles"] > 0
    assert "cluster_reconcile" in d1["events"]
    # billing reconciles with the shared store: every billed remote row
    # produced a put — a first fill, or a same-window duplicate row that
    # rode the fill's own remote call (redundant put, not a re-fetch)
    scs = h1.shared_cache.stats
    b = d1["billing"]["billing"]
    assert b["remote_calls"] == scs.fills + scs.redundant_puts
    assert b["requests"] == n


def test_harness_admission_share_scales_soft_watermark():
    clock = VirtualClock()
    cfg = ServeConfig(batch_size=8, remote_fraction_budget=0.25,
                      t_remote=0.0, pipeline_depth=1, cache_size=0,
                      adaptive=True, control_window=16, replicas=2,
                      admission_limit=40, admission_soft_ratio=0.5,
                      observability=True)
    h = ClusterHarness(cfg, local_apply, transport=make_router(clock),
                       fallback=lambda r: -1, clock=clock)
    sched = h.replica("r0").scheduler
    assert sched._soft_watermark() == sched.admission_soft  # share 1.0
    h.cluster.state.global_target = 0.25
    h.cluster.state.targets = {"r0": 0.125, "r1": 0.375}
    assert sched._soft_watermark() == 10         # squeezed: sheds early
    h.cluster.state.targets = {"r0": 10.0, "r1": 0.375}
    # headroom is capped below the hard limit (hard bound still owns)
    assert sched._soft_watermark() == cfg.admission_limit - 1
    h.close()


def test_serveconfig_cluster_validation():
    with pytest.raises(ValueError, match="adaptive"):
        ServeConfig(replicas=2)
    with pytest.raises(ValueError, match="fused"):
        ServeConfig(fused=True, replicas=2, adaptive=True)
    with pytest.raises(ValueError, match="fused"):
        ServeConfig(fused=True, data_parallel=True)
    cfg = ServeConfig(replicas=3, adaptive=True)
    assert cfg.replicas == 3


def test_data_parallel_shard_is_numeric_noop():
    """The data-parallel gated step (shard_map over the serving mesh,
    selection over the gathered confidences) returns the one-device
    triple bit for bit."""
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.engine import make_gated_local_step
    mesh = make_serving_mesh()
    w = jnp.linspace(-1, 1, 4 * 8).reshape(4, 8)

    def local_apply(x):
        return jnp.tanh(x) @ w * 3.0

    x = jnp.linspace(-1, 1, 32).reshape(8, 4)
    t, n = np.float32(0.6), np.int32(7)
    want = jax.jit(make_gated_local_step(local_apply))(x, t, n)
    got = jax.jit(make_gated_local_step(local_apply, mesh=mesh))(x, t, n)
    for key in ("conf", "pred", "idx"):
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]), key)
    assert (np.asarray(want["idx"]) >= 0).any()
