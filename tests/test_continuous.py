"""Continuous batching (DESIGN.md §11, ISSUE 8): the slot-map serve
loop must keep responses, billing and controller state bitwise-identical
to the fixed-window streaming drain — under adversarial completion
orders, seeded chaos and a live controller — while handing trusted-local
rows back at gate time (no window-drain quantization, no starvation
behind a stuck escalation). Plus the slot-occupancy queue-wait estimate,
the ``/metrics`` scrape endpoint and the ``ServeConfig`` plumbing."""

from __future__ import annotations

import json
import logging
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import (AdaptiveController, ChaosEpisode, ChaosSchedule,
                           ControllerConfig, RemoteTransport,
                           TransportConfig)
from repro.runtime.observability import MetricsRegistry, MetricsServer
from repro.serving.engine import BILLING_FIELDS, CascadeEngine
from repro.serving.policy import ServeConfig
from repro.serving.scheduler import MicrobatchScheduler, Request


def local_apply(x):
    return x + 0.3 * jnp.sin(17.0 * x)


def remote_apply(x):
    return 5.0 * np.asarray(x)


def make_stream(rng, n, c=4, hard_frac=0.5):
    labels = rng.integers(0, c, n)
    x = rng.normal(0, 0.05, (n, c))
    margin = np.where(rng.random(n) < hard_frac, 0.1, 3.0)
    x[np.arange(n), labels] += margin
    return np.float32(x), labels


def quiet_tconf(**kw):
    base = dict(retry_backoff_s=0.0, max_retries=0, breaker_failures=10**6,
                timeout_s=60.0)
    base.update(kw)
    return TransportConfig(**base)


def build(remote=remote_apply, *, batch=8, budget=0.5, depth=4,
          batching="continuous", controller=None, tconf=None,
          transport=None):
    if transport is None:
        transport = RemoteTransport(remote, tconf or quiet_tconf())
    engine = CascadeEngine(local_apply, batch_size=batch,
                           remote_fraction_budget=budget, t_remote=0.0,
                           transport=transport, controller=controller)
    sched = MicrobatchScheduler(engine, fallback=lambda r: -7,
                                pipeline_depth=depth,
                                completion_mode="streaming",
                                batching=batching)
    return sched, engine


def serve_all(sched, xs):
    for i, row in enumerate(xs):
        sched.submit(Request(uid=i, local_input=row, remote_input=row))
    return sched.flush()


def by_uid(responses):
    return {r.uid: (r.prediction, r.source) for r in responses}


def assert_same_accounting(e_a, e_b):
    for f in BILLING_FIELDS:
        assert getattr(e_a.stats, f) == getattr(e_b.stats, f), f
    assert e_a.stats.per_backend == e_b.stats.per_backend


# ------------------------------------------------------ mode plumbing

def test_unknown_batching_rejected():
    _, engine = build()
    with pytest.raises(ValueError, match="batching"):
        MicrobatchScheduler(engine, batching="quantum")
    engine.close()


def test_continuous_requires_streaming_completion():
    _, engine = build()
    with pytest.raises(ValueError, match="streaming"):
        MicrobatchScheduler(engine, completion_mode="fifo",
                            batching="continuous")
    engine.close()


def test_continuous_requires_runtime_path():
    engine = CascadeEngine(local_apply, remote_apply, batch_size=8,
                           remote_fraction_budget=0.5, t_remote=0.0)
    with pytest.raises(ValueError, match="runtime"):
        MicrobatchScheduler(engine, completion_mode="streaming",
                            batching="continuous")


def test_serveconfig_batching_validation():
    with pytest.raises(ValueError, match="batching"):
        ServeConfig(batch_size=8, batching="quantum")
    with pytest.raises(ValueError, match="streaming"):
        ServeConfig(batch_size=8, batching="continuous",
                    completion_mode="fifo")
    with pytest.raises(ValueError, match="fused"):
        ServeConfig(batch_size=8, fused=True, batching="continuous",
                    completion_mode="streaming")
    cfg = ServeConfig(batch_size=8, batching="continuous",
                      completion_mode="streaming")
    assert cfg.batching == "continuous"


# ------------------------------------- continuous == window identity

def test_continuous_matches_window_static_thresholds():
    """Slot-map admission + early emit must never change what the
    cascade answers or charges: same stream, same cohorts, bitwise-
    identical responses and billing vs the fixed-window drain."""
    rng = np.random.default_rng(1)
    xs, _ = make_stream(rng, 64)

    s_win, e_win = build(batching="window")
    s_con, e_con = build(batching="continuous")
    r_win = serve_all(s_win, xs)
    r_con = serve_all(s_con, xs)
    assert sorted(r.uid for r in r_con) == list(range(64))
    assert by_uid(r_win) == by_uid(r_con)
    assert_same_accounting(e_win, e_con)
    e_win.close()
    e_con.close()


def test_continuous_matches_window_adversarial_completion_order():
    """Early windows complete LAST: later cohorts' escalations resolve
    and hand back first, slots churn out of submission order — answers
    and billing must still match the window drain bit for bit."""
    rng = np.random.default_rng(2)
    xs, _ = make_stream(rng, 64)

    def make_reordering():
        calls = {"n": 0}
        lock = threading.Lock()

        def reordering_remote(x):
            with lock:
                calls["n"] += 1
                i = calls["n"]
            time.sleep(0.03 * max(0, 4 - i))    # first windows slowest
            return remote_apply(x)
        return reordering_remote

    s_win, e_win = build(make_reordering(), batching="window")
    s_con, e_con = build(make_reordering(), batching="continuous")
    r_win = serve_all(s_win, xs)
    r_con = serve_all(s_con, xs)
    assert by_uid(r_win) == by_uid(r_con)
    assert_same_accounting(e_win, e_con)
    e_win.close()
    e_con.close()


def test_continuous_with_live_controller_matches_window():
    """A live controller couples acceptance thresholds to commit order.
    The continuous loop keeps the depth-window admission bound in
    controller mode, so the begin/commit interleaving — and hence every
    threshold snapshot — reproduces the window drain exactly."""
    rng = np.random.default_rng(3)
    xs, _ = make_stream(rng, 96)

    def make(batching):
        ctl = AdaptiveController(ControllerConfig(
            target_remote_fraction=0.3, window=32))
        return build(batching=batching, controller=ctl)

    s_win, e_win = make("window")
    s_con, e_con = make("continuous")
    r_win = serve_all(s_win, xs)
    r_con = serve_all(s_con, xs)
    assert by_uid(r_win) == by_uid(r_con)
    assert_same_accounting(e_win, e_con)
    assert e_win.controller.state == e_con.controller.state
    e_win.close()
    e_con.close()


def test_continuous_matches_window_under_seeded_chaos():
    """A seeded brownout faults windows by call COUNT; with a single
    transport worker the count order is the submission order in both
    modes, so the same cohorts fault the same way — REJECTED/fallback
    rows and billing must stay identical."""
    rng = np.random.default_rng(4)
    xs, _ = make_stream(rng, 64, hard_frac=0.8)

    def run(batching):
        t = RemoteTransport(remote_apply,
                            quiet_tconf(max_concurrent=1))
        ChaosSchedule([ChaosEpisode("brownout", 0.0, 1e12, rate=0.5,
                                    name="b")],
                      seed=9).wrap_transport(t, "only")
        sched, engine = build(batching=batching, transport=t)
        resp = serve_all(sched, xs)
        engine.close()
        return resp, engine

    r_win, e_win = run("window")
    r_con, e_con = run("continuous")
    assert by_uid(r_win) == by_uid(r_con)
    assert_same_accounting(e_win, e_con)
    assert e_win.stats.transport_failures > 0       # chaos actually bit
    assert {r.source for r in r_win} >= {"local", "fallback"}


def test_forced_early_emit_matches_window_and_sweeps():
    """early_emit=True forces the in-kernel io_callback path even on
    CPU (from_config arms it via "auto" only where dispatch overlaps —
    TPU). The callback-fed host half must produce identical results to
    the window drain, every dispatch must land a callback, and commits
    must sweep the stored triples."""
    rng = np.random.default_rng(8)
    xs, _ = make_stream(rng, 48)

    def make(batching, early_emit):
        t = RemoteTransport(remote_apply, quiet_tconf())
        engine = CascadeEngine(local_apply, batch_size=8,
                               remote_fraction_budget=0.5, t_remote=0.0,
                               transport=t, early_emit=early_emit)
        sched = MicrobatchScheduler(engine, fallback=lambda r: -7,
                                    pipeline_depth=4,
                                    completion_mode="streaming",
                                    batching=batching)
        return sched, engine

    s_win, e_win = make("window", early_emit=False)
    s_con, e_con = make("continuous", early_emit=True)
    assert e_con.early_emit and not e_win.early_emit
    r_win = serve_all(s_win, xs)
    r_con = serve_all(s_con, xs)
    assert by_uid(r_win) == by_uid(r_con)
    assert_same_accounting(e_win, e_con)
    assert e_con._gate_emits == 48 // 8     # one callback per dispatch
    assert e_con._gate_results == {}        # swept at commit
    e_win.close()
    e_con.close()


def test_warmup_compiles_every_loop_program_and_counts_no_window():
    """``warmup`` compiles the gated local step and the 2nd-level remote
    scoring before serving: the serve loop then compiles nothing, and
    the warm-up dispatch (seq 0) lands no early-emit window."""
    rng = np.random.default_rng(9)
    xs, _ = make_stream(rng, 48)
    engine = CascadeEngine(local_apply, batch_size=8,
                           remote_fraction_budget=0.5, t_remote=0.0,
                           transport=RemoteTransport(remote_apply,
                                                     quiet_tconf()),
                           early_emit=True)
    sched = MicrobatchScheduler(engine, fallback=lambda r: -7,
                                pipeline_depth=4,
                                completion_mode="streaming",
                                batching="continuous")
    hlo = engine.warmup(xs[:8], remote_classes=xs.shape[1])
    assert "HloModule" in hlo
    assert engine._gate_emits == 0 and engine._gate_results == {}
    compiles = []
    with jax.log_compiles(True):
        handler = logging.Handler()
        handler.emit = lambda rec: compiles.append(rec.getMessage())
        log = logging.getLogger("jax")
        log.addHandler(handler)
        try:
            responses = serve_all(sched, xs)
        finally:
            log.removeHandler(handler)
    assert len(responses) == 48
    assert engine._gate_emits == 48 // 8
    assert not [m for m in compiles if m.startswith("Compiling")], compiles
    engine.close()


def test_continuous_fused_local_head_matches_window():
    """The fused local-head->gate path (kernels/fused_head_gate) drives
    the engine's local step whenever local_apply is a FusedLocalHead;
    slot-map scheduling on top of it must still match the window drain
    bitwise."""
    from repro.kernels.fused_head_gate.ops import FusedLocalHead
    rng = np.random.default_rng(7)
    xs, _ = make_stream(rng, 48)
    w = jnp.asarray(rng.normal(0, 0.5, (4, 4)), jnp.float32)
    head = FusedLocalHead(trunk=lambda x: x, w=w,
                          bias=jnp.zeros((4,), jnp.float32))

    def make(batching):
        t = RemoteTransport(remote_apply, quiet_tconf())
        engine = CascadeEngine(head, batch_size=8,
                               remote_fraction_budget=0.5, t_remote=0.0,
                               transport=t)
        sched = MicrobatchScheduler(engine, fallback=lambda r: -7,
                                    pipeline_depth=4,
                                    completion_mode="streaming",
                                    batching=batching)
        return sched, engine

    s_win, e_win = make("window")
    s_con, e_con = make("continuous")
    r_win = serve_all(s_win, xs)
    r_con = serve_all(s_con, xs)
    assert by_uid(r_win) == by_uid(r_con)
    assert_same_accounting(e_win, e_con)
    e_win.close()
    e_con.close()


# ------------------------------------------- the point of continuous

def test_trusted_locals_hand_back_while_escalation_stuck():
    """Slot starvation guard: one cohort's escalation parked on a slow
    remote must not wedge later cohorts — their trusted-local rows join
    free slots, clear the gate and hand back immediately."""
    remote_lat = 0.3
    calls = {"n": 0}
    lock = threading.Lock()

    def slow_first_remote(x):
        with lock:
            calls["n"] += 1
            i = calls["n"]
        time.sleep(remote_lat if i == 1 else 0.0)
        return remote_apply(x)

    rng = np.random.default_rng(5)
    # first cohort: half hard (escalates, rides the stuck remote);
    # everything after: easy, trusted-local
    xs_hard, _ = make_stream(rng, 8, hard_frac=0.5)
    xs_easy, _ = make_stream(rng, 40, hard_frac=0.0)
    xs = np.concatenate([xs_hard, xs_easy])

    sched, engine = build(slow_first_remote, batch=8, depth=4)
    # warm the jit cache out of band, then reset accounting: measured
    # latencies must reflect serving, not first-call compilation
    engine.serve({"local": xs[:8], "remote": xs[:8]})
    engine.stats = type(engine.stats)()
    calls["n"] = 0
    responses = serve_all(sched, xs)
    assert sorted(r.uid for r in responses) == list(range(48))
    local = [r for r in responses if r.source == "local"]
    esc = [r for r in responses if r.source != "local"]
    # capacity-k: every cohort escalates its bottom half, but only the
    # FIRST cohort's escalations ride the stuck remote call
    stuck = [r for r in esc if r.uid < 8]
    assert stuck and min(r.latency_s for r in stuck) >= remote_lat
    # every trusted-local row beat the stuck remote home — including
    # rows submitted AFTER the stuck cohort
    assert max(r.latency_s for r in local) < remote_lat
    assert sched.first_response_s < remote_lat
    # slot ledger reconciles: every admitted row joined and left
    assert sched._slots.joins == sched._slots.leaves == 48
    assert sched._slots.occupied == 0
    assert 0 < sched._slots.peak <= sched._slots.capacity
    engine.close()


# --------------------------- full cohorts admitted while on the wire

def flush_within(sched, limit_s):
    """``sched.flush()`` with a time limit: a loop that hangs fails this
    test instead of wedging the suite."""
    out, errors = [], []

    def run():
        try:
            out.extend(sched.flush())
        except BaseException as e:          # re-raised in the test
            errors.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(limit_s)
    assert not t.is_alive(), f"flush still running after {limit_s} s"
    if errors:
        raise errors[0]
    return out


def record_dispatches(engine):
    """Wrap ``engine.begin_serve``: the list fills with (time the
    dispatch began, window handle) per cohort."""
    log = []
    begin = engine.begin_serve

    def wrapped(*args, **kw):
        t = time.perf_counter()
        fl = begin(*args, **kw)
        log.append((t, fl))
        return fl

    engine.begin_serve = wrapped
    return log


def sleepy(remote_s, returns):
    """A remote that holds each call ``remote_s`` and notes its return."""
    def remote(x):
        time.sleep(remote_s)
        returns.append(time.perf_counter())
        return remote_apply(x)
    return remote


def submit_all(sched, xs):
    for i, row in enumerate(xs):
        sched.submit(Request(uid=i, local_input=row, remote_input=row))


def test_full_cohort_admitted_while_escalations_on_the_wire():
    """At depth 1 the first cohort's escalations hold slots until their
    answers land, yet with full cohorts queued the next one is dispatched
    at once: rows that wait only on the wire do not block it."""
    rng = np.random.default_rng(11)
    xs, _ = make_stream(rng, 24)
    returns = []
    sched, engine = build(sleepy(0.3, returns), batch=8, depth=1)
    engine.warmup(xs[:8], remote_classes=xs.shape[1])
    log = record_dispatches(engine)
    submit_all(sched, xs)
    out = flush_within(sched, 60)
    assert sorted(r.uid for r in out) == list(range(24))
    assert len(log) == 3
    assert log[1][0] < min(returns)     # before the first call returned
    assert sched.admission.on_wire >= 1
    assert sched._slots.occupied == 0
    engine.close()


def test_full_cohort_arriving_during_the_remote_wait_is_admitted():
    """The loop's remote wait also ends when a full cohort is queued
    meanwhile: rows submitted from another thread while the first
    cohort's call is out go out before that call returns."""
    rng = np.random.default_rng(14)
    xs, _ = make_stream(rng, 16)
    returns, calling = [], threading.Event()
    slow = sleepy(0.5, returns)

    def remote(x):
        calling.set()
        return slow(x)

    sched, engine = build(remote, batch=8, depth=1)
    engine.warmup(xs[:8], remote_classes=xs.shape[1])
    log = record_dispatches(engine)
    submit_all(sched, xs[:8])
    out, errors = [], []

    def run():
        try:
            out.extend(sched.flush())
        except BaseException as e:          # re-raised in the test
            errors.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert calling.wait(30)
    for i, row in enumerate(xs[8:], start=8):
        sched.submit(Request(uid=i, local_input=row, remote_input=row))
    t.join(60)
    assert not t.is_alive(), "flush still running after 60 s"
    if errors:
        raise errors[0]
    assert sorted(r.uid for r in out) == list(range(16))
    assert len(log) == 2
    assert log[1][0] < min(returns)     # before the first call returned
    assert sched.admission.on_wire == 1
    engine.close()


def test_partial_cohort_waits_for_the_wire_and_counts_match_window():
    """A cohort short of ``batch_size`` is never admitted early: it waits
    until the wire rows have left, so every dispatch holds the rows and
    escalates the count that the window drain gives it."""
    rng = np.random.default_rng(12)
    xs, _ = make_stream(rng, 8 + 8 + 5)

    def run(batching):
        returns = []
        sched, engine = build(sleepy(0.2, returns), batch=8, depth=1,
                              batching=batching)
        engine.warmup(xs[:8], remote_classes=xs.shape[1])
        log = record_dispatches(engine)
        submit_all(sched, xs)
        out = flush_within(sched, 60)
        engine.close()
        return out, log, returns, sched, engine

    r_win, log_win, _, _, e_win = run("window")
    r_con, log_con, returns, sched, e_con = run("continuous")
    counts = [(fl.real, fl.k) for _, fl in log_con]
    assert counts == [(fl.real, fl.k) for _, fl in log_win]
    assert counts == [(8, 4), (8, 4), (5, 4)]
    # the second (full) cohort went out on the wire, the third (partial)
    # only after both calls before it had returned
    assert log_con[1][0] < min(returns)
    assert log_con[2][0] > sorted(returns)[1]
    assert sched.admission.on_wire == 1
    assert by_uid(r_win) == by_uid(r_con)
    assert_same_accounting(e_win, e_con)


def test_escalations_hand_back_before_the_next_gate_when_step_is_slower():
    """With a local step slower than the remote, the answers of a cohort
    on the wire land while the next cohort's step runs; the loop hands
    them back before that cohort's gate clears, not after."""
    def slow_local(x):
        def nap(v):
            time.sleep(0.3)
            return v
        x = jax.pure_callback(nap, jax.ShapeDtypeStruct(x.shape, x.dtype),
                              x)
        return local_apply(x)

    rng = np.random.default_rng(13)
    xs, _ = make_stream(rng, 24)
    returns = []
    engine = CascadeEngine(slow_local, batch_size=8,
                           remote_fraction_budget=0.5, t_remote=0.0,
                           transport=RemoteTransport(sleepy(0.05, returns),
                                                     quiet_tconf()))
    sched = MicrobatchScheduler(engine, fallback=lambda r: -7,
                                pipeline_depth=1,
                                completion_mode="streaming",
                                batching="continuous")
    engine.warmup(xs[:8], remote_classes=xs.shape[1])
    log = record_dispatches(engine)
    submit_all(sched, xs)
    out = flush_within(sched, 60)
    assert sorted(r.uid for r in out) == list(range(24))
    assert log[1][0] < min(returns)     # cohort 2 rode cohort 1's call
    order = {r.uid: i for i, r in enumerate(out)}   # hand-back order
    first_escalated = [order[r.uid] for r in out
                       if r.uid < 8 and r.source != "local"]
    second_local = [order[r.uid] for r in out
                    if 8 <= r.uid < 16 and r.source == "local"]
    assert first_escalated and second_local
    assert max(first_escalated) < min(second_local)
    engine.close()


def test_queue_wait_estimate_prices_slot_occupancy():
    """Continuous mode prices admission against slot occupancy amortized
    over the pipeline width; window mode prices whole windows ahead."""
    s_con, e_con = build(batch=8, depth=4)
    s_win, e_win = build(batch=8, depth=4, batching="window")
    for e in (e_con, e_win):
        e.stats.window_service_ema_s = 0.1

    # idle slot map: one window's EMA, regardless of queue depth < batch
    assert s_con._queue_wait_estimate(0) == pytest.approx(0.1)
    # 24 occupied slots + 8 queued = 4 windows ahead, amortized over 4
    s_con._slots.join(24)
    assert s_con._queue_wait_estimate(8) == pytest.approx(
        0.1 * (1.0 + (8 + 24) // 8 / 4))
    # window mode: whole windows ahead of the row, plus its own
    assert s_win._queue_wait_estimate(0) == pytest.approx(0.1)
    assert s_win._queue_wait_estimate(24) == pytest.approx(0.4)
    s_con._slots.leave(24)
    e_con.close()
    e_win.close()


def test_slot_map_telemetry_ema():
    from repro.serving.scheduler import _SlotMap
    sm = _SlotMap(32)
    assert sm.free == 32
    sm.join(16)
    assert sm.free == 16 and sm.peak == 16 and sm.occupied == 16
    sm.join(8)
    sm.leave(16)
    # the peak holds the high-water mark after rows leave
    assert sm.occupied == 8 and sm.peak == 24 and sm.free == 24
    sm.leave(8)
    assert sm.occupied == 0 and sm.leaves == 24 and sm.joins == 24


# --------------------------------------------- /metrics scrape endpoint

def test_metrics_server_serves_prometheus_and_json():
    reg = MetricsRegistry()
    reg.counter("cascade_requests_total").inc(42)
    with MetricsServer(reg, port=0) as srv:
        assert srv.port > 0
        body = urllib.request.urlopen(srv.url, timeout=5).read().decode()
        assert "cascade_requests_total 42" in body

        js = urllib.request.urlopen(
            f"http://{srv.host}:{srv.port}/metrics.json",
            timeout=5).read()
        snap = json.loads(js)
        assert snap["counters"]["cascade_requests_total"] == 42

        ok = urllib.request.urlopen(
            f"http://{srv.host}:{srv.port}/healthz", timeout=5).read()
        assert ok == b"ok\n"

        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/nope", timeout=5)
    # closed: the port no longer accepts connections
    with pytest.raises(OSError):
        urllib.request.urlopen(srv.url, timeout=0.5)


def test_metrics_server_live_engine_counters():
    """End to end: a continuous serve loop's commit-time counters are
    scrapeable over HTTP while the engine is still open."""
    from repro.runtime import Observability
    rng = np.random.default_rng(6)
    xs, _ = make_stream(rng, 16)
    sched, engine = build()
    Observability.enabled().install(engine)
    serve_all(sched, xs)
    with MetricsServer(engine.observability.metrics, port=0) as srv:
        body = urllib.request.urlopen(srv.url, timeout=5).read().decode()
    assert "cascade_requests_total 16" in body
    engine.close()
