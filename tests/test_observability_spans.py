"""Profiler spans of the serve loop (DESIGN.md §9 span table): a traced
continuous-batching flush puts every span on the profiler's trace, where
the benchmark's reduction (``chipbench/trace.py``) finds them; full
cohorts are admitted while earlier calls are on the wire, and the remote
wait covers the round trips the serving thread sat out after; with
observability off no ``TraceAnnotation`` is ever built; the gated step
keeps the program names the benchmark's device-trace readers match."""

from __future__ import annotations

import gc
import re
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import spec  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench.steps import STEP_MODULES  # noqa: E402
from repro.runtime import RemoteTransport, TransportConfig  # noqa: E402
from repro.runtime.observability import (SPAN_NAMES,  # noqa: E402
                                         Observability)
from repro.serving.engine import (CascadeEngine,  # noqa: E402
                                  make_gated_local_step)
from repro.serving.scheduler import MicrobatchScheduler, Request  # noqa: E402

REMOTE_S = 0.05
BATCH = 8


def local_apply(x):
    return x + 0.3 * jnp.sin(17.0 * x)


def sleepy_remote(x):
    time.sleep(REMOTE_S)
    return 5.0 * np.asarray(x)


def rows(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 0.05, (n, 4))
    x[np.arange(n), rng.integers(0, 4, n)] += 3.0
    return np.float32(x)


def build(observability):
    transport = RemoteTransport(sleepy_remote, TransportConfig(
        retry_backoff_s=0.0, max_retries=0, timeout_s=60.0))
    engine = CascadeEngine(local_apply, batch_size=BATCH,
                           remote_fraction_budget=0.5, t_remote=0.0,
                           transport=transport, early_emit=True,
                           observability=observability)
    sched = MicrobatchScheduler(engine, fallback=lambda r: -7,
                                pipeline_depth=1,
                                completion_mode="streaming",
                                batching="continuous")
    return sched, engine


def serve(sched, xs, uid0=0):
    for i, row in enumerate(xs):
        sched.submit(Request(uid=uid0 + i, local_input=row,
                             remote_input=row))
    return sched.flush()


def overlap(a, b) -> float:
    """Seconds that the unions of two event lists have in common."""
    ua, ub = T.merged(a), T.merged(b)
    return sum(max(0.0, min(x1, y1) - max(x0, y0))
               for x0, x1 in ua for y0, y1 in ub)


def test_traced_flush_puts_every_span_on_the_profiler_trace(tmp_path):
    obs = Observability.enabled()
    sched, engine = build(obs)
    serve(sched, rows(BATCH, seed=1), uid0=1000)     # compile outside
    n = 4 * BATCH                                     # four cohorts
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            out = serve(sched, rows(n))
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    assert len(out) == n
    tr = T.load(str(tmp_path))
    by = {}
    for e in tr.host:
        by.setdefault(e.name, []).append(e)
    assert set(SPAN_NAMES) <= set(by), sorted(set(SPAN_NAMES) - set(by))
    assert {e.name for e in T.clip(tr.host, *tr.window)} >= set(SPAN_NAMES)

    # one transport call per cohort, each holding the remote's sleep
    calls = by["transport.call"]
    assert len(calls) == 4
    assert all(c.end - c.start >= REMOTE_S for c in calls)
    # the queue held full cohorts, so each one after the first was
    # admitted while the calls before it were on the wire
    assert sched.admission.on_wire == 3
    share = spec.reader("admit_on_wire_share")(SimpleNamespace(trace=tr))
    assert share == pytest.approx(75.0)
    # with nothing left to admit, the serving thread spent nearly all of
    # the rest of the round trips waiting on them, or handing back those
    # that had landed while the others were still out
    last_route = max(e.end for e in by["cascade.route"])
    rest = T.clip(calls, last_route, tr.window[1])
    covered = overlap(by["cascade.remote_wait"] + by["cascade.complete"]
                      + by["scheduler.handback"], rest)
    assert covered >= 0.8 * T.busy_seconds(rest, *tr.window), covered
    # leaves: no wait sits inside a span that does work
    for work in ("scheduler.admit", "cascade.gate", "cascade.route",
                 "cascade.complete", "scheduler.handback"):
        for wait in ("cascade.remote_wait", "cascade.gate_wait"):
            assert overlap(by[work], by[wait]) == 0.0, (work, wait)

    hook = obs._gc_spans
    assert hook in gc.callbacks
    engine.close()
    assert hook not in gc.callbacks and obs._gc_spans is None


def test_observability_off_builds_no_trace_annotation(monkeypatch):
    built: list[str] = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **attrs):
            built.append(name)
            super().__init__(name, **attrs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    sched, engine = build(None)
    gcs = len(gc.callbacks)
    out = serve(sched, rows(3 * BATCH))
    gc.collect()
    engine.close()
    assert len(out) == 3 * BATCH
    assert built == []
    assert len(gc.callbacks) == gcs
    # the same flush with observability on builds them through the patch
    sched, engine = build(Observability.enabled())
    serve(sched, rows(3 * BATCH))
    engine.close()
    assert set(SPAN_NAMES) - {"python.gc"} <= set(built)


@pytest.mark.parametrize("emit", [False, True])
def test_gated_step_keeps_the_names_the_benchmark_matches(emit):
    """The engine jits the gated step as ``step`` (early emit armed) or
    ``gate``: ``chipbench/steps.py`` finds its program by those names;
    the trunk and the gate carry named scopes in the op metadata."""
    step = jax.jit(make_gated_local_step(
        local_apply, emit=(lambda *a: None) if emit else None))
    args = (rows(BATCH), np.float32(np.inf), np.int32(BATCH))
    lowered = step.lower(*args + ((np.int32(1),) if emit else ()))
    module = re.search(r"module @(\S+)", lowered.as_text()).group(1)
    assert module in STEP_MODULES
    assert module == ("jit_step" if emit else "jit_gate")
    debug = lowered.as_text(debug_info=True)
    assert f"{module[4:]})/trunk/" in debug and \
        f"{module[4:]})/gate/" in debug
