"""Pipelined + streaming serving-path benchmark (ISSUE 2/4 acceptance;
DESIGN.md §5, §7).

Synthetic load at ~20% escalation against a fake remote with a real
0.3s round-trip latency. Three engines serve the SAME request stream:

  serial    — the runtime path, one microbatch at a time: local step,
              then block on the remote window before the next batch's
              local step can dispatch;
  pipelined — ``pipeline_depth`` microbatches in flight: batch i+1's
              local tier (fused confidence gate) runs while batch i's
              escalations are on the wire; windows drain in submission
              order (FIFO);
  streaming — the same pipeline with per-request completion: locally
              trusted requests hand back the moment the confidence gate
              clears, escalations stream back as their remote futures
              resolve (``--completion-mode streaming``).

Throughput is the headline FIFO metric; the streaming section reports
the per-request hand-back latency distribution split by trusted-local
vs escalated rows. The run VERIFIES that all paths produce bitwise-
identical predictions/routing and identical billing stats — overlap and
reordering must never change what the cascade answers or charges — and
that the streaming trusted-local p95 is at most half the FIFO-drain
per-request p95 (ISSUE 4 acceptance).

A fourth, mixed-SLA section (DESIGN.md §8) attaches a tight
``RequestPolicy`` deadline to half the stream: the policy-aware
scheduler packs likely-escalating rows into dedicated windows (purity is
reported and gated) and the engine downgrades deadline-infeasible
escalations to ``DEADLINE_LOCAL``, so tight-deadline requests meet their
SLA instead of inheriting the remote round trip. The section reports the
deadline-hit-rate, packed-window purity and per-disposition counts.

A continuous-batching section (DESIGN.md §11) re-serves the streaming
stream with ``batching="continuous"``: a slot map over the persistent
padded batch admits requests as slots free up and the in-kernel early
emit hands trusted-local rows back at gate time. Gated: predictions
and billing bitwise identical to fixed-window streaming, and the
trusted-local SERVICE p95 (net of queue wait) at most half of
window streaming's.

A fifth, observability section (DESIGN.md §9) re-runs the headline
stream with the full tracing/metrics/event stack enabled and gates:
traced throughput within 3% of untraced, answers and billing unchanged,
exactly one monotonic span per request, span costs and commit-time
metric counters reconciling (bitwise) with ``CascadeStats``.
``--trace-jsonl`` / ``--metrics-out`` export the traced run's spans and
metrics snapshot (CI uploads both as artifacts).

Machine-readable results are written to ``BENCH_serving.json`` so the
perf trajectory is tracked across PRs and gated by
``benchmarks/check_regression.py``.

    PYTHONPATH=src python -m benchmarks.serving_bench \
        [--requests 1024] [--depth 8] [--remote-latency 0.3] \
        [--completion-mode streaming] [--json BENCH_serving.json]
"""

from __future__ import annotations

import argparse
import json
import time
from collections import Counter

import jax.numpy as jnp
import numpy as np

from repro.runtime import (Observability, TransportConfig,
                           fit_escalation_prior)
from repro.serving import RemoteSpec, RequestPolicy, ServeConfig
from repro.serving.engine import BILLING_FIELDS
from repro.serving.scheduler import Request

BATCH = 32
NCLS = 8
TARGET = 0.20           # escalation fraction (capacity-k, no controller)
STREAMING_P95_RATIO = 0.5       # trusted-local p95 <= ratio * FIFO p95
CONTINUOUS_SERVICE_RATIO = 0.5  # continuous trusted-local service p95
                                # <= ratio * window-streaming's (ISSUE 8)
OVERHEAD_BAR = 0.97             # traced throughput >= 97% untraced (§9)
DEADLINE_HIT_BAR = 0.95         # tight rows meeting their SLA (§8)
PURITY_BAR = 0.95               # packed windows from one class only


def local_apply(x):
    return x + 0.3 * jnp.sin(17.0 * x)     # noisy view of the features


def make_remote(latency_s: float):
    def remote(x):
        time.sleep(latency_s)              # the wire + the big model
        return 5.0 * np.asarray(x)
    return remote


def make_load(rng, n, hard_frac=0.3):
    """Feature batches whose argmax is the label; hard rows have small
    margins -> low 1st-level confidence. All rows distinct (the cache
    must not blur the serial/pipelined billing comparison)."""
    labels = rng.integers(0, NCLS, n)
    x = rng.normal(0, 0.05, (n, NCLS))
    margin = np.where(rng.random(n) < hard_frac,
                      rng.uniform(0.05, 0.4, n), rng.uniform(2.0, 4.0, n))
    x[np.arange(n), labels] += margin
    return np.float32(x), labels


def _mk_config(depth: int, latency_s: float, completion_mode="fifo",
               packing="none", t_local=None,
               batching="window") -> ServeConfig:
    """The one ServeConfig every bench engine is built from (§8)."""
    return ServeConfig(
        batch_size=BATCH, remote_fraction_budget=TARGET, t_remote=0.0,
        t_local=t_local, pipeline_depth=depth,
        completion_mode=completion_mode, packing=packing, cache_size=0,
        batching=batching,
        transport=TransportConfig(max_in_flight=BATCH, retry_backoff_s=0.0,
                                  timeout_s=max(2.0, 10 * latency_s),
                                  max_concurrent=max(depth, 1)),
        remotes=(RemoteSpec("remote", None, latency_s),))


def _serve(xs, depth: int, latency_s: float, completion_mode="fifo",
           policies=None, packing="none", prior=None, t_local=None,
           observability=False, batching="window"):
    cfg = _mk_config(depth, latency_s, completion_mode, packing, t_local,
                     batching)
    engine, sched = cfg.build(local_apply, make_remote(latency_s),
                              fallback=lambda r: -1, prior=prior)
    # warm the jit cache with one out-of-band batch, then reset accounting
    engine.serve({"local": xs[:BATCH], "remote": xs[:BATCH]})
    engine.stats = type(engine.stats)()
    if observability:
        # installed AFTER the warm-up reset so the commit-time counters
        # stay bitwise-reconcilable with the (reset) CascadeStats
        Observability.enabled().install(engine)
    t0 = time.perf_counter()
    for i, row in enumerate(xs):
        sched.submit(Request(uid=i, local_input=row, remote_input=row,
                             policy=policies[i] if policies else None))
    responses = sched.flush()
    wall = time.perf_counter() - t0
    engine.close()
    return responses, engine, wall, sched


def _metrics(tag, responses, engine, wall, n) -> dict:
    st = engine.stats
    lat = [r.latency_s for r in responses]
    return {
        "path": tag,
        "requests": n,
        "wall_s": wall,
        "throughput_rps": n / wall,
        "p50_wall_latency_s": st.wall_percentile(50),
        "p95_wall_latency_s": st.wall_percentile(95),
        "mean_wall_latency_s": st.mean_wall_latency_s,
        # per-request hand-back latency (enqueue -> response, §8)
        "p50_request_latency_s": float(np.percentile(lat, 50)),
        "p95_request_latency_s": float(np.percentile(lat, 95)),
        "modelled_mean_latency_s": st.mean_latency_s,
        "remote_fraction": st.remote_fraction,
        "escalation_fraction": st.escalation_fraction,
        "remote_calls": st.remote_calls,
        "total_cost": st.total_cost,
        # per-backend measured remote latency (TransportStats), so the
        # latency-ema routing policy is observable in bench JSON
        "backend_remote_latency": {
            b.name: {"p95_s": b.stats.latency_percentile(95),
                     "ema_s": b.stats.latency_ema_s}
            for b in engine.router},
    }


def _service_lat(r) -> float:
    """Dispatch -> hand-back: latency net of load-dependent queue wait
    (Response.latency_s is enqueue-anchored since §8)."""
    return r.latency_s - r.queue_s


def _latency_split(responses) -> dict:
    """Per-request hand-back latency, split trusted-local vs escalated.
    Both the enqueue-anchored latency and the SERVICE latency (net of
    queue wait) are reported; the trusted-local-vs-FIFO ratio check uses
    the service numbers so an oversubscribed submit burst (shared queue
    wait on both sides) cannot mask a head-of-line regression."""
    out = {}
    for tag, rows in (
            ("trusted_local", [r for r in responses if r.source == "local"]),
            ("escalated", [r for r in responses if r.source != "local"])):
        lat = [r.latency_s for r in rows]
        svc = [_service_lat(r) for r in rows]
        out[tag] = {
            "count": len(rows),
            "p50_latency_s": float(np.percentile(lat, 50)) if lat else 0.0,
            "p95_latency_s": float(np.percentile(lat, 95)) if lat else 0.0,
            "mean_latency_s": float(np.mean(lat)) if lat else 0.0,
            "service_p95_latency_s":
                float(np.percentile(svc, 95)) if svc else 0.0,
        }
    return out


def _by_uid(responses):
    return {r.uid: (r.prediction, r.source) for r in responses}


def _margin(row: np.ndarray) -> float:
    """Cheap request-observable proxy score: top-1 vs top-2 feature gap
    (correlates with the 1st-level supervisor confidence)."""
    s = np.sort(np.asarray(row))
    return float(s[-1] - s[-2])


def _policy_section(xs, depth: int, latency_s: float) -> dict:
    """Mixed-SLA workload (DESIGN.md §8): 50% of the stream carries a
    tight per-request deadline equal to the remote round trip (so ANY
    escalation would blow the SLA once the window is in flight), 50% is
    relaxed (no policy). The calibration-table escalation prior +
    policy feasibility drive the scheduler's hot/cold window packing;
    the engine downgrades deadline-infeasible escalations to
    DEADLINE_LOCAL. Gated: deadline-hit-rate, packed-window purity, zero
    drops, per-response costs summing to the billed total."""
    n = len(xs)
    tight = RequestPolicy(deadline_s=latency_s)
    policies = [tight if i % 2 == 0 else None for i in range(n)]

    # calibration table (§8): offline 1st-level confidences on a slice
    # pick t_local at the TARGET quantile and fit the escalation prior
    # on the request-observable margin proxy
    n_cal = min(256, n)
    logits = np.asarray(local_apply(jnp.asarray(xs[:n_cal])))
    conf = np.max(np.exp(logits) / np.exp(logits).sum(-1, keepdims=True),
                  -1)
    t_local = float(np.quantile(conf, TARGET))
    prior = fit_escalation_prior(
        np.array([_margin(r) for r in xs[:n_cal]]), conf <= t_local)

    responses, engine, wall, sched = _serve(
        xs, depth=depth, latency_s=latency_s, completion_mode="streaming",
        policies=policies, packing="policy",
        prior=lambda req: prior(_margin(req.local_input)),
        t_local=t_local)

    tight_rows = [r for r in responses if r.uid % 2 == 0]
    hits = [r for r in tight_rows if r.latency_s <= latency_s]
    hit_rate = len(hits) / max(len(tight_rows), 1)
    ps = dict(sched.packing_stats)
    purity = (ps["cold"] + ps["hot"]) / max(ps["windows"], 1)
    st = engine.stats
    cost_sum = sum(r.cost for r in responses)
    dispositions = dict(Counter(r.disposition for r in responses))
    checks = {
        "deadline_hit_rate_ok": hit_rate >= DEADLINE_HIT_BAR,
        "zero_dropped": len(responses) == n,
        "windows_pure": ps["mixed"] == 0 and purity >= PURITY_BAR,
        "response_costs_sum_to_total":
            abs(cost_sum - st.total_cost) < 1e-9,
        "billing_invariant": (st.escalations == st.remote_calls
                              + st.cache_hits + st.transport_failures),
    }
    lat_tight = [r.latency_s for r in tight_rows]
    lat_rel = [r.latency_s for r in responses if r.uid % 2 == 1]
    return {
        "requests": n,
        "tight_fraction": 0.5,
        "tight_deadline_s": latency_s,
        "wall_s": wall,
        "throughput_rps": n / wall,
        "deadline_hit_rate": hit_rate,
        "packed_window_purity": purity,
        "packing_stats": ps,
        "dispositions": dispositions,
        "tight": {
            "count": len(tight_rows),
            "p50_latency_s": float(np.percentile(lat_tight, 50)),
            "p95_latency_s": float(np.percentile(lat_tight, 95)),
        },
        "relaxed": {
            "count": len(lat_rel),
            "p50_latency_s": float(np.percentile(lat_rel, 50)),
            "p95_latency_s": float(np.percentile(lat_rel, 95)),
        },
        "total_cost": st.total_cost,
        "remote_fraction": st.remote_fraction,
        "checks": checks,
        "passed": all(checks.values()),
    }


def _spans_monotonic(spans) -> bool:
    for s in spans:
        ts = [t for _, t in s["stages"]]
        if ts != sorted(ts):
            return False
    return True


def _observability_section(xs, depth, latency_s, completion_mode,
                           trace_jsonl=None, metrics_out=None) -> dict:
    """Traced twin of the headline run (DESIGN.md §9): the SAME stream
    against the same sleeping fake remote, with the full observability
    stack on. Both arms take the best of 5 walls — against a sleeping
    remote the wall clock quantises to whole round trips, so a single
    missed window overlap in one run would masquerade as ~50% overhead.
    Gated: tracing must not change answers or billing, must cost <=3%
    throughput, must produce exactly one monotonic span per request,
    and the commit-time metric counters must reconcile bitwise with
    ``CascadeStats``."""
    n = len(xs)

    def best_of(observability):
        best = None
        for _ in range(5):
            r, eng, w, _s = _serve(xs, depth=depth, latency_s=latency_s,
                                   completion_mode=completion_mode,
                                   observability=observability)
            if best is None or w < best[2]:
                best = (r, eng, w)
        return best

    r_base, eng_base, w_base = best_of(False)
    r_tr, eng_tr, w_tr = best_of(True)
    obs = eng_tr.observability
    st = eng_tr.stats
    spans = obs.trace.spans()
    counters = obs.metrics.snapshot()["counters"]
    span_cost = sum(s["cost"] for s in spans)
    span_disp = dict(Counter(s["disposition"] for s in spans))
    resp_disp = dict(Counter(r.disposition for r in r_tr))
    checks = {
        "overhead_ok": (n / w_tr) >= OVERHEAD_BAR * (n / w_base),
        "predictions_identical": _by_uid(r_tr) == _by_uid(r_base),
        "billing_identical": _billing_identical(eng_tr, eng_base),
        "one_span_per_request":
            sorted(s["uid"] for s in spans) == list(range(n)),
        "spans_monotonic": _spans_monotonic(spans),
        "span_costs_match_billing":
            abs(span_cost - st.total_cost) < 1e-9
            and span_disp == resp_disp,
        # commit-order counter updates reconcile BITWISE with the stats
        "metrics_match_stats": (
            counters.get("cascade_requests_total") == st.requests
            and counters.get("cascade_escalations_total") == st.escalations
            and counters.get("cascade_remote_calls_total") == st.remote_calls
            and counters.get("cascade_cost_dollars_total") == st.total_cost),
    }
    if trace_jsonl:
        obs.trace.write_jsonl(trace_jsonl)
    if metrics_out:
        with open(metrics_out, "w") as f:
            json.dump(obs.metrics.snapshot(), f, indent=1, sort_keys=True)
    return {
        "untraced_throughput_rps": n / w_base,
        "traced_throughput_rps": n / w_tr,
        "overhead_ratio": (n / w_tr) / (n / w_base),
        "spans": len(spans),
        "trace_dropped": obs.trace.dropped,
        "events": dict(sorted(obs.events.counts().items())),
        "dispositions": span_disp,
        "checks": checks,
        "passed": all(checks.values()),
    }


def _billing_identical(a, b) -> bool:
    if any(getattr(a.stats, f) != getattr(b.stats, f) for f in BILLING_FIELDS):
        return False
    cost = lambda e: {n: u.cost for n, u in e.stats.per_backend.items()}
    return cost(a) == cost(b)


def run(verbose: bool = True, requests: int = 1024, depth: int = 8,
        remote_latency_s: float = 0.3, completion_mode: str = "streaming",
        json_path: str | None = "BENCH_serving.json",
        trace_jsonl: str | None = None,
        metrics_out: str | None = None) -> dict:
    rng = np.random.default_rng(0)
    xs, _ = make_load(rng, requests)

    r_ser, eng_ser, w_ser, _ = _serve(xs, depth=1,
                                      latency_s=remote_latency_s)
    r_pip, eng_pip, w_pip, _ = _serve(xs, depth=depth,
                                      latency_s=remote_latency_s)

    identical = ([(r.uid, r.prediction, r.source) for r in r_ser]
                 == [(r.uid, r.prediction, r.source) for r in r_pip])
    billing_identical = _billing_identical(eng_ser, eng_pip)

    n = len(xs)
    serial = _metrics("serial", r_ser, eng_ser, w_ser, n)
    pipelined = _metrics("pipelined", r_pip, eng_pip, w_pip, n)
    report = {
        "batch_size": BATCH,
        "pipeline_depth": depth,
        "remote_latency_s": remote_latency_s,
        "target_escalation_fraction": TARGET,
        "serial": serial,
        "pipelined": pipelined,
        "speedup": serial["wall_s"] / pipelined["wall_s"],
        "predictions_identical": identical,
        "billing_identical": billing_identical,
        "passed_2x": (serial["wall_s"] / pipelined["wall_s"] >= 2.0
                      and identical and billing_identical),
    }

    # --- streaming completion mode (DESIGN.md §7) ---
    if completion_mode == "streaming":
        r_str, eng_str, w_str, s_str = _serve(
            xs, depth=depth, latency_s=remote_latency_s,
            completion_mode="streaming")
        fifo_p95 = float(np.percentile([_service_lat(r) for r in r_pip],
                                       95))
        split = _latency_split(r_str)
        local_p95 = split["trusted_local"]["service_p95_latency_s"]
        checks = {
            # reordering must never change answers, routing or billing
            "predictions_identical": _by_uid(r_str) == _by_uid(r_pip),
            "billing_identical": _billing_identical(eng_str, eng_pip),
            "zero_dropped": len(r_str) == n,
            # the point of streaming: cheap locally-trusted requests no
            # longer inherit the remote p95 (ISSUE 4 acceptance)
            "trusted_local_p95_halved":
                local_p95 <= STREAMING_P95_RATIO * fifo_p95,
        }
        report["streaming"] = {
            "wall_s": w_str,
            "throughput_rps": n / w_str,
            "first_response_s": s_str.first_response_s,
            "fifo_service_p95_latency_s": fifo_p95,
            "trusted_local_p95_ratio_vs_fifo":
                local_p95 / max(fifo_p95, 1e-12),
            **split,
            "checks": checks,
            "passed": all(checks.values()),
        }
        report["passed"] = report["passed_2x"] and all(checks.values())

        # --- continuous batching vs fixed-window streaming (ISSUE 8) ---
        # Same stream, same depth, batching="continuous": slot-map
        # admission + in-kernel early emit + host half at gate time.
        # Cohorts are drawn identically to the fixed-window packer, so
        # predictions AND billing must stay bitwise identical; the win
        # is emission timing — trusted-local SERVICE latency (net of
        # queue wait) must at least halve vs window streaming.
        r_cont, eng_cont, w_cont, s_cont = _serve(
            xs, depth=depth, latency_s=remote_latency_s,
            completion_mode="streaming", batching="continuous")
        split_cont = _latency_split(r_cont)
        win_local_p95 = split["trusted_local"]["service_p95_latency_s"]
        cont_local_p95 = split_cont["trusted_local"]["service_p95_latency_s"]
        slots = s_cont._slots
        cont_checks = {
            # slot-map scheduling must never change answers or billing
            "predictions_identical": _by_uid(r_cont) == _by_uid(r_str),
            "billing_identical": _billing_identical(eng_cont, eng_str),
            "zero_dropped": len(r_cont) == n,
            # the point of continuous batching: trusted-local rows hand
            # back at gate time, not at window-drain time
            "trusted_local_service_halved":
                cont_local_p95 <= CONTINUOUS_SERVICE_RATIO * win_local_p95,
        }
        report["continuous"] = {
            "wall_s": w_cont,
            "throughput_rps": n / w_cont,
            "first_response_s": s_cont.first_response_s,
            "window_trusted_local_service_p95_s": win_local_p95,
            "trusted_local_service_ratio_vs_window":
                cont_local_p95 / max(win_local_p95, 1e-12),
            "slot_stats": {
                "capacity": slots.capacity,
                "peak_occupied": slots.peak,
                "joins": slots.joins,
                "leaves": slots.leaves,
            },
            **split_cont,
            "checks": cont_checks,
            "passed": all(cont_checks.values()),
        }
        report["passed"] = report["passed"] and all(cont_checks.values())
    else:
        report["passed"] = report["passed_2x"]

    # --- mixed-SLA policy section (DESIGN.md §8) ---
    report["policy"] = _policy_section(xs, depth, remote_latency_s)
    report["passed"] = report["passed"] and report["policy"]["passed"]

    # --- observability overhead + trace/metric reconciliation (§9) ---
    report["observability"] = _observability_section(
        xs, depth, remote_latency_s, completion_mode, trace_jsonl,
        metrics_out)
    report["passed"] = (report["passed"]
                        and report["observability"]["passed"])

    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=1)
    if verbose:
        print(f"\n--- Serving: pipelined vs serial runtime path "
              f"({n} requests, {TARGET:.0%} escalation, "
              f"{remote_latency_s}s fake remote, depth {depth}) ---")
        print(f"{'path':>10} {'req/s':>8} {'wall':>7} {'p50':>7} {'p95':>7} "
              f"{'remote%':>8}")
        for m in (serial, pipelined):
            print(f"{m['path']:>10} {m['throughput_rps']:8.1f} "
                  f"{m['wall_s']:6.1f}s {m['p50_wall_latency_s']*1e3:6.0f}m "
                  f"{m['p95_wall_latency_s']*1e3:6.0f}m "
                  f"{m['remote_fraction']:8.2f}")
        print(f"speedup {report['speedup']:.2f}x; predictions identical: "
              f"{identical}; billing identical: {billing_identical}")
        if "streaming" in report:
            s = report["streaming"]
            print("--- Streaming completion (per-request hand-back) ---")
            print(f"trusted-local service p95 "
                  f"{s['trusted_local']['service_p95_latency_s']*1e3:7.1f} "
                  f"ms ({s['trusted_local']['count']} requests) vs FIFO "
                  f"service p95 {s['fifo_service_p95_latency_s']*1e3:.1f}"
                  f" ms -> ratio {s['trusted_local_p95_ratio_vs_fifo']:.3f}")
            print(f"escalated     p95 "
                  f"{s['escalated']['p95_latency_s']*1e3:7.1f} ms "
                  f"({s['escalated']['count']} requests); first response "
                  f"{s['first_response_s']*1e3:.1f} ms; checks {s['checks']}")
        if "continuous" in report:
            c = report["continuous"]
            print("--- Continuous batching (slot map + early emit) ---")
            print(f"trusted-local service p95 "
                  f"{c['trusted_local']['service_p95_latency_s']*1e3:7.2f} "
                  f"ms vs window-streaming "
                  f"{c['window_trusted_local_service_p95_s']*1e3:.2f} ms "
                  f"-> ratio {c['trusted_local_service_ratio_vs_window']:.3f}"
                  f" (bar {CONTINUOUS_SERVICE_RATIO})")
            print(f"slots {c['slot_stats']}; checks {c['checks']}")
        pol = report["policy"]
        print("--- Mixed-SLA policy section (DESIGN.md §8) ---")
        print(f"tight deadline {pol['tight_deadline_s']*1e3:.0f} ms: "
              f"hit rate {pol['deadline_hit_rate']:.3f} "
              f"(tight p95 {pol['tight']['p95_latency_s']*1e3:.1f} ms, "
              f"relaxed p95 {pol['relaxed']['p95_latency_s']*1e3:.1f} ms)")
        print(f"window packing {pol['packing_stats']} -> purity "
              f"{pol['packed_window_purity']:.2f}; dispositions "
              f"{pol['dispositions']}; checks {pol['checks']}")
        ob = report["observability"]
        print("--- Observability overhead (DESIGN.md §9) ---")
        print(f"traced {ob['traced_throughput_rps']:.1f} req/s vs "
              f"untraced {ob['untraced_throughput_rps']:.1f} req/s "
              f"-> ratio {ob['overhead_ratio']:.3f} "
              f"(bar {OVERHEAD_BAR}); {ob['spans']} spans "
              f"({ob['trace_dropped']} dropped); checks {ob['checks']}")
        if json_path:
            print(f"JSON -> {json_path}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=1024)
    ap.add_argument("--depth", type=int, default=8,
                    help="pipelined in-flight microbatch window")
    ap.add_argument("--remote-latency", type=float, default=0.3,
                    help="fake remote round-trip seconds")
    ap.add_argument("--completion-mode", default="streaming",
                    choices=("fifo", "streaming"),
                    help="streaming adds the per-request completion "
                         "section (DESIGN.md §7); fifo skips it")
    ap.add_argument("--json", default="BENCH_serving.json",
                    help="machine-readable output path ('' disables)")
    ap.add_argument("--trace-jsonl", default="",
                    help="write the traced run's span timelines here "
                         "(JSONL, one span per line; '' disables)")
    ap.add_argument("--metrics-out", default="",
                    help="write the traced run's metrics snapshot here "
                         "(JSON; '' disables)")
    args = ap.parse_args(argv)
    report = run(requests=args.requests, depth=args.depth,
                 remote_latency_s=args.remote_latency,
                 completion_mode=args.completion_mode,
                 json_path=args.json or None,
                 trace_jsonl=args.trace_jsonl or None,
                 metrics_out=args.metrics_out or None)
    return 0 if report["passed"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
