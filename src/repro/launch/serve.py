"""Cascade serving driver — BiSupervised as a deployable two-tier runtime.

Local tier: a trained surrogate classifier (replicated, cheap).
Remote tier: a sharded in-framework model of any assigned architecture
(``--remote-arch``), reached through the fault-aware ``repro.runtime``
transport (windows / retries / circuit breaker) with a content-keyed
response cache. The 1st-level supervisor escalates the lowest-confidence
requests; the 2nd-level supervisor filters untrusted remote predictions
(fallback). Prints the paper's cost/latency accounting plus transport,
cache, controller and per-request policy telemetry.

The serving surface is ONE object (DESIGN.md §8): the driver builds a
single ``repro.serving.ServeConfig`` and every runtime component — the
engine, scheduler, remote registry/router, budget controller and cache —
is constructed from it. The per-knob CLI flags of earlier PRs are gone;
any ``ServeConfig`` field (including nested ``transport.*``, ``cost.*``
and ``default_policy.*`` fields) is set with a repeatable

    --set key=value

override (migration table in DESIGN.md §8), e.g.::

    --set pipeline_depth=8 --set completion_mode=streaming \
    --set transport.timeout_s=1.0 --set route_policy=weighted \
    --set remotes=cheap:0.002:0.4;fast:0.008:0.1 \
    --set default_policy.deadline_s=0.5 --set packing=policy

An N-tier ladder (DESIGN.md §13) replaces the flat registry: the tier
specs chain into one routed ``CascadeStage`` head — each hop answers
what its supervisor trusts and escalates the residual, e.g.::

    --set "tiers=edge:0.001:0.1:0.6;cloud:0.0048:0.8"

Workload-level knobs keep first-class flags:
  --remote-budget   target remote fraction (capacity / controller target)
  --fpr             2nd-level supervisor nominal false-alarm rate
  --adaptive        enable the online budget controller (EMA/PID + drift)
  --calibrate       offline Pareto sweep picking (t_local, t_remote, k)
  --fused           bypass the transport: seed-style fully-jitted cascade

Observability (DESIGN.md §9): ``--metrics-dump`` / ``--metrics-interval``
snapshot the metrics registry (JSON or Prometheus text by extension),
``--metrics-port`` serves the LIVE registry over HTTP while the loop
runs (``GET /metrics`` Prometheus text, ``/metrics.json`` snapshot,
``/healthz``), ``--trace`` writes per-request span timelines as JSONL
and ``--trace-chrome`` exports Chrome ``trace_event`` JSON for perfetto.
Any of these implies ``observability=True`` on the ``ServeConfig``.

Without ``--smoke`` the remote tier runs at the architecture's published
widths (Yi-6B: 12.1 GB of bf16 parameters, one TPU v5e). On a CPU use
``--smoke`` (reduced remote config); ``chip_smoke.py`` at the repo root
drives this module on the chip. Every shape the serve loop dispatches is
compiled before the timed loop: the remote forward runs in fixed windows
of ``transport.max_in_flight`` rows, and the engine's gated local step is
warmed on a full batch.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --remote-arch yi-6b \
        --smoke --requests 256 --remote-budget 0.3 --adaptive --calibrate \
        --set pipeline_depth=4 --set completion_mode=streaming
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import threading
import time
from collections import Counter
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.thresholds import nominal_quantile_threshold
from repro.data.synthetic import make_classification_task
from repro.launch.compile_cache import enable_compile_cache
from repro.models import surrogate as S
from repro.models import transformer as T
from repro.runtime import calibrate, content_key, content_keys
from repro.serving import Request, ServeConfig
from repro.serving.engine import CostModel
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state


def train_surrogate(cfg, toks, labels, steps=60, lr=3e-3, seed=0):
    params = S.init_params(cfg, jax.random.PRNGKey(seed))
    opt = init_opt_state(params)
    ocfg = AdamWConfig(lr=lr, warmup_steps=5, weight_decay=0.0)

    @jax.jit
    def step(p, o, tk, lb):
        (l, m), g = jax.value_and_grad(
            lambda p: S.loss_fn(cfg, p, tk, lb, jax.random.PRNGKey(1)),
            has_aux=True)(p)
        p, o, _ = adamw_update(ocfg, p, g, o)
        return p, o, l

    for i in range(steps):
        params, opt, loss = step(params, opt, toks, labels)
    return params, float(loss)


@dataclass
class ServeResult:
    """What one ``run`` served. Holds host values only, no device arrays,
    so the model it served is freed once ``run`` returns."""
    responses: list
    wall_s: float               # timed serve loop
    compile_s: float            # warm-up: every shape the loop dispatches
    init_s: float               # remote parameter init, its compile included
    pallas_gate: bool           # compiled local step holds a Pallas kernel
    gate_emits: int = 0         # early-emit callbacks that landed
    # transport faults summed over backends (+ unrouted windows): a
    # healthy run has all zero; fallbacks from the 2nd supervisor are not
    # faults and are not counted here
    faults: dict = field(default_factory=dict)


def _fault_counts(router) -> dict:
    out = dict.fromkeys(("errors", "timeouts", "short_circuited",
                         "breaker_opens", "failed_requests"), 0)
    if router is None:
        return out
    for b in router:
        for k in out:
            out[k] += getattr(b.stats, k)
    out["unrouted"] = router.stats.unrouted
    return out


def build_serve_config(args) -> ServeConfig:
    """One ``ServeConfig`` from the CLI: first-class workload flags, then
    the repeatable ``--set key=value`` field overrides (DESIGN.md §8)."""
    cfg = ServeConfig(
        batch_size=args.batch,
        remote_fraction_budget=args.remote_budget,
        target_rejection_rate=args.fpr,
        adaptive=args.adaptive,
        fused=args.fused,
        cost=CostModel())
    return cfg.with_overrides(args.set or [])


def _serve_cluster(args, cfg, router, local_apply, toks, local_toks,
                   labels, rcfg, ncls,
                   compile_s: float) -> tuple[list, float, float, str]:
    """Replicated serving (DESIGN.md §12): ``cfg.replicas`` engines
    behind one logical cascade — one shared router, a single-fill
    shared response cache and a cluster budget reconciler re-weighting
    per-replica targets. Requests round-robin across replicas. Returns
    the responses, the loop's wall seconds, ``compile_s`` plus the
    replicas' warm-up, and a replica's compiled local step (HLO text)."""
    from repro.runtime.cluster import ClusterHarness

    harness = ClusterHarness(
        cfg, local_apply, transport=router, fallback=lambda r: -1,
        clock=time.perf_counter, reconcile_interval_s=1.0,
        cache_key_fn=lambda row: content_key(row["tokens"]),
        cache_key_batch_fn=lambda b, n: content_keys(b["tokens"], n))
    names = harness.names
    hlo = ""
    t_compile = time.perf_counter()
    for name in names:
        hlo = harness.replica(name).engine.warmup(
            local_toks[:cfg.batch_size], ncls)
    compile_s += time.perf_counter() - t_compile
    print(f"[serve] compile: {compile_s:.3f}s (every program the serve "
          f"loop dispatches, first call each, {cfg.replicas} replicas)")
    print(f"[serve] cluster: {cfg.replicas} replicas {names}, shared "
          f"cache {'on' if harness.shared_cache is not None else 'off'}, "
          f"reconcile every {harness.reconcile_interval_s:.1f}s")

    # the fleet shares ONE MetricsRegistry (replica-labelled series), so
    # the live scrape endpoint and the interval pump serve the merged
    # snapshot directly — no per-replica aggregation pass needed
    metrics_server = None
    if harness.metrics is not None and args.metrics_port is not None:
        from repro.runtime.observability import MetricsServer
        metrics_server = MetricsServer(harness.metrics,
                                       port=args.metrics_port)
        print(f"[serve] metrics endpoint: {metrics_server.url} "
              f"(merged fleet registry)")
    stop_pump = threading.Event()

    def pump():
        while not stop_pump.wait(args.metrics_interval):
            c = harness.metrics.snapshot()["counters"]
            print(f"[serve] fleet metrics: "
                  f"{c.get('cascade_requests_total', 0):.0f} requests, "
                  f"{c.get('cascade_escalations_total', 0):.0f} "
                  f"escalated, "
                  f"${c.get('cascade_cost_dollars_total', 0.0):.4f}")

    pump_thread = None
    if harness.metrics is not None and args.metrics_interval:
        pump_thread = threading.Thread(target=pump, daemon=True)
        pump_thread.start()

    t0 = time.perf_counter()
    responses = []
    flush_every = max(cfg.batch_size, 1) * len(names)
    try:
        for i in range(args.requests):
            shed = harness.submit(names[i % len(names)], Request(
                uid=i, local_input=local_toks[i],
                remote_input={"tokens": toks[i] % rcfg.vocab_size,
                              "idx": np.int32(i)}))
            if shed is not None:
                responses.append(shed)
            if (i + 1) % flush_every == 0:
                for batch in harness.flush().values():
                    responses.extend(batch)
        for batch in harness.flush().values():
            responses.extend(batch)
        # short runs can finish inside one cadence interval: force a
        # final reconcile so the budget summary below is always live
        harness.cluster.reconcile(time.perf_counter())
    finally:
        harness.close()
        if pump_thread is not None:
            stop_pump.set()
            pump_thread.join(timeout=5.0)
        if metrics_server is not None:
            metrics_server.close()
    wall = time.perf_counter() - t0

    correct = sum(r.prediction == labels[r.uid] for r in responses
                  if r.source != "fallback")
    nfall = sum(r.source == "fallback" for r in responses)
    print(f"[serve] cluster: {len(responses)} responses in "
          f"{wall:.1f}s wall "
          f"({len(responses) / max(wall, 1e-9):.0f} req/s)")
    print(f"[serve] accepted accuracy: "
          f"{correct / max(len(responses) - nfall, 1):.3f}")
    for name in names:
        rep = harness.replica(name)
        st, ad = rep.engine.stats, rep.scheduler.admission
        line = (f"[serve]   {name}: {st.requests} requests, remote "
                f"fraction {st.remote_fraction:.2f} "
                f"(target {harness.cluster.target(name):.2f}), "
                f"shed {ad.shed}, degraded {ad.degraded}")
        if rep.cache is not None:
            line += (f", cache {rep.cache.stats.hits} hits "
                     f"({rep.cache.stats.cross_hits} cross-replica)")
        print(line)
    b = harness.global_billing()["billing"]
    print(f"[serve] cluster billing: {b['requests']} requests, "
          f"{b['escalations']} escalations, {b['remote_calls']} remote "
          f"calls, {b['cache_hits']} cache hits, "
          f"${b['total_cost']:.4f} total")
    cst = harness.cluster.state
    gt = cst.global_target
    gf = cst.global_ema_fraction
    print(f"[serve] cluster budget: {cst.reconciles} reconciles "
          f"(mode {cst.mode}), global target "
          f"{'n/a' if gt is None else f'{gt:.3f}'}, realised fleet "
          f"fraction {'n/a' if gf is None else f'{gf:.3f}'}, "
          f"stale {list(cst.stale)}")
    if harness.shared_cache is not None:
        scs = harness.shared_cache.stats
        print(f"[serve] shared cache: {scs.fills} fills, "
              f"{scs.duplicate_fills} duplicate fills, "
              f"{scs.waits} waits, {scs.steals} steals "
              f"({len(harness.shared_cache)} entries)")
    if harness.events is not None:
        evc = harness.events.counts()
        if evc:
            print(f"[serve] events: {dict(sorted(evc.items()))}")
    if harness.metrics is not None and args.metrics_dump:
        if args.metrics_dump.endswith(".json"):
            text = json.dumps(harness.metrics.snapshot(), indent=2,
                              sort_keys=True) + "\n"
        else:
            text = harness.metrics.render_prometheus()
        with open(args.metrics_dump, "w") as f:
            f.write(text)
        print(f"[serve] wrote metrics snapshot -> {args.metrics_dump}")
    return responses, wall, compile_s, hlo


def run(argv=None) -> ServeResult:
    """Parse the CLI, build the cascade, serve ``--requests`` requests and
    print the accounting; ``main`` is this plus an exit code."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--remote-arch", default="yi-6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--remote-budget", type=float, default=0.3,
                    help="capacity fraction escalated to the remote tier")
    ap.add_argument("--fpr", type=float, default=0.05,
                    help="2nd-level supervisor nominal false-alarm rate")
    ap.add_argument("--fused", action="store_true",
                    help="seed-style fully-jitted cascade (no transport)")
    ap.add_argument("--adaptive", action="store_true",
                    help="online EMA/PID budget controller")
    ap.add_argument("--calibrate", action="store_true",
                    help="offline Pareto sweep for (t_local, t_remote, k)")
    ap.add_argument("--set", action="append", default=None,
                    metavar="KEY=VALUE",
                    help="ServeConfig field override, repeatable — any "
                         "field incl. nested transport.* / cost.* / "
                         "default_policy.* (DESIGN.md §8 migration "
                         "table), e.g. --set pipeline_depth=8 "
                         "--set default_policy.deadline_s=0.5")
    ap.add_argument("--metrics-dump", metavar="PATH",
                    help="write the final metrics snapshot here: JSON "
                         "for *.json, Prometheus exposition text "
                         "otherwise (implies observability)")
    ap.add_argument("--metrics-interval", type=float, metavar="S",
                    help="re-dump/print metrics every S seconds while "
                         "serving (implies observability)")
    ap.add_argument("--metrics-port", type=int, metavar="PORT",
                    help="serve the live metrics registry over HTTP on "
                         "this port (GET /metrics = Prometheus text, "
                         "/metrics.json = JSON snapshot, /healthz; 0 = "
                         "ephemeral; implies observability)")
    ap.add_argument("--trace", metavar="PATH",
                    help="write per-request span timelines as JSONL "
                         "(implies observability)")
    ap.add_argument("--trace-chrome", metavar="PATH",
                    help="write Chrome trace_event JSON for perfetto / "
                         "chrome://tracing (implies observability)")
    args = ap.parse_args(argv)
    want_obs = (args.metrics_dump or args.metrics_interval
                or args.metrics_port is not None
                or args.trace or args.trace_chrome)
    try:
        cfg = build_serve_config(args)
        if want_obs:
            if cfg.fused:
                ap.error("--metrics-dump/--metrics-interval/--trace "
                         "require the transport path (not --fused)")
            cfg = dataclasses.replace(cfg, observability=True)
    except ValueError as e:
        ap.error(str(e))
    if (cfg.cost_budget is not None and not cfg.adaptive
            and not args.calibrate):
        ap.error("cost_budget is only enforced by the controller or the "
                 "offline sweep; add --adaptive and/or --calibrate")
    if cfg.replicas > 1 and (args.trace or args.trace_chrome):
        ap.error("replicas>1 supports the metrics surface "
                 "(--metrics-dump / --metrics-interval / --metrics-port "
                 "serve the merged fleet registry, replica-labelled); "
                 "per-replica tracing is a follow-on (DESIGN.md §12)")
    enable_compile_cache()
    devs = jax.devices()
    print(f"[serve] devices: {len(devs)} x {devs[0].platform} "
          f"({devs[0].device_kind})")

    # ---- task + local surrogate (paper §4.1: input-domain-reduced) ----
    # rows [0, requests) are served; the surrogate trains on the next 512
    # and both tiers are calibrated on the 128 after those, so neither the
    # training nor the calibration slice overlaps what is served
    vocab, seq, ncls = 512, 48, 8
    train = slice(args.requests, args.requests + 512)
    val = slice(train.stop, train.stop + 128)
    toks, labels, _ = make_classification_task(
        1, n=val.stop, vocab=vocab, seq_len=seq, num_classes=ncls)
    scfg = S.SurrogateConfig("local", vocab_size=vocab // 4, max_len=seq // 2,
                             d_model=32, num_heads=2, d_ff=32,
                             num_classes=ncls, dropout=0.0)
    # input-domain reduction: clipped seq, folded vocab
    local_toks = (toks[:, : seq // 2] % (vocab // 4)).astype(np.int32)
    sparams, sloss = train_surrogate(scfg, jnp.asarray(local_toks[train]),
                                     jnp.asarray(labels[train]))
    print(f"[serve] local surrogate trained (final loss {sloss:.3f})")

    # ---- remote tier: a sharded in-framework model ----
    rcfg = get_config(args.remote_arch)
    if args.smoke:
        rcfg = rcfg.reduced()
    # jitted: an eager init would draw each stacked weight in f32 before
    # the cast, several GB per leaf at full width
    t_init = time.perf_counter()
    rparams = jax.block_until_ready(jax.jit(
        lambda k: T.init_params(rcfg, k))(jax.random.PRNGKey(7)))
    init_s = time.perf_counter() - t_init
    print(f"[serve] remote tier {rcfg.name} on {len(devs)} device(s); "
          f"init {init_s:.3f}s (jitted, compile included)")

    # the remote model consumes the FULL input (no domain reduction); its
    # last-position hidden is decoded by a task head. For the demo the head
    # is an oracle readout so the remote tier is accurate (stands in for a
    # GPT-3-quality model, as in the paper's case studies).
    oracle = jax.nn.one_hot(jnp.asarray(labels), ncls) * 8.0
    window = max(1, cfg.transport.max_in_flight)

    @jax.jit
    def remote_forward(params, toks_full, idx):
        logits, _ = T.prefill(rcfg, params, {"tokens": toks_full})
        # project LM logits to task classes via oracle head (+ tiny noise
        # from the real hidden state so confidences vary per input)
        jitter = 0.01 * logits[:, :ncls].astype(jnp.float32)
        return oracle[idx] + jitter

    def remote_apply(batch):
        # whole windows of `window` rows (the tail padded with its last
        # row): one compiled shape serves every call the transport makes
        toks_full = np.asarray(batch["tokens"])
        idx = np.asarray(batch["idx"])
        n = toks_full.shape[0]
        rows = np.r_[np.arange(n), np.full((-n) % window, n - 1)]
        outs = [remote_forward(rparams, toks_full[rows[lo:lo + window]],
                               idx[rows[lo:lo + window]])
                for lo in range(0, rows.size, window)]
        return np.concatenate(jax.device_get(outs))[:n]

    t_compile = time.perf_counter()
    jax.block_until_ready(remote_apply(
        {"tokens": toks[:1] % rcfg.vocab_size, "idx": np.arange(1)}))
    compile_s = time.perf_counter() - t_compile

    def local_apply(tk):
        return S.apply(scfg, sparams, tk)

    # an explicit --set t_remote/t_local always wins over the computed
    # thresholds below ("any ServeConfig field is settable" must hold)
    user_set = {item.partition("=")[0].strip() for item in (args.set or [])}

    # ---- 2nd-level threshold: nominal-quantile calibration (§4.5) ----
    cal_logits = np.asarray(remote_apply(
        {"tokens": jnp.asarray(toks[val] % rcfg.vocab_size),
         "idx": jnp.arange(val.start, val.stop)}))
    cal_conf = np.max(
        np.exp(cal_logits) / np.exp(cal_logits).sum(-1, keepdims=True), -1)
    if "t_remote" not in user_set:
        cfg = dataclasses.replace(
            cfg, t_remote=nominal_quantile_threshold(cal_conf, args.fpr))

    # ---- remote registry / cache from the one ServeConfig ----
    router = cache = None
    if not cfg.fused:
        router = cfg.build_router(remote_apply)
        print(f"[serve] remote registry: "
              f"{[b.name for b in router.candidates()]} "
              f"(policy {router.policy})")
        if cfg.tiers:
            head = router.candidates()[0]
            print("[serve] tier ladder: " + " -> ".join(
                f"{s.name}(t={s.threshold:g})" for s in head.chain()))
        # key on token content only: the per-request "idx" (oracle-head
        # plumbing) would make every key unique and the cache cold
        cache = cfg.build_cache(
            key_fn=lambda row: content_key(row["tokens"]),
            key_batch_fn=lambda batch, n: content_keys(batch["tokens"], n))

    if args.calibrate:
        # offline Pareto sweep on a labelled validation slice (DESIGN.md §1)
        # — priced at the policy-preferred backend's per-call cost when a
        # registry is configured, selected by $ when cost_budget is set
        val_logits = np.asarray(local_apply(jnp.asarray(local_toks[val])))
        val_sm = np.exp(val_logits) / np.exp(val_logits).sum(-1, keepdims=1)
        esc_cost = (cfg.cost or CostModel()).remote_cost_per_request
        if router is not None:
            esc_cost = router.expected_cost_per_escalation(esc_cost)
        point, k, front = calibrate(
            local_conf=val_sm.max(-1),
            local_correct=val_logits.argmax(-1) == labels[val],
            remote_conf=cal_conf,
            remote_correct=cal_logits.argmax(-1) == labels[val],
            budget=(None if cfg.cost_budget is not None
                    else cfg.remote_fraction_budget),
            cost_budget=cfg.cost_budget, batch_size=cfg.batch_size,
            max_rejection_rate=args.fpr, remote_cost_per_request=esc_cost)
        cal_updates = {}
        if "t_local" not in user_set:
            cal_updates["t_local"] = point.t_local
        if "t_remote" not in user_set:
            cal_updates["t_remote"] = point.t_remote
        cfg = dataclasses.replace(cfg, **cal_updates)
        print(f"[serve] calibrated operating point: "
              f"t_local={point.t_local:.4f} "
              f"t_remote={point.t_remote:.4f} k={k} "
              f"(val remote fraction {point.remote_fraction:.2f}, "
              f"${point.cost_per_request:.5f}/req, "
              f"accepted acc {point.accuracy:.3f}; "
              f"frontier has {len(front)} points)")

    # ---- replicated serving: N engines, one logical cascade ----
    if cfg.replicas > 1:
        responses, wall, compile_s, hlo = _serve_cluster(
            args, cfg, router, local_apply, toks, local_toks, labels, rcfg,
            ncls, compile_s)
        return ServeResult(responses, wall, compile_s, init_s,
                           "tpu_custom_call" in hlo,
                           faults=_fault_counts(router))

    # ---- the whole serving stack from the one ServeConfig ----
    warm = slice(0, cfg.batch_size)
    remote_warm = None
    if cfg.fused:
        # the fused step calls the remote tier inside its own jit
        eng, sched = cfg.build(
            local_apply,
            lambda b: remote_forward(rparams, b["tokens"], b["idx"]),
            fallback=lambda r: -1)
        remote_warm = {"tokens": toks[warm] % rcfg.vocab_size,
                       "idx": np.arange(cfg.batch_size, dtype=np.int32)}
    else:
        eng, sched = cfg.build(local_apply, transport=router, cache=cache,
                               fallback=lambda r: -1)
    t_compile = time.perf_counter()
    hlo = eng.warmup(local_toks[warm], ncls, remote_warm)
    compile_s += time.perf_counter() - t_compile
    # the fused step scores in jnp: a custom call there is the remote tier's
    pallas_gate = not cfg.fused and "tpu_custom_call" in hlo
    print(f"[serve] compile: {compile_s:.3f}s (every program the serve "
          f"loop dispatches, first call each); local gate "
          f"{'Pallas' if pallas_gate else 'jnp reference'}")

    obs = eng.observability

    def dump_metrics(path):
        # JSON snapshot for *.json, Prometheus exposition text otherwise
        if path.endswith(".json"):
            text = json.dumps(obs.metrics.snapshot(), indent=2,
                              sort_keys=True) + "\n"
        else:
            text = obs.metrics.render_prometheus()
        with open(path, "w") as f:
            f.write(text)

    stop_pump = threading.Event()

    def pump():
        while not stop_pump.wait(args.metrics_interval):
            if args.metrics_dump:
                dump_metrics(args.metrics_dump)
            else:
                c = obs.metrics.snapshot()["counters"]
                print(f"[serve] metrics: "
                      f"{c.get('cascade_requests_total', 0):.0f} requests, "
                      f"{c.get('cascade_escalations_total', 0):.0f} "
                      f"escalated, "
                      f"${c.get('cascade_cost_dollars_total', 0.0):.4f}")

    pump_thread = None
    if obs is not None and args.metrics_interval:
        pump_thread = threading.Thread(target=pump, daemon=True)
        pump_thread.start()

    # live HTTP scrape endpoint (DESIGN.md §9): Prometheus polls the
    # registry while the serve loop runs, no file dumps required
    metrics_server = None
    if obs is not None and args.metrics_port is not None:
        from repro.runtime.observability import MetricsServer
        metrics_server = MetricsServer(obs.metrics, port=args.metrics_port)
        print(f"[serve] metrics endpoint: {metrics_server.url}")

    t0 = time.perf_counter()
    try:
        for i in range(args.requests):
            sched.submit(Request(
                uid=i, local_input=local_toks[i],
                remote_input={"tokens": toks[i] % rcfg.vocab_size,
                              "idx": np.int32(i)}))
        responses = sched.flush()
    finally:
        eng.close()     # drain windows + shut down every backend pool
        if pump_thread is not None:
            stop_pump.set()
            pump_thread.join(timeout=5.0)
        if metrics_server is not None:
            metrics_server.close()
    wall = time.perf_counter() - t0

    correct = sum(r.prediction == labels[r.uid] for r in responses
                  if r.source != "fallback")
    srcs = {s: sum(r.source == s for r in responses)
            for s in ("local", "remote", "fallback")}
    st = eng.stats
    print(f"[serve] {len(responses)} requests in {wall:.1f}s wall")
    print(f"[serve] routing: {srcs}")
    print(f"[serve] dispositions: "
          f"{dict(Counter(r.disposition for r in responses))}")
    print(f"[serve] accepted accuracy: "
          f"{correct / max(len(responses) - srcs['fallback'], 1):.3f}")
    print(f"[serve] remote fraction: {st.remote_fraction:.2f} "
          f"(budget {cfg.remote_fraction_budget})")
    print(f"[serve] modelled cost: ${st.total_cost:.4f} "
          f"(${st.total_cost / max(st.requests, 1):.5f}/req; remote-only "
          f"would be ${st.requests * eng.cost.remote_cost_per_request:.4f})")
    if st.mean_latency_s is not None:
        print(f"[serve] modelled mean latency: "
              f"{st.mean_latency_s * 1e3:.0f} ms "
              f"(remote-only {eng.cost.remote_latency_s * 1e3:.0f} ms)")
    p50, p95 = st.wall_percentile(50), st.wall_percentile(95)
    if p50 is not None:
        print(f"[serve] measured wall latency: "
              f"p50 {p50 * 1e3:.0f} ms, p95 {p95 * 1e3:.0f} ms "
              f"(throughput {len(responses) / max(wall, 1e-9):.0f} req/s, "
              f"pipeline depth {cfg.pipeline_depth}, "
              f"completion mode {cfg.completion_mode})")
    # per-request hand-back latency, split trusted-local vs escalated
    # (the streaming mode's value proposition — DESIGN.md §7)
    if sched.first_response_s is not None:
        print(f"[serve] first response: "
              f"{sched.first_response_s * 1e3:.0f} ms after flush start")
    lat_local = [r.latency_s for r in responses if r.source == "local"]
    lat_esc = [r.latency_s for r in responses if r.source != "local"]
    for tag, lat in (("trusted-local", lat_local), ("escalated", lat_esc)):
        if lat:
            print(f"[serve] {tag} hand-back latency: "
                  f"p50 {np.percentile(lat, 50) * 1e3:.0f} ms, "
                  f"p95 {np.percentile(lat, 95) * 1e3:.0f} ms "
                  f"({len(lat)} requests)")
    if cfg.packing != "none":
        ps = sched.packing_stats
        pure = ps["cold"] + ps["hot"]
        print(f"[serve] window packing: {ps} "
              f"(purity {pure / max(ps['windows'], 1):.2f})")
    if cfg.admission_limit:
        ad = sched.admission
        print(f"[serve] admission: {ad.submitted} submitted, "
              f"{ad.shed} shed {ad.shed_reasons}, "
              f"{ad.degraded} degraded {ad.degrade_reasons} "
              f"(queue limit {sched.admission_limit}, "
              f"soft {sched.admission_soft})")
    if router is not None:
        rs = router.stats
        print(f"[serve] router: picks {rs.picks}, "
              f"failovers {rs.failovers}, unrouted {rs.unrouted}, "
              f"replays {rs.replay_served}/{rs.replay_enqueued} served")
        for b in router:
            ts, u = b.stats, st.per_backend.get(b.name)
            p95r = ts.latency_percentile(95)
            line = (f"[serve]   {b.name}: {ts.windows} windows, "
                    f"{ts.failed_requests} failed reqs, "
                    f"{ts.retries} retries, "
                    f"breaker opens {ts.breaker_opens}, "
                    f"p95 remote "
                    f"{'n/a' if p95r is None else f'{p95r * 1e3:.0f} ms'}")
            if u is not None:
                line += (f"; billed ${u.cost:.4f} "
                         f"({u.remote_calls} calls, {u.cache_hits} hits, "
                         f"{u.transport_failures} failures)")
            print(line)
    if eng.cache is not None:
        hr = eng.cache.stats.hit_rate
        print(f"[serve] cache: {eng.cache.stats.hits} hits / "
              f"{eng.cache.stats.misses} misses "
              f"(hit rate {'n/a' if hr is None else f'{hr:.2f}'})")
    if eng.controller is not None:
        cs = eng.controller.state
        print(f"[serve] controller: {cs.windows} windows, "
              f"ema remote fraction {cs.ema_fraction:.3f}, "
              f"t_local={cs.t_local}, t_remote={cs.t_remote}, "
              f"{cs.drift_events} drift events")
        if cfg.cost_budget is not None:
            per_esc = cs.ema_cost_per_escalation
            print(f"[serve] dollar budget: target "
                  f"${cfg.cost_budget:.5f}/req, realised "
                  f"${st.total_cost / max(st.requests, 1):.5f}/req "
                  f"(learned $/escalation "
                  f"{'n/a' if per_esc is None else f'{per_esc:.5f}'}, "
                  f"effective target fraction {cs.effective_target})")
    if obs is not None:
        evc = obs.events.counts()
        if evc:
            drop = (f" ({obs.events.dropped} dropped)"
                    if obs.events.dropped else "")
            print(f"[serve] events: {dict(sorted(evc.items()))}{drop}")
        if obs.trace is not None and obs.trace.dropped:
            print(f"[serve] trace: {obs.trace.dropped} spans dropped "
                  f"(capacity {obs.trace.capacity})")
        if args.trace:
            n = obs.trace.write_jsonl(args.trace)
            print(f"[serve] wrote {n} spans -> {args.trace}")
        if args.trace_chrome:
            n = obs.trace.write_chrome_trace(args.trace_chrome)
            print(f"[serve] wrote {n} trace events -> {args.trace_chrome}")
        if args.metrics_dump:
            dump_metrics(args.metrics_dump)
            print(f"[serve] wrote metrics snapshot -> {args.metrics_dump}")
    return ServeResult(responses, wall, compile_s, init_s, pallas_gate,
                       gate_emits=eng._gate_emits,
                       faults=_fault_counts(router))


def main(argv=None) -> int:
    run(argv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
