"""One benchmark run of one cell: set-up, the measured window, the check.

    python3 chipbench/run.py --workload W --seed N --seconds S --trace 0|1

1. Set-up (``setup_s``, from process start): the configuration's weights
   drawn on the chip from the seed, the serving stack built through
   ``ServeConfig.build`` with the tier's ``FusedLocalHead``, the gated
   step and the 2nd-level scoring compiled (or loaded from the persistent
   cache) by ``CascadeEngine.warmup``, then one warm-up batch served
   through ``submit``/``flush`` on content outside the traffic.
2. The window: the traffic's open-loop schedule for ``--seconds`` (see
   ``drive.py``), then the drain. With ``--trace 1`` the profiler records
   the window and the engine's observability spans are on.
3. Peak device memory is read, the program's state is freed, and the
   check (``check.py``) compares the cascade with the modelled remote and
   the sampled windows with the plain reference.

The last line of standard output is the result object; the numbers
compared are also the last lines of standard error.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from chipbench import check, drive, spec, traffic
from chipbench.remote import ModelledRemote


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Run:
    """Everything a metric reader may read. Fields a run could not take
    (a trace in an untraced run) are None."""
    cell: spec.Cell
    seconds: float
    opened: float               # perf_counter when the window opened
    setup_s: float
    batch: int
    records: list               # requests due in the window
    windows: list               # records grouped by dispatch window
    spans: list | None = None   # engine observability spans (traced run)
    remote_windows: list = field(default_factory=list)  # seconds each
    trace: object | None = None  # chipbench.trace.Trace (traced run)
    costs: dict = field(default_factory=dict)
    peak: dict | None = None


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chip(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX reports "
                     f"{len(devs)}")
    return devs


def _log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def _compile_counter():
    import jax
    count = [0]

    def on_event(name, *_args, **_kw):
        if name.endswith("backend_compile_duration"):
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return count


def _records(plan, window) -> list[dict]:
    out = []
    waited = time.perf_counter() - window.opened
    for i, due in enumerate(plan.due):
        r = window.responses.get(i)
        rec = {"uid": i, "content": int(plan.content[i]), "due": float(due),
               "answered": r is not None}
        if r is None:
            rec.update(latency=waited - due, source="unanswered",
                       disposition="UNANSWERED")
        else:
            rec.update(source=r.source, disposition=r.disposition,
                       prediction=int(r.prediction),
                       local_conf=float(r.local_conf),
                       remote_conf=float(r.remote_conf),
                       latency=float(r.latency_s), queue=float(r.queue_s),
                       t_disp=float(due + r.queue_s),
                       handback=float(due + r.latency_s))
        out.append(rec)
    return out


def failed(records: list[dict]) -> int:
    """Requests that errored, were dropped, shed or never answered. A
    fallback the 2nd-level supervisor chose is an answer, not a failure;
    an escalation the transport lost (``remote_conf`` -inf) is one."""
    return sum((not r["answered"]) or r["source"] == "shed"
               or (r["source"] == "fallback" and r["remote_conf"] == -np.inf)
               for r in records)


def _device_block(devs, dev, peak_bytes, traced) -> dict:
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": int(peak_bytes)}
    if traced is not None:
        out["busy_s"], out["window_s"] = traced
    return out


@dataclass
class Stack:
    """The serving stack a cell runs, built as a user builds it."""
    tier: object
    remote: ModelledRemote
    scfg: object                # ServeConfig
    router: object
    cache: object
    eng: object                 # CascadeEngine
    sched: object               # Scheduler


def build_stack(cell: spec.Cell, seed: int, observability: bool,
                marks: list | None = None) -> Stack:
    """The configuration's weights drawn on the chip, then the cascade
    through ``ServeConfig.build`` with the tier's ``FusedLocalHead``, the
    modelled remote behind the program's router and cache. ``marks``
    collects ``(stage, perf_counter)`` pairs for the set-up breakdown."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.runtime import content_key, content_keys
    from repro.serving import ServeConfig

    marks = [] if marks is None else marks
    _log(f"compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    config, mix = cell.config, cell.traffic
    tier = spec.module("tiers", config["tier"]).build(config, seed)
    jax.block_until_ready(tier.params)
    marks.append(("weights", time.perf_counter()))
    remote = ModelledRemote(config["remote"], tier.vocab, seed)
    scfg = ServeConfig(**config["serve"],
                       remote_fraction_budget=mix["remote_fraction_budget"],
                       observability=observability)
    if scfg.t_local is not None:
        raise ValueError("the benchmark serves in capacity mode (t_local "
                         "unset)")
    router = scfg.build_router(remote)
    cache = scfg.build_cache(
        key_fn=lambda row: content_key(row["tokens"]),
        key_batch_fn=lambda b, n: content_keys(b["tokens"], n))
    eng, sched = scfg.build(tier.local_apply, transport=router, cache=cache,
                            fallback=lambda r: -1)
    marks.append(("stack", time.perf_counter()))
    return Stack(tier, remote, scfg, router, cache, eng, sched)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devs, control: str | None = None) -> dict:
    """One run. ``control`` (``chipbench/control.py`` only), a comma list
    of lower precisions, possibly empty, also returns the check's numbers,
    and those of the reference in each precision put in the program's
    place on the same sampled windows, each with its own verdict."""
    import jax
    from repro.serving import Request

    marks = [("start", t_start), ("imports", time.perf_counter())]
    dev = devs[0]
    config = cell.config
    st = build_stack(cell, seed, trace, marks)
    eng, sched, scfg, router = st.eng, st.sched, st.scfg, st.router
    plan = traffic.plan(cell.traffic, cell.knee["knee_rps"], st.tier.vocab,
                        st.tier.seq_len, scfg.batch_size, seconds, seed)

    hlo = eng.warmup(plan.warm, st.tier.vocab)
    pallas_gate = "tpu_custom_call" in hlo
    marks.append(("warmup", time.perf_counter()))
    warm = {}
    for j, tk in enumerate(plan.warm):
        sched.submit(Request(uid=-1 - j, local_input=tk,
                             remote_input={"tokens": tk}))
    done = threading.Event()
    done.set()
    drive.serve_until_answered(sched, len(plan.warm), done,
                               threading.Event(), warm,
                               time.perf_counter() + 120.0)
    if len(warm) != len(plan.warm):
        raise RuntimeError("the warm-up batch was not answered")
    remote_seen = len(router.candidates()[0].stats.latency_samples)
    marks.append(("warm batch", time.perf_counter()))
    setup_s = marks[-1][1] - t_start
    _log(f"set-up {setup_s:.3f}s: " + ", ".join(
        f"{name} {b - a:.3f}s" for (_, a), (name, b) in zip(marks, marks[1:]))
        + f"; {len(plan)} requests over {seconds:g}s at {plan.rate:.3f} "
        f"req/s; Pallas gate in the compiled step: {pallas_gate}")

    compiles = _compile_counter()
    before = compiles[0]
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    tokens = plan.tokens
    try:
        window = drive.open_loop(
            sched,
            lambda i, t: Request(
                uid=i, local_input=tokens[plan.content[i]],
                remote_input={"tokens": tokens[plan.content[i]]}, t_enq=t),
            plan.due, seconds, annotate=trace)
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = compiles[0] - before
    stats = dev.memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use", 0)
    spans = eng.observability.trace.spans() if trace else None
    rwin = list(router.candidates()[0].stats.latency_samples)[remote_seen:]
    eng.close()
    late = window.lateness
    _log(f"generator lateness: median {np.median(late) * 1e3:.3f} ms, p99 "
         f"{np.percentile(late, 99) * 1e3:.3f} ms, max "
         f"{late.max() * 1e3:.3f} ms; compiles inside the window: "
         f"{in_window}; peak HBM {peak_bytes / 1e9:.3f} GB")

    records = _records(plan, window)
    run = Run(cell=cell, seconds=seconds, opened=window.opened,
              setup_s=setup_s,
              batch=scfg.batch_size, records=records,
              windows=check.windows_of(records), spans=spans,
              remote_windows=rwin, costs=_costs(config, scfg),
              peak=_peak(dev))
    capacity = eng.capacity
    remote = st.remote
    del st, eng, sched, router, warm
    gc.collect()
    jax.clear_caches()
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    _log(f"program state freed: {live / 1e9:.3f} GB of device arrays left")

    traced = None
    if trace:
        run.trace = _load_trace(log_dir)
        lo, hi = run.trace.window
        from chipbench import trace as T
        busy = [T.busy_seconds(ops, lo, hi) for ops in run.trace.ops]
        traced = (float(np.mean(busy)) if busy else 0.0, float(hi - lo))

    t_check = time.perf_counter()
    numbers, checked, ctl = _check(run, config, remote, plan, capacity,
                                   seed, pallas_gate, dev.platform, control)
    ok, checks = check.verdict(numbers, config["limits"],
                               sum(not r["answered"] for r in records))
    _log(f"check: {checked} requests through the reference, "
         f"{time.perf_counter() - t_check:.3f}s")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": ok, "attempted": len(records),
              "failed": failed(records), "metrics": metrics,
              "device": _device_block(devs, dev, peak_bytes, traced)}
    if trace and run.trace.ops:
        result["breakdown"] = _breakdown(run.trace)
    if ctl is not None:
        result["numbers"] = numbers
        result["control"] = {}
        for quant, nums in ctl.items():
            c_ok, c_checks = check.control_verdict(nums, config["limits"])
            result["control"][quant] = {"correct": c_ok, "checks": c_checks}
    result["checks"] = checks
    for name, c in checks.items():
        _log(f"checked {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def _costs(config: dict, scfg) -> dict:
    """Operations and bytes of one dispatch of the gated step."""
    import jax.numpy as jnp
    from chipbench.costs import dense_decoder, fused_head_gate
    from chipbench.weights import dense_decoder as W
    sizes = config["model"]
    shapes = W.program_shapes(sizes, jnp.dtype(sizes["torch_dtype"]))
    b = scfg.batch_size
    trunk = dense_decoder.trunk_flops(shapes, sizes, b, config["seq_len"])
    w_bytes = jnp.dtype(sizes["torch_dtype"]).itemsize
    hf, hb = fused_head_gate.cost(b, sizes["hidden_size"],
                                  sizes["vocab_size"], w_bytes, w_bytes)
    return {"trunk_flops": trunk, "head_flops": hf, "head_bytes": hb}


def _peak(dev) -> dict | None:
    from chipbench.peaks import peak
    return peak(dev.device_kind) if dev.platform == "tpu" else None


def _load_trace(log_dir: str):
    import shutil
    from chipbench import trace as T
    try:
        return T.load(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def _breakdown(tr) -> dict:
    from chipbench import trace as T
    lo, hi = tr.window
    return {"device_ops": T.top_ops(tr.ops[0], lo, hi),
            "idle_gaps": T.idle_gaps(tr.ops[0], tr.host, lo, hi)}


def _check(run: Run, config: dict, remote, plan, capacity: int, seed: int,
           pallas_gate: bool, platform: str, control: str | None
           ) -> tuple[dict, int, dict | None]:
    numbers = {"cascade_mismatch": check.cascade_mismatch(
        run.records, run.windows, capacity, remote, plan.tokens,
        config["serve"]["t_remote"]),
               "escalation_order": check.escalation_order(run.windows)}
    sample = check.sample_windows(run.windows, config["check"]["windows"],
                                  seed)
    rows = [r for w in sample for r in w]
    ref = spec.module("references", config["reference"])
    tokens = plan.tokens[[r["content"] for r in rows]]
    logits = ref.logits(config["model"], seed, tokens)
    numbers.update(check.reference_numbers(sample, logits))
    numbers["pallas_gate_missing"] = int(platform == "tpu"
                                         and not pallas_gate)
    ctl = None
    if control is not None:
        ctl = {}
        for quant in filter(None, control.split(",")):
            low = ref.logits(config["model"], seed, tokens, quant=quant)
            ctl[quant] = check.reference_numbers(
                check.as_served(sample, low, capacity), logits)
    return numbers, len(rows), ctl


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    cell = spec.cell(args.workload)
    try:
        devs = require_chip(cell.workload["chips"])
    except NoChip as e:
        _log(f"FAILED: {e}")
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start, devs)
    print(json.dumps(result), flush=True)
    return 0
