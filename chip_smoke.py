#!/usr/bin/env python3
"""Bring-up smoke of the BiSupervised serve path on one TPU chip.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # the data-parallel path, four chips

One process holds the chip and runs, in order:

1. Kernel parity on the chip: ``confidence_gate`` at the serve shape
   (``[32, 8]`` logits, padded to 128 classes) and at vocabulary width
   (``[32, 64000]``), and ``fused_head_gate`` at ``[32, 4096] x
   [4096, 64000]`` in bf16, each against its ``ref.py`` oracle: ``pred``
   and ``idx`` bitwise, ``conf`` within 1e-4 of ``max(1, |ref|)``.
2. ``repro.launch.serve`` through its normal entry point with the remote
   tier at full Yi-6B width (``--remote-arch yi-6b --requests 256
   --batch 32 --calibrate``, window batching). Every request must be
   answered, the compiled local step must hold the Pallas gate
   (``tpu_custom_call``), and the transport must report no error,
   timeout, breaker opening or unrouted window. Fallbacks from the
   second supervisor are answers, not faults.
3. The same with ``batching=continuous`` and ``completion_mode=streaming``,
   the configuration that arms the gate's early-emit callback: callbacks
   must land and every prediction must equal phase 2's.

``--chips 4`` runs only the data-parallel comparison: the same serve
command (remote tier cut with ``--smoke``; the local gate is what is
compared) once with ``data_parallel=True`` over every device and once on
one device, requiring identical predictions, escalation sets and
dispositions.

Earlier lines report device kind, peak HBM, remote init, compile and
serve seconds.
The last line of standard output is one JSON object, printed only when
every phase passed. Without a TPU the script exits non-zero before any
work.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

SERVE_ARGS = ["--remote-arch", "yi-6b", "--requests", "256", "--batch", "32",
              "--calibrate"]
STREAMING = ["--set", "batching=continuous",
             "--set", "completion_mode=streaming"]


class SmokeFailure(Exception):
    """A phase produced a wrong or incomplete result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX reports {devs[0].platform!r} "
                           f"devices; this smoke runs only on the chip")
    return devs


def peak_hbm_gb(dev) -> float:
    return dev.memory_stats()["peak_bytes_in_use"] / 1e9


def kernel_parity() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.confidence_gate.ops import confidence_gate
    from repro.kernels.confidence_gate.ref import confidence_gate_ref
    from repro.kernels.fused_head_gate.ops import fused_head_gate
    from repro.kernels.fused_head_gate.ref import fused_head_gate_ref

    key = jax.random.PRNGKey(0)

    def compare(name, got, want):
        got, want = jax.device_get((got, want))
        for f in ("pred", "idx"):
            check(np.array_equal(got[f], want[f]),
                  f"{name}: {f} differs from the ref oracle")
        diff = np.abs(got["conf"] - want["conf"])
        rel = float(np.max(diff / np.maximum(np.abs(want["conf"]), 1.0)))
        check(rel <= 1e-4, f"{name}: conf off by {rel:g} (relative)")
        esc = int((got["idx"] >= 0).sum())
        print(f"[smoke] parity {name}: pred/idx bitwise, conf max |diff| "
              f"{float(diff.max()):.3g} (relative {rel:.3g}), {esc} "
              f"escalation candidates", flush=True)

    def gate_case(b, c, supervisor):
        logits = jax.random.normal(jax.random.fold_in(key, c), (b, c)) * 4.0
        want = confidence_gate_ref(logits, supervisor=supervisor)
        # threshold between two rows, never on one; the last two rows
        # stand for scheduler padding
        t = float(np.median(np.asarray(want["conf"])))
        want = confidence_gate_ref(logits, t, b - 2, supervisor=supervisor)
        got = jax.jit(lambda x: confidence_gate(
            x, t, b - 2, supervisor=supervisor))(logits)
        compare(f"confidence_gate[{b},{c}] {supervisor}", got, want)

    for sup in ("max_softmax", "pcs", "neg_entropy", "gini"):
        gate_case(32, 8, sup)
    for sup in ("max_softmax", "neg_entropy"):
        gate_case(32, 64000, sup)

    b, d, c = 32, 4096, 64000
    h = jax.random.normal(jax.random.fold_in(key, 1), (b, d), jnp.bfloat16)
    w = (jax.random.normal(jax.random.fold_in(key, 2), (d, c), jnp.bfloat16)
         / np.sqrt(d)).astype(jnp.bfloat16)
    bias = jax.random.normal(jax.random.fold_in(key, 3), (c,)) * 0.1
    with jax.default_matmul_precision("highest"):
        want = fused_head_gate_ref(h, w, bias, supervisor="max_softmax")
    t = float(np.median(np.asarray(want["conf"])))
    with jax.default_matmul_precision("highest"):
        want = fused_head_gate_ref(h, w, bias, t, b - 2,
                                   supervisor="max_softmax")
    got = jax.jit(lambda h, w, bias: fused_head_gate(
        h, w, bias, t, b - 2, supervisor="max_softmax"))(h, w, bias)
    compare(f"fused_head_gate[{b},{d}]x[{d},{c}] bf16", got, want)


def serve_once(argv: list[str], tag: str):
    from repro.launch import serve

    print(f"[smoke] {tag}: python -m repro.launch.serve {' '.join(argv)}",
          flush=True)
    res = serve.run(argv)
    gc.collect()        # drop the remote tier before the next phase
    n = int(argv[argv.index("--requests") + 1])
    check(len(res.responses) == n,
          f"{tag}: {len(res.responses)} of {n} requests answered")
    check(sorted(r.uid for r in res.responses) == list(range(n)),
          f"{tag}: answers do not cover every request once")
    bad = {k: v for k, v in res.faults.items() if v}
    check(not bad, f"{tag}: transport faults {bad}")
    check(res.pallas_gate,
          f"{tag}: no tpu_custom_call in the compiled local step")
    remote = sum(r.source != "local" for r in res.responses)
    print(f"[smoke] {tag}: {n}/{n} answered, {remote} escalated, "
          f"transport faults 0, Pallas gate in the served step, remote "
          f"init {res.init_s:.3f}s, compile {res.compile_s:.3f}s, serve "
          f"wall {res.wall_s:.3f}s",
          flush=True)
    return res


def outcome(res) -> dict:
    """Per-request outcome keyed by uid: (prediction, escalated,
    disposition)."""
    return {r.uid: (r.prediction, r.source != "local", r.disposition)
            for r in res.responses}


def compare_outcomes(a, b, what: str) -> None:
    oa, ob = outcome(a), outcome(b)
    for field, i in (("predictions", 0), ("escalation sets", 1),
                     ("dispositions", 2)):
        diff = [u for u in oa if oa[u][i] != ob[u][i]]
        check(not diff, f"{what}: {field} differ at uids {diff[:10]}")
        print(f"[smoke] {what}: {field} identical "
              f"({len(oa)} requests)", flush=True)


def one_chip(dev) -> None:
    t0 = time.perf_counter()
    kernel_parity()
    print(f"[smoke] kernel parity: {time.perf_counter() - t0:.3f}s, peak "
          f"HBM {peak_hbm_gb(dev):.3f} GB", flush=True)

    window = serve_once(SERVE_ARGS, "serve window")
    print(f"[smoke] peak HBM after window serve: {peak_hbm_gb(dev):.3f} GB "
          f"(in use now {dev.memory_stats()['bytes_in_use'] / 1e9:.3f} GB)",
          flush=True)

    stream = serve_once(SERVE_ARGS + STREAMING, "serve continuous")
    check(stream.gate_emits > 0, "continuous: no early-emit callback landed")
    print(f"[smoke] continuous: {stream.gate_emits} early-emit callbacks "
          f"landed", flush=True)
    compare_outcomes(window, stream, "window vs continuous")
    print(f"[smoke] compile seconds: window {window.compile_s:.3f}, "
          f"continuous {stream.compile_s:.3f}; serve wall seconds: window "
          f"{window.wall_s:.3f}, continuous {stream.wall_s:.3f}", flush=True)
    print(f"[smoke] peak HBM: {peak_hbm_gb(dev):.3f} GB", flush=True)


def four_chips(devs) -> None:
    check(len(devs) == 4, f"--chips 4 needs 4 devices, JAX reports "
                          f"{len(devs)}")
    argv = SERVE_ARGS + ["--smoke"]
    dp = serve_once(argv + ["--set", "data_parallel=True"],
                    "serve data-parallel x4")
    single = serve_once(argv, "serve one device")
    compare_outcomes(dp, single, "4-device data-parallel vs 1 device")
    print(f"[smoke] peak HBM device 0: {peak_hbm_gb(devs[0]):.3f} GB",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel comparison")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        devs = require_tpu()
        from repro.launch.compile_cache import enable_compile_cache
        print(f"[smoke] device: {devs[0].device_kind} x {len(devs)} "
              f"({devs[0].platform}); compile cache "
              f"{enable_compile_cache()}", flush=True)
        if args.chips == 4:
            four_chips(devs)
        else:
            one_chip(devs[0])
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
