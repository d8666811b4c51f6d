"""Pallas-kernel microbenchmarks.

On a CPU the kernels dispatch to their jnp reference path (the Pallas
bodies are validated in interpret mode by tests/test_kernels.py);
the numbers here time the REFERENCE path at serving-relevant shapes and
derive the kernels' arithmetic intensity — the quantity the BlockSpec
tiling was designed around (see kernels/*/kernel.py docstrings).

The confidence-gate family (ISSUE 8) is benched in three forms at the
same serving shapes: the plain gate over precomputed logits, the gate
with the in-kernel early-emit host callback armed, and the fused local
head -> gate path (``fused_head_gate``) that composes the final
projection with gate scoring so full-vocab logits never round-trip
through HBM. The ``checks`` dict verifies fused-vs-composed parity,
interpret-mode Pallas parity and that the early-emit callback actually
fires from inside jit — so the bench gate catches functional breakage,
not just slowdowns.

Machine-readable results go to ``BENCH_kernels.json``
(``{"rows": [...], "checks": {...}}``) and are gated across PRs by
``benchmarks/check_regression.py --kernels``.

    PYTHONPATH=src python -m benchmarks.kernels_bench \
        [--json BENCH_kernels.json]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.confidence_gate.ops import confidence_gate
from repro.kernels.confidence_gate.ref import confidence_gate_ref
from repro.kernels.decode_attention.ops import decode_attn
from repro.kernels.flash_attention.ops import attention
from repro.kernels.fused_head_gate.ops import fused_head_gate
from repro.kernels.fused_head_gate.ref import fused_head_gate_ref
from repro.kernels.maxconf.ops import maxconf
from repro.kernels.mdsa.ops import mdsa_distance
from repro.kernels.rwkv6_scan.ops import rwkv6_time_mix_scan
from repro.serving.engine import make_gated_local_step


def _time(fn, *args, iters=3, **kw):
    jax.block_until_ready(fn(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args, **kw))
    return (time.perf_counter() - t0) / iters


def _gate_rows(key) -> list[dict]:
    """Confidence-gate family at serving shapes (ISSUE 8).

    The fused rows time hidden@W + gate in ONE call; the `AI` column is
    the fused path's arithmetic intensity (the matmul flops over the
    hidden + weight traffic — the logits [b,v] never hit HBM), which is
    the quantity the fusion exists to raise: gate-only AI is O(1)."""
    rows = []
    for b, v in ((32, 8_192), (64, 102_400)):
        lg = jax.random.normal(key, (b, v), jnp.float32)
        us = _time(confidence_gate, lg, 0.5, supervisor="max_softmax",
                   k=b) * 1e6
        # softmax + max + threshold select ~ 6 passes over the logits
        rows.append({"kernel": "confidence_gate", "shape": f"[{b},{v}]",
                     "us_per_call": us,
                     "arith_intensity": 6 * b * v / (4 * b * v)})

        # the served gated step over the same logits with the early-emit
        # host callback armed: the row prices the io_callback tax paid
        # per dispatch in continuous batching (engine hands trusted rows
        # back at gate time)
        fired = []
        step = jax.jit(make_gated_local_step(
            lambda x: x, emit=lambda *a: fired.append(a)))
        us = _time(step, lg, 0.5, b, 0) * 1e6
        rows.append({"kernel": "confidence_gate_emit",
                     "shape": f"[{b},{v}]", "us_per_call": us,
                     "arith_intensity": 6 * b * v / (4 * b * v)})

    for b, d, v in ((32, 1_024, 8_192), (32, 1_024, 102_400)):
        h = jax.random.normal(key, (b, d), jnp.float32)
        w = jax.random.normal(key, (d, v), jnp.float32) / np.sqrt(d)
        us = _time(fused_head_gate, h, w, None, 0.5,
                   supervisor="max_softmax", k=b) * 1e6
        flops = 2 * b * d * v
        rows.append({"kernel": "fused_head_gate",
                     "shape": f"[{b},{d}]x[{d},{v}]", "us_per_call": us,
                     "arith_intensity": flops / (4 * (b * d + d * v))})
    return rows


def _gate_checks(key) -> dict:
    """Functional gates for the fused/early-emit path (ISSUE 8):
    fused == composed (head then gate), Pallas body == ref in interpret
    mode, and the early-emit callback fires from inside jit with the
    same pred the gate returns."""
    b, d, v = 24, 96, 640           # non-aligned batch, vb|v for pallas
    h = jax.random.normal(key, (b, d), jnp.float32)
    w = jax.random.normal(key, (d, v), jnp.float32) / np.sqrt(d)
    bias = jax.random.normal(key, (v,), jnp.float32) * 0.1

    fused = fused_head_gate_ref(h, w, bias, 0.5, supervisor="max_softmax",
                                k=b)
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32) + bias
    composed = confidence_gate_ref(logits, 0.5, supervisor="max_softmax",
                                   k=b)
    fused_matches_composed = (
        bool(jnp.array_equal(fused["pred"], composed["pred"]))
        and bool(jnp.array_equal(fused["idx"], composed["idx"]))
        and bool(jnp.allclose(fused["conf"], composed["conf"],
                              rtol=2e-4, atol=1e-5)))

    pal = fused_head_gate(h, w, bias, 0.5, supervisor="max_softmax",
                          k=b, force_pallas=True, interpret=True)
    pallas_parity = (
        bool(jnp.array_equal(pal["pred"], fused["pred"]))
        and bool(jnp.array_equal(pal["idx"], fused["idx"]))
        and bool(jnp.allclose(pal["conf"], fused["conf"],
                              rtol=2e-4, atol=1e-5)))

    fired = []
    out = jax.jit(make_gated_local_step(
        lambda x: x, emit=lambda tag, conf, pred, idx: fired.append(
            (int(tag), np.asarray(pred)))))(logits, 0.5, b, 7)
    jax.block_until_ready(out["pred"])
    early_emit_fired = (
        len(fired) == 1 and fired[0][0] == 7
        and bool(np.array_equal(fired[0][1], np.asarray(out["pred"]))))

    return {
        "fused_matches_composed": fused_matches_composed,
        "fused_pallas_interpret_parity": pallas_parity,
        "early_emit_fired": early_emit_fired,
    }


def run(verbose: bool = True,
        json_path: str | None = None) -> dict:
    key = jax.random.PRNGKey(0)
    rows = []

    # maxconf: supervisor over LM-head logits (vocab up to 152k)
    for b, v in ((32, 102_400), (64, 152_064)):
        lg = jax.random.normal(key, (b, v), jnp.float32)
        us = _time(jax.jit(maxconf), lg) * 1e6
        flops = 5 * b * v      # exp, 2 max-scans, sum, div (approx)
        rows.append({"kernel": "maxconf", "shape": f"[{b},{v}]",
                     "us_per_call": us,
                     "arith_intensity": flops / (4 * b * v)})

    # confidence gate + early emit + fused head->gate (ISSUE 8)
    rows.extend(_gate_rows(key))

    # mdsa: Mahalanobis distance, penultimate width 4096
    x = jax.random.normal(key, (256, 4096))
    mean = jnp.zeros((4096,))
    prec = jnp.eye(4096)
    us = _time(jax.jit(mdsa_distance), x, mean, prec) * 1e6
    rows.append({"kernel": "mdsa", "shape": "[256,4096]x[4096,4096]",
                 "us_per_call": us,
                 "arith_intensity": (2 * 256 * 4096 * 4096)
                 / (4 * (4096 * 4096 + 2 * 256 * 4096))})

    # flash attention: remote-tier prefill block
    q = jax.random.normal(key, (1, 1024, 8, 128), jnp.bfloat16)
    k = jax.random.normal(key, (1, 1024, 2, 128), jnp.bfloat16)
    us = _time(jax.jit(lambda q, k: attention(q, k, k, causal=True)),
               q, k) * 1e6
    rows.append({"kernel": "flash_attention", "shape": "[1,1024,8|2,128]",
                 "us_per_call": us,
                 "arith_intensity": 2 * 1024 / 2 / 2})   # ~T/2 per byte

    # decode attention: one token vs 32k cache
    q1 = jax.random.normal(key, (8, 32, 128), jnp.bfloat16)
    kc = jax.random.normal(key, (8, 16_384, 8, 128), jnp.bfloat16)
    kv_len = jnp.full((8,), 16_384, jnp.int32)
    us = _time(jax.jit(lambda a, b, c, d: decode_attn(a, b, c, d)),
               q1, kc, kc, kv_len) * 1e6
    rows.append({"kernel": "decode_attention", "shape": "[8,16k,8,128]",
                 "us_per_call": us, "arith_intensity": 32 / 8 / 2})

    # rwkv6 scan: long-context chunk
    b, t, h, m = 1, 1024, 32, 64
    r = jax.random.normal(key, (b, t, h, m)) * 0.3
    w = jax.nn.sigmoid(jax.random.normal(key, (b, t, h, m)))
    u = jax.random.normal(key, (h, m)) * 0.3
    s0 = jnp.zeros((b, h, m, m))
    us = _time(jax.jit(rwkv6_time_mix_scan), r, r, r, w, u, s0) * 1e6
    rows.append({"kernel": "rwkv6_scan", "shape": f"[{b},{t},{h},{m}]",
                 "us_per_call": us, "arith_intensity": m / 4})

    checks = _gate_checks(key)
    report = {"rows": rows, "checks": checks,
              "passed": all(checks.values())}

    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=1)
    if verbose:
        print("\n--- Kernel microbench (CPU ref path; Pallas bodies are "
              "interpret-validated in tests) ---")
        print(f"{'kernel':>20} {'shape':>24} {'us/call':>10} {'AI':>7}")
        for r_ in rows:
            print(f"{r_['kernel']:>20} {r_['shape']:>24} "
                  f"{r_['us_per_call']:10.0f} {r_['arith_intensity']:7.1f}")
        print(f"checks {checks}")
        if json_path:
            print(f"JSON -> {json_path}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default="BENCH_kernels.json",
                    help="machine-readable output path ('' disables)")
    args = ap.parse_args(argv)
    report = run(json_path=args.json or None)
    return 0 if report["passed"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
