#!/usr/bin/env python3
"""Find a cell's knee once, by a sweep of offered rates on the chip.

    python3 chipbench/sweep.py --workload yi6b-imdb-steady --seed 11 \
        --seconds 20 --rates 8,12,14,16,20

One process sets the cell up once, then offers each rate (requests/s,
the traffic file's arrival pattern and content) for ``--seconds``, drains,
and prints one JSON line per rate: completed requests/s inside the window,
the tails, and the backlog (requests due in the window but handed back
after it closed). The knee is the highest rate whose completions keep up
with the offer and whose backlog stays small; ``chipbench/cells/<cell>.json``
records it with the sweep behind it. Not part of a benchmark run.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    from chipbench import hoist
    hoist.enable()

    import numpy as np

    from chipbench import check, drive, harness, spec, stats, traffic
    from repro.serving import Request

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    harness.require_chip(cell.workload["chips"])
    mix = cell.traffic
    st = harness.build_stack(cell, args.seed, False)
    tier, scfg, eng, sched = st.tier, st.scfg, st.eng, st.sched
    warm = traffic.plan(mix, 1.0, tier.vocab, tier.seq_len,
                        scfg.batch_size, 1.0, args.seed).warm
    eng.warmup(warm, tier.vocab)
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        plan = traffic.plan(mix, rate / mix["load"], tier.vocab,
                            tier.seq_len, scfg.batch_size, args.seconds,
                            args.seed + k)
        uid0 = k * 1_000_000
        window = drive.open_loop(
            sched,
            lambda i, t: Request(
                uid=uid0 + i, local_input=plan.tokens[plan.content[i]],
                remote_input={"tokens": plan.tokens[plan.content[i]]},
                t_enq=t),
            plan.due, args.seconds)
        window.responses = {u - uid0: r for u, r in window.responses.items()}
        recs = harness._records(plan, window)
        wins = check.windows_of(recs)
        done = sum(r["answered"] and r["handback"] < args.seconds
                   for r in recs)
        print(json.dumps({
            "rate": rate, "offered": len(recs),
            "completed_rps": done / args.seconds,
            "backlog": len(recs) - done,
            "p95_ms": stats.latency_p95_ms(recs),
            "local_p95_ms": stats.latency_p95_ms(recs, local_only=True),
            "windows": len(wins),
            "rows_per_window": float(np.mean([len(w) for w in wins])),
            "lateness_p99_ms": 1e3 * float(np.percentile(window.lateness,
                                                         99))}),
              flush=True)
    eng.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
