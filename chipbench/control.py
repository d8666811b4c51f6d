#!/usr/bin/env python3
"""The check's control: the reference, in the precision below the served
one, put in the program's place.

    python3 chipbench/control.py --workload yi6b-imdb-steady \
        --seeds 11,12,13,14 --seconds 10

For each seed, one process runs the cell as ``run.py`` does (a short
window at the cell's own load), then reads the check's numbers on the
same sampled windows: for the program (its lower readings) and for the
plain reference computed in each ``--precision`` (fp8: both operands of
every weight product rounded to e4m3) serving in its place (the control's
readings). Each is judged against the configuration's ``limits`` by the
check's own comparison. Prints one JSON line per seed: ``correct`` is the
program's verdict, ``control_correct`` each control's, which has to come
out false. ``--precision ''`` reads the program alone. Not part of a
benchmark run.
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
    from chipbench import harness, spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--precision", default="fp8",
                    help="comma list of control precisions (fp8, int8)")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    devs = harness.require_chip(cell.workload["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(cell, seed, args.seconds, False,
                               time.perf_counter(), devs,
                               control=args.precision)
        ctl = res["control"]
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "control_correct": {q: c["correct"] for q, c in ctl.items()},
            "program": res["numbers"],
            "control": {q: {k: v["value"] for k, v in c["checks"].items()}
                        for q, c in ctl.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
