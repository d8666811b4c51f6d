#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 chipbench/run.py --workload yi6b-imdb-steady --seed 7 \
        --seconds 30 --trace 0

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``, each number compared beside its
limit). Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import hoist  # noqa: E402

hoist.enable()

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(harness.main(sys.argv[1:], T_START))
