"""The one traffic generator: a traffic file's parameters -> a request plan.

A traffic file (``chipbench/traffic/<name>.json``) is data only:

* ``arrivals``: ``pattern`` (``poisson`` | ``pareto_burst``), ``shape_seed``
  and, for bursts, ``alpha`` (see ``arrivals.schedule``);
* ``load``: offered rate as a multiple of the cell's knee
  (``chipbench/cells/<workload>.json`` holds the knee, from the sweep);
* ``remote_fraction_budget``: the cascade's escalation capacity share;
* ``repeat_share``: share of requests whose token content repeats an
  earlier request's in the same run (0 = all distinct).

Token ids are uniform over the configuration's vocabulary at its fixed
input length. Exactly ``round(repeat_share * n)`` requests repeat, each
the content of a uniformly drawn earlier request; which ones, and every
token, come from the run's seed.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from chipbench import arrivals


@dataclass
class Plan:
    due: np.ndarray             # [n] seconds after the window opens
    content: np.ndarray         # [n] row of ``tokens`` each request sends
    tokens: np.ndarray          # [u, seq_len] int32 distinct contents
    warm: np.ndarray            # [batch, seq_len] warm-up content
    rate: float

    def __len__(self) -> int:
        return len(self.due)


def plan(traffic: dict, knee_rps: float, vocab: int, seq_len: int,
         batch: int, seconds: float, seed: int) -> Plan:
    rate = traffic["load"] * knee_rps
    due = arrivals.schedule(traffic["arrivals"], rate, seconds, seed)
    n = len(due)
    rng = np.random.default_rng([int(seed), 2])
    n_rep = int(round(traffic.get("repeat_share", 0.0) * n))
    repeats = np.zeros(n, bool)
    if n_rep:
        repeats[1 + rng.choice(n - 1, size=n_rep, replace=False)] = True
    content = np.zeros(n, np.int64)
    u = 0
    for i in range(n):
        if repeats[i]:
            content[i] = content[rng.integers(0, i)]
        else:
            content[i] = u
            u += 1
    tokens = rng.integers(0, vocab, (u + batch, seq_len), dtype=np.int32)
    return Plan(due=due, content=content, tokens=tokens[:u],
                warm=tokens[u:], rate=rate)
