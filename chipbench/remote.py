"""The remote tier as a modelled wire: off the chip by definition.

``ModelledRemote`` is the ``remote_apply`` the repository's transport
calls for each window of escalated rows. It holds each call for the
configuration's round trip (the ``CostModel`` constant, 0.32 s) and answers
with logits over the local tier's vocabulary that are a function of the
request's token content and the run's seed alone: a standard-normal
background with one answer token raised by a margin drawn from the
configuration's ``answer_margin`` range. The answer, and so whether the
2nd-level supervisor trusts it, never depends on batching, timing or
order, which lets the check recompute every escalated answer.
"""

from __future__ import annotations

import hashlib
import time

import numpy as np


class ModelledRemote:
    def __init__(self, remote: dict, vocab: int, seed: int):
        self.latency_s = float(remote["latency_s"])
        self.margin = tuple(remote["answer_margin"])
        self.vocab = vocab
        self.seed = int(seed)

    def row_logits(self, tokens: np.ndarray) -> np.ndarray:
        """The remote's logits [V] float32 for one request's content."""
        h = hashlib.blake2b(np.ascontiguousarray(tokens, np.int32).tobytes(),
                            digest_size=16,
                            key=self.seed.to_bytes(16, "little"))
        rng = np.random.default_rng(int.from_bytes(h.digest(), "little"))
        logits = rng.standard_normal(self.vocab, np.float32)
        answer = int(rng.integers(0, self.vocab))
        logits[answer] += np.float32(rng.uniform(*self.margin))
        return logits

    def __call__(self, batch: dict) -> np.ndarray:
        t0 = time.perf_counter()
        tokens = np.asarray(batch["tokens"])
        out = np.stack([self.row_logits(t) for t in tokens])
        rest = self.latency_s - (time.perf_counter() - t0)
        if rest > 0:
            time.sleep(rest)
        return out
