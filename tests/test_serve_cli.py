"""The serve driver end to end through its own entry point, on the CPU.

``repro.launch.serve.run`` is what ``python -m repro.launch.serve`` and
``chip_smoke.py`` call. Each case serves a small stream with the remote
tier cut by ``--smoke`` and checks that every request is answered exactly
once and that the transport reports no fault.
"""

from __future__ import annotations

import pytest

from repro.launch import serve

REQUESTS = 64
BASE = ["--smoke", "--requests", str(REQUESTS), "--batch", "16"]


@pytest.mark.parametrize("extra", [
    [],                                             # transport, window
    ["--adaptive", "--set", "replicas=2"],          # replicated engines
    ["--set", "fused=True"],                        # fully-jitted cascade
], ids=["window", "replicas", "fused"])
def test_serve_run_answers_every_request(extra, capsys):
    res = serve.run(BASE + extra)
    assert sorted(r.uid for r in res.responses) == list(range(REQUESTS))
    assert res.faults and not any(res.faults.values()), res.faults
    assert res.init_s > 0 and res.compile_s > 0 and res.wall_s > 0
    assert not res.pallas_gate                      # the CPU takes jnp
    out = capsys.readouterr().out
    assert "[serve] devices: 1 x cpu" in out
    assert "init" in out and "[serve] compile:" in out
