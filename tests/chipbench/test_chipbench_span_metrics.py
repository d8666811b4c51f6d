"""The readers of the program's own spans on hand-built traces:
``remote_wait_share`` (union of ``cascade.remote_wait`` over the window)
and ``remote_call_p95_ms`` (``transport.call`` durations starting in the
window), each None where the trace holds no such span."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import spec  # noqa: E402
from chipbench import trace as T  # noqa: E402

E = T.Event
WAIT, CALL = "cascade.remote_wait", "transport.call"


def run_of(host, window=(1.0, 11.0)):
    return SimpleNamespace(trace=T.Trace(
        ops=[[E("fusion.1", 1.0, 2.0)]], modules=[[]],
        host=[E(T.WINDOW_SPAN, *window)] + host, window=window))


def test_remote_wait_share_is_the_clipped_union_over_the_window():
    host = [E(WAIT, 0.0, 2.0),          # clipped to 1.0 .. 2.0
            E(WAIT, 3.0, 4.0), E(WAIT, 3.5, 4.5),   # overlapping: 1.5
            E(WAIT, 10.5, 12.0),        # clipped to 10.5 .. 11.0
            E("cascade.gate", 5.0, 9.0), E(CALL, 2.0, 9.0)]
    read = spec.reader("remote_wait_share")
    assert read(run_of(host)) == pytest.approx(100.0 * 3.0 / 10.0)


def test_remote_wait_share_overload_uses_the_same_reader():
    host = [E(WAIT, 2.0, 4.0)]
    assert spec.reader("remote_wait_share.overload")(run_of(host)) == \
        pytest.approx(20.0)


def test_remote_call_p95_counts_calls_that_start_in_the_window():
    host = [E(CALL, 0.5, 1.5),          # starts before the window: out
            E(T.WINDOW_SPAN, 0.0, 100.0)]
    host += [E(CALL, 2.0 + i * 0.1, 2.0 + i * 0.1 + 0.32) for i in range(19)]
    host += [E(CALL, 9.0, 9.64),        # two windows back to back
             E(CALL, 10.9, 12.0),       # starts inside, ends after: in
             E(WAIT, 3.0, 9.0)]
    read = spec.reader("remote_call_p95_ms")
    # 21 calls in the window: the nearest-rank p95 is the 20th smallest
    assert read(run_of(host)) == pytest.approx(640.0)


@pytest.mark.parametrize("metric", ["remote_wait_share",
                                    "remote_wait_share.overload",
                                    "remote_call_p95_ms"])
def test_none_without_the_programs_spans(metric):
    read = spec.reader(metric)
    assert read(run_of([E("np.asarray(jax.Array)", 2.0, 3.0)])) is None
    assert read(SimpleNamespace(trace=None)) is None
