"""The reader ``admit_on_wire_share`` on hand-built traces: the share of
``scheduler.admit`` spans made while a ``transport.call`` was out, the
i-th call read as sent at the i-th ``cascade.route``; None where the
trace holds no admission span."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import spec  # noqa: E402
from chipbench import trace as T  # noqa: E402

E = T.Event
ADMIT, CALL, ROUTE = "scheduler.admit", "transport.call", "cascade.route"


def run_of(host, window=(1.0, 11.0)):
    return SimpleNamespace(trace=T.Trace(
        ops=[[E("fusion.1", 1.0, 2.0)]], modules=[[]],
        host=[E(T.WINDOW_SPAN, *window)] + host, window=window))


def test_counts_admits_made_while_a_call_is_out():
    host = [E(ADMIT, 0.5, 0.6),         # before the window: not counted
            E(ADMIT, 1.0, 1.1), E(ROUTE, 1.2, 1.3),
            E(CALL, 1.25, 1.6),         # on the wire at the next admit
            E(ADMIT, 1.4, 1.5), E(ROUTE, 2.6, 2.7),
            # the call submitted in the route above opens only after the
            # next admit began, but before the route after it: counted
            E(ADMIT, 2.8, 2.9), E(CALL, 2.95, 3.3), E(ROUTE, 4.0, 4.1),
            E(CALL, 4.05, 4.4),         # this admit's own call: not counted
            E(ADMIT, 5.0, 5.1),         # every call has returned
            E(ADMIT, 11.5, 11.6)]       # after the window: not counted
    read = spec.reader("admit_on_wire_share")
    assert read(run_of(host)) == pytest.approx(100.0 * 2 / 4)
    assert spec.reader("admit_on_wire_share.overload")(run_of(host)) == \
        pytest.approx(50.0)


def test_pairs_late_calls_with_their_routes():
    # the pool thread opened each call only after the serving thread had
    # begun the next route: the i-th call still went out at the i-th route
    host = [E(ADMIT, 1.0, 1.1), E(ROUTE, 1.2, 1.3),
            E(ADMIT, 1.4, 1.5), E(ROUTE, 1.6, 1.7),
            E(CALL, 1.65, 2.0), E(CALL, 1.75, 2.1),
            E(ADMIT, 1.8, 1.9),         # both calls out
            E(ADMIT, 2.5, 2.6)]         # both back
    read = spec.reader("admit_on_wire_share")
    assert read(run_of(host)) == pytest.approx(100.0 * 2 / 4)


def test_reads_zero_when_admits_wait_for_answers():
    # the serial loop: each admit after the previous call returned
    host = [E(ADMIT, 1.0 + i, 1.1 + i) for i in range(4)]
    host += [E(ROUTE, 1.2 + i, 1.3 + i) for i in range(4)]
    host += [E(CALL, 1.25 + i, 1.9 + i) for i in range(4)]
    assert spec.reader("admit_on_wire_share")(run_of(host)) == 0.0


@pytest.mark.parametrize("metric", ["admit_on_wire_share",
                                    "admit_on_wire_share.overload"])
def test_none_without_the_programs_spans(metric):
    read = spec.reader(metric)
    assert read(run_of([E("np.asarray(jax.Array)", 2.0, 3.0),
                        E(CALL, 2.0, 2.3)])) is None
    assert read(SimpleNamespace(trace=None)) is None
