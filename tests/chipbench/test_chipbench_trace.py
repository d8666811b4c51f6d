"""The trace reduction on a hand-built trace: busy union, idle share,
kernel sums, the top operations and the idle gaps' host attribution."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import trace as T  # noqa: E402

E = T.Event
OPS = [E("fusion.1", 0.0, 1.0), E("head_gate_scores_pallas", 0.5, 1.5),
       E("fusion.2", 3.0, 4.0), E("fusion.1", 6.0, 6.8),
       E("head_gate_scores_pallas", 6.5, 7.5)]
HOST = [E(T.WINDOW_SPAN, 0.0, 10.0), E("PjitFunction(step)", 1.4, 3.1),
        E("device_get", 4.0, 6.0), E("wait", 7.6, 9.9)]


def test_merged_intervals_are_the_union():
    assert T.merged(OPS) == [(0.0, 1.5), (3.0, 4.0), (6.0, 7.5)]


@pytest.mark.parametrize("lo,hi,busy", [(0.0, 10.0, 4.0), (0.5, 6.5, 2.5),
                                        (1.5, 3.0, 0.0)])
def test_busy_seconds_clips_to_the_window(lo, hi, busy):
    assert T.busy_seconds(OPS, lo, hi) == pytest.approx(busy)


def test_idle_share():
    assert T.idle_share(OPS, 0.0, 10.0) == pytest.approx(0.6)


def test_kernel_sum():
    k = T.matching(T.clip(OPS, 0.0, 10.0), "head_gate")
    assert len(k) == 2 and T.seconds(k) == pytest.approx(2.0)
    assert T.seconds(T.matching(T.clip(OPS, 0.0, 7.0), "head_gate")) == \
        pytest.approx(1.5)


def test_top_ops_sums_by_name():
    top = T.top_ops(OPS, 0.0, 10.0, n=2)
    assert top[0] == ["head_gate_scores_pallas", pytest.approx(2.0)]
    assert top[1] == ["fusion.1", pytest.approx(1.8)]


def test_idle_gaps_named_by_the_overlapping_host_span():
    gaps = T.idle_gaps(OPS, HOST, 0.0, 10.0, n=3)
    assert [g[1] for g in gaps] == pytest.approx([2.5, 2.0, 1.5])
    assert [g[0] for g in gaps] == ["wait", "device_get",
                                    "PjitFunction(step)"]


def test_idle_gap_with_no_host_span():
    gaps = T.idle_gaps([E("op", 0.0, 1.0)], [], 0.0, 2.0)
    assert gaps == [["(no host span)", pytest.approx(1.0)]]


def test_top_ops_skip_ops_that_hold_others():
    evs = [E("%while.9", 0.0, 10.0), E("fusion.1", 1.0, 4.0),
           E("fusion.2", 5.0, 6.0)]
    assert [t[0] for t in T.top_ops(evs, 0.0, 10.0)] == ["fusion.1",
                                                         "fusion.2"]
    assert T.busy_seconds(evs, 0.0, 10.0) == pytest.approx(10.0)
