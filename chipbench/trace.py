"""Reduction of a profiler trace to device busy time, kernel time and gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX, into plain event lists: per device, the operations
(``XLA Ops`` line) and the whole programs (``XLA Modules`` line); from
the host, every span (JAX's own dispatch spans and the benchmark's
``TraceAnnotation`` around the measured window, whose bounds clip every
reduction). The reductions below take event lists, so a test can feed
them a hand-built trace.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

WINDOW_SPAN = "chipbench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass(frozen=True)
class Event:
    name: str
    start: float                # seconds, trace clock
    end: float


@dataclass
class Trace:
    ops: list = field(default_factory=list)       # [device] -> [Event]
    modules: list = field(default_factory=list)   # [device] -> [Event]
    host: list = field(default_factory=list)      # [Event]
    window: tuple | None = None                   # (lo, hi) seconds


def _events(line) -> list[Event]:
    return [Event(e.name, e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(paths)}")
    out = Trace()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                out.ops.append(_events(lines[OPS_LINE]))
                out.modules.append(_events(lines[MODULES_LINE])
                                   if MODULES_LINE in lines else [])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                out.host.extend(_events(ln))
    spans = [e for e in out.host if e.name == WINDOW_SPAN]
    if spans:
        out.window = (spans[0].start, spans[0].end)
    return out


def clip(events, lo: float, hi: float) -> list[Event]:
    return [Event(e.name, max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def merged(events) -> list[tuple[float, float]]:
    """Union of the events' intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def busy_seconds(events, lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged(clip(events, lo, hi)))


def idle_share(events, lo: float, hi: float) -> float:
    return 1.0 - busy_seconds(events, lo, hi) / (hi - lo)


def matching(events, needle: str) -> list[Event]:
    return [e for e in events if needle in e.name]


def seconds(events) -> float:
    return sum(e.end - e.start for e in events)


def leaves(events) -> list[Event]:
    """The events that hold no other event of their line (a ``while``
    loop's op spans the ops of its body)."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    return [e for i, e in enumerate(evs)
            if i + 1 == len(evs) or not (evs[i + 1].start < e.end
                                         and evs[i + 1].end <= e.end)]


def top_ops(events, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` operation names (of ops that hold no other op) that took
    most device time, with their summed seconds."""
    by: dict[str, float] = {}
    for e in leaves(clip(events, lo, hi)):
        by[e.name] = by.get(e.name, 0.0) + (e.end - e.start)
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(device_events, host_events, lo: float, hi: float,
              n: int = 10) -> list:
    """The ``n`` longest stretches with no device operation, each named by
    the host span that overlaps it most (the window span itself aside)."""
    busy = merged(clip(device_events, lo, hi))
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [e for e in host_events if e.name != WINDOW_SPAN]
    out = []
    for a, b in gaps[:n]:
        best, best_overlap = "(no host span)", 0.0
        for e in host:
            ov = min(b, e.end) - max(a, e.start)
            if ov > best_overlap:
                best, best_overlap = e.name, ov
        out.append([best, b - a])
    return out
