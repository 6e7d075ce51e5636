"""From a JAX profiler trace to device busy time, kernel time and idle gaps.

A trace is the ``*.xplane.pb`` that ``jax.profiler`` writes.  Device
operations are the events of the ``XLA Ops`` line on each ``/device:TPU:n``
plane.  The window is the span of the host annotation that the harness
puts around its timed steps (``WINDOW_SPAN``).  Host annotations on the
Python thread name what the host was doing in each idle gap.

Everything is in nanoseconds on the profiler's clock, which puts host and
device events on one timeline.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Tuple

WINDOW_SPAN = "bench/window"
SPAN_PREFIX = "bench/"


class Event(NamedTuple):
    name: str
    start: float     # ns
    end: float       # ns


def find_trace(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def device_ops(profile, plane_prefix: str = "/device:TPU:",
               line_name: str = "XLA Ops") -> Dict[str, List[Event]]:
    """Device operation events, by device plane."""
    out = {}
    for plane in profile.planes:
        if plane.name.startswith(plane_prefix) and \
                plane.name[len(plane_prefix):].isdigit():
            for line in plane.lines:
                if line.name == line_name:
                    out[plane.name] = _events(line)
    return out


def host_spans(profile, prefix: str = SPAN_PREFIX) -> List[Event]:
    """The harness's own annotations (names starting with ``prefix``) on
    the host planes."""
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans += [e for e in _events(line) if e.name.startswith(prefix)]
    return sorted(spans, key=lambda e: e.start)


def window_of(spans: List[Event]) -> Tuple[float, float]:
    wins = [s for s in spans if s.name == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(wins)}")
    return wins[0].start, wins[0].end


def _clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Disjoint intervals covering the events, in order."""
    merged: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if merged and e.start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end)
        else:
            merged.append([e.start, e.end])
    return [(s, t) for s, t in merged]


def _host_activity(spans: List[Event], lo: float, hi: float) -> str:
    """The innermost harness span that covers most of [lo, hi], or
    ``host`` where none does."""
    best, best_cover, best_len = "host", 0.0, float("inf")
    for s in spans:
        if s.name == WINDOW_SPAN:
            continue
        cover = min(s.end, hi) - max(s.start, lo)
        if cover <= 0:
            continue
        length = s.end - s.start
        if cover > best_cover or (cover == best_cover and length < best_len):
            best, best_cover, best_len = s.name, cover, length
    return best


def base_name(name: str) -> str:
    """``%s2fp8_matmul_pallas.131 = f32[...] custom-call(...)`` ->
    ``s2fp8_matmul_pallas``: the HLO instruction's name without its
    number.  A TPU trace names each device operation by its HLO text."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    stem, _, num = head.rpartition(".")
    return stem if stem and num.isdigit() else head


def self_times(events: List[Event]) -> List[Tuple[str, float, float]]:
    """(name, total ns, self ns) of each event.  Device ops nest (a
    ``while`` or ``conditional`` spans the ops of its body); an event's
    self time leaves out the events inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].end))
    own = [e.end - e.start for e in events]
    stack: List[int] = []
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            parent = events[stack[-1]]
            own[stack[-1]] -= min(e.end, parent.end) - e.start
        stack.append(i)
    return [(e.name, e.end - e.start, own[i]) for i, e in enumerate(events)]


class Reduction(NamedTuple):
    window_s: float
    busy_s: float                       # mean over devices
    op_seconds: Dict[str, float]        # self time per op text, all devices
    op_counts: Dict[str, int]
    idle_gaps: List[Tuple[str, float]]  # longest first
    n_devices: int

    def kernel_calls(self, match) -> List[Tuple[str, float, int]]:
        """(op text, device seconds, calls) of each op whose text
        satisfies the predicate ``match``."""
        return [(name, t, self.op_counts[name])
                for name, t in self.op_seconds.items() if match(name)]

    def top_ops(self, n: int = 10) -> List[List]:
        """The operations that took most device time (self time, summed
        by instruction name)."""
        by = defaultdict(float)
        for name, t in self.op_seconds.items():
            by[base_name(name)] += t
        ops = sorted(by.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in ops[:n]]


def reduce(ops_by_device: Dict[str, List[Event]],
           spans: List[Event]) -> Reduction:
    """Busy time, per-op self time and idle gaps of the devices in the
    window, the span named ``WINDOW_SPAN``."""
    if not ops_by_device:
        raise ValueError("the trace holds no device operations")
    lo, hi = window_of(spans)
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, int] = defaultdict(int)
    busy = 0.0
    gaps: List[Tuple[str, float]] = []
    for dev, events in ops_by_device.items():
        inside = _clip(events, lo, hi)
        for name, _, own in self_times(inside):
            op_s[name] += own * 1e-9
            op_n[name] += 1
        intervals = union(inside)
        busy += sum(t - s for s, t in intervals) * 1e-9
        edges = [lo] + [x for iv in intervals for x in iv] + [hi]
        for s, t in zip(edges[0::2], edges[1::2]):
            if t > s:
                gaps.append((_host_activity(spans, s, t), (t - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    n = len(ops_by_device)
    return Reduction((hi - lo) * 1e-9, busy / n, dict(op_s), dict(op_n),
                     gaps, n)


def reduce_file(log_dir: str) -> Reduction:
    profile = load(find_trace(log_dir))
    return reduce(device_ops(profile), host_spans(profile))
