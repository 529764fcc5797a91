"""The program's host phases in a profiler trace.

With ``ExecutionPolicy(trace=True)`` every span of ``runtime/obs.py`` is
also a host annotation named ``repro.<span>`` (``repro.forward``,
``repro.hoist``, ``repro.pack``, ``repro.slot_launch``, ``repro.scatter``
...), on the profiler's clock beside the device operations.  This reduces
one trace, clipped to the ``bench.window`` annotation, to:

* each span name's total time, self time and count: self time is a
  span's duration less the union of the spans nested in it on the same
  thread, so the ``repro.*`` self times of one call add up to the call's
  host time inside the program;
* the device's idle gaps charged to the innermost ``bench.*`` or
  ``repro.*`` span open at each gap's midpoint (``bench.window`` when none
  is), as ``trace.py`` charges them to ``bench.*`` spans alone.

On a truncated device record the window ends with the last call it
covers, as in ``trace.py``.

Busy time, kernel time and the idle share stay with ``trace.py``.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional, Tuple

from bench.harness import trace

PREFIXES = ("bench.", "repro.")
PHASE = "repro."   # the program's own spans


@dataclasses.dataclass
class Phases:
    span_seconds: Dict[str, float]       # time in the window, by name
    span_self_seconds: Dict[str, float]  # less the spans nested in each
    span_counts: Dict[str, int]
    gap_seconds: Dict[str, float]        # idle time by innermost span
    longest_gaps: List[Tuple[str, float]]


def host_spans(planes) -> List[Tuple[float, float, str, tuple]]:
    """``(start_ns, end_ns, name, thread)`` of every host annotation of
    either prefix; ``thread`` tells one host line from another."""
    spans = []
    for p, plane in enumerate(planes):
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name, (p, i)))
    return spans


def self_times(spans, w0: float, w1: float):
    """``(seconds, self_seconds, counts)`` by span name, each span clipped
    to ``[w0, w1)``."""
    total: Dict[str, float] = collections.Counter()
    own: Dict[str, float] = collections.Counter()
    counts: Dict[str, int] = collections.Counter()

    def close(entry):
        a, b, name, covered = entry
        total[name] += (b - a) / 1e9
        own[name] += (b - a - covered) / 1e9
        counts[name] += 1

    threads = collections.defaultdict(list)
    for a, b, name, thread in spans:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            threads[thread].append((a, -b, name))
    for ivs in threads.values():
        stack: list = []          # open spans: [start, end, name, covered]
        for a, neg_b, name in sorted(ivs):   # parents before children
            while stack and stack[-1][1] <= a:
                close(stack.pop())
            if stack:
                stack[-1][3] += min(-neg_b, stack[-1][1]) - a
            stack.append([a, -neg_b, name, 0.0])
        while stack:
            close(stack.pop())
    return dict(total), dict(own), dict(counts)


def reduce_planes(planes, window: str = trace.WINDOW,
                  call: Optional[str] = None) -> Phases:
    """Over the window of ``trace.window_of``: on a truncated device
    record, the calls it covers."""
    planes = list(planes)
    spans = host_spans(planes)
    win = trace.window_of(planes, spans, window, call)
    w0, w1 = win.start_ns, win.end_ns
    inner = sorted(s for s in spans
                   if s[2] != window and s[1] > w0 and s[0] < w1)
    seconds, own, counts = self_times(inner, w0, w1)

    n_planes = sum(1 for p in planes
                   if trace.DEVICE_PLANE.match(p.name)) or 1
    gaps: Dict[str, float] = collections.Counter()
    longest: List[Tuple[str, float]] = []
    for plane in planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        busy = trace._union([
            (max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1))
            for line in plane.lines if line.name == trace.OPS_LINE
            for e in line.events
            if min(e.start_ns + e.duration_ns, w1) > max(e.start_ns, w0)])
        if not busy:
            continue
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        names = trace._attribute(inner, [(a + b) / 2 for a, b in idle])
        for (a, b), name in zip(idle, names):
            name = name or window
            gaps[name] += (b - a) / 1e9 / n_planes
            longest.append((name, (b - a) / 1e9))
    longest.sort(key=lambda kv: -kv[1])
    return Phases(seconds, own, counts, dict(gaps), longest[:10])

