"""Reduce a profiler trace (``.xplane.pb``) to device busy time, kernel
time and idle gaps attributed to what the host was doing.

* Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
  event per operation that ran on the chip, named by its HLO text
  (``%lstm_seq.1 = (...) custom-call(...)``).  Operations are keyed by the
  instruction name without its numeric suffix (``%lstm_seq``), so a
  Pallas kernel is found by the name of the jitted function that wraps it.
* The window is the host annotation ``bench.window`` that the drivers
  open around the measured loop; everything is clipped to it.
* The profiler's device buffer holds a few million events; a program that
  issues more in the window loses the rest, and the device plane then
  carries a ``dropped_traces`` stat above 0.  On such a truncated record
  the window ends instead with the last *covered* call: a call is the
  host span a driver names as one unit of its work (``bench.forward``,
  ``bench.step``), and it is covered when it ends by the record's end,
  the latest end of a device operation (the least over the device
  planes).  Every reading then covers the same calls, and the driver
  counts the work of those alone.  A complete record keeps the whole
  window: the last call's span ends just after its own last operation,
  so clipping there would drop that call.
* Busy time is the union of the operation intervals (averaged over the
  device planes); idle is the window less that.
* Each idle gap is charged to the innermost ``bench.*`` host annotation
  open at its midpoint (``bench.window`` itself when none is), so the
  breakdown says whether the chip waited for the planner, the executor's
  host work, the next arrival or the benchmark's own bookkeeping.
"""
from __future__ import annotations

import collections
import heapq
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
DROPPED = "dropped_traces"
WINDOW = "bench.window"
PREFIX = "bench."
_SUFFIX = re.compile(r"\.\d+$")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over device planes
    n_devices: int
    op_seconds: Dict[str, float]        # device time by operation name
    op_counts: Dict[str, int]
    gap_seconds: Dict[str, float]       # idle time by host annotation
    longest_gaps: List[Tuple[str, float]]
    truncated: bool = False             # the device record was cut short
    dropped_traces: int = 0             # the device planes' stat, summed
    covered_calls: int = 0              # call spans the window holds

    @property
    def readable(self) -> bool:
        """False for a truncated record that holds no whole call: then
        no reading of it covers any work."""
        return not self.truncated or self.covered_calls > 0

    def kernel_seconds(self, op: str) -> float:
        """Device time of the operations keyed ``op`` (``%lstm_seq``)."""
        return self.op_seconds.get(op, 0.0)

    def kernel_count(self, op: str) -> int:
        return self.op_counts.get(op, 0)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.longest_gaps[:top]]}


def op_key(hlo: str) -> str:
    """``%name.N = ...`` -> ``%name``; other event names as they are."""
    head = hlo.split(" = ", 1)[0]
    return _SUFFIX.sub("", head) if head.startswith("%") else head


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _host_spans(planes) -> List[Tuple[float, float, str]]:
    spans = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    return spans


def _attribute(spans, mids: List[float]) -> List[Optional[str]]:
    """For each time in ``mids`` (ascending), the innermost (shortest)
    span that covers it, by one sweep over spans sorted by start."""
    out, active, i = [], [], 0
    for t in mids:
        while i < len(spans) and spans[i][0] <= t:
            heapq.heappush(active, (spans[i][1], spans[i]))
            i += 1
        while active and active[0][0] < t:
            heapq.heappop(active)
        best = min((s for _, s in active), key=lambda s: s[1] - s[0],
                   default=None)
        out.append(None if best is None else best[2])
    return out


def _dropped(planes) -> int:
    """The device planes' ``dropped_traces``; planes without stats (as
    synthetic ones are) drop nothing."""
    return sum(int(v) for p in planes if DEVICE_PLANE.match(p.name)
               for k, v in getattr(p, "stats", ()) if k == DROPPED)


def _record_end(planes) -> float:
    """The latest end of a device operation, the least over the device
    planes that hold any (``-inf`` when none does)."""
    ends = [max(e.start_ns + e.duration_ns for e in line.events)
            for p in planes if DEVICE_PLANE.match(p.name)
            for line in p.lines if line.name == OPS_LINE and line.events]
    return min(ends, default=float("-inf"))


@dataclasses.dataclass(frozen=True)
class Window:
    start_ns: float
    end_ns: float
    truncated: bool
    dropped_traces: int
    covered_calls: int


def window_of(planes, spans, window: str = WINDOW,
              call: Optional[str] = None) -> Window:
    """The window every reading covers.  ``spans`` are host spans, each
    ``(start_ns, end_ns, name, ...)``; the window runs from the first
    ``window`` span's start to the last one's end, and on a truncated
    record to the end of the last ``call`` span the record covers
    (``start_ns`` itself when none is, or no ``call`` is named)."""
    windows = [(s[0], s[1]) for s in spans if s[2] == window]
    if not windows:
        raise ValueError(f"trace has no {window!r} host annotation")
    w0, w1 = min(a for a, _ in windows), max(b for _, b in windows)
    ends = [s[1] for s in spans if s[2] == call and s[1] > w0 and s[0] < w1]
    dropped = _dropped(planes)
    if not dropped:
        return Window(w0, w1, False, 0, len(ends))
    record_end = _record_end(planes)
    covered = [b for b in ends if b <= record_end]
    return Window(w0, min(max(covered, default=w0), w1), True, dropped,
                  len(covered))


def reduce_planes(planes, window: str = WINDOW,
                  call: Optional[str] = None) -> TraceSummary:
    """Reduce the planes of one ``jax.profiler.ProfileData`` over the
    window of ``window_of``; ``call`` names the host span of one unit of
    the driver's work."""
    planes = list(planes)
    spans = _host_spans(planes)
    win = window_of(planes, spans, window, call)
    w0, w1 = win.start_ns, win.end_ns
    cover = dict(truncated=win.truncated, dropped_traces=win.dropped_traces,
                 covered_calls=win.covered_calls)
    if not win.covered_calls and win.truncated:
        return TraceSummary(0.0, 0.0, 0, {}, {}, {}, [], **cover)
    inner = [s for s in spans if s[2] != window and s[1] > w0 and s[0] < w1]
    inner.sort()

    n_planes = sum(1 for p in planes if DEVICE_PLANE.match(p.name)) or 1
    busy_total, n_dev = 0.0, 0
    op_ns: Dict[str, float] = collections.Counter()
    op_n: Dict[str, int] = collections.Counter()
    gaps: Dict[str, float] = collections.Counter()
    longest: List[Tuple[str, float]] = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ivs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                a, b = max(e.start_ns, w0), min(e.start_ns + e.duration_ns,
                                                w1)
                if b <= a:
                    continue
                ivs.append((a, b))
                key = op_key(e.name)
                op_ns[key] += b - a
                op_n[key] += 1
        if not ivs:
            continue
        n_dev += 1
        busy = _union(ivs)
        busy_total += sum(b - a for a, b in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        names = _attribute(inner, [(a + b) / 2 for a, b in idle])
        for (a, b), name in zip(idle, names):
            name = name or window
            gaps[name] += (b - a) / 1e9 / n_planes
            longest.append((name, (b - a) / 1e9))
    if n_dev == 0:
        raise ValueError("trace has no device operations in the window")
    longest.sort(key=lambda kv: -kv[1])
    return TraceSummary(
        window_s=(w1 - w0) / 1e9, busy_s=busy_total / n_dev / 1e9,
        n_devices=n_dev,
        op_seconds={k: v / n_dev / 1e9 for k, v in op_ns.items()},
        op_counts=dict(op_n), gap_seconds=dict(gaps),
        longest_gaps=longest[:10], **cover)


def reduce_file(path, window: str = WINDOW,
                call: Optional[str] = None) -> TraceSummary:
    import jax

    return reduce_planes(
        jax.profiler.ProfileData.from_file(str(path)).planes, window, call)
