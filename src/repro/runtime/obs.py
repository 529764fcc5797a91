"""Zero-dependency tracing + metrics for the planned execution path.

The instrument for the executor's host work: which phase of a call
(planning, the per-cell input GEMMs, row packing, the kernel launch, the
scatter of results back per cell) the host spends its time in, on the
same clock as the device's operations.

Two cooperating pieces, stdlib-only (``time``; jax is imported lazily,
only for the profiler annotation and the benchmark timer):

``Tracer``
    Records nested host spans (``forward``, ``prefill``, ``decode_tick``,
    ``plan``, ``verify``, ``hoist``, ``pack``, ``slot_launch``,
    ``scatter``, ``fallback_rung``, ``admit``, ``request`` ...) tagged
    with the slot signature, plan id and request uids.  Each live span
    also enters a ``jax.profiler.TraceAnnotation`` named
    ``repro.<name>`` (its entry tags ride along as the event's stats), so
    an operator who captures a ``jax.profiler`` trace around traced calls
    sees every phase on the profiler's clock, beside the device
    operations it issued — that is where device time and idle gaps come
    from.  Spans never fence: a span's duration is the HOST time of the
    region (the executor issues device work asynchronously), so a traced
    run runs the same program as an untraced one.  Exports:
    ``snapshot()`` (machine-readable dict) and ``describe()`` (text,
    merged into ``CompiledStack.describe()``).

``MetricsRegistry``
    Counters and streaming histograms (bounded reservoir; nearest-rank
    p50/p90/p99): decode tick host time, queue depth, slot occupancy,
    degraded launches, request outcomes.

The whole subsystem is opt-in via ``ExecutionPolicy(trace=True)``.  Off
(the default), every instrumented call site holds the module-level
``NULL_TRACER`` whose ``span()`` returns one reused no-op context manager
— no events, no ``Span`` object, no profiler annotation, and executor
outputs bit-identical to the un-instrumented code (asserted in
tests/rnn/test_obs.py and priced in BENCH_dispatch's ``obs_*`` rows).

``measure_us`` is the one benchmark timer (warmup exclusion +
``block_until_ready`` fencing + median/min reduction) — the bench suites
and the calibration replay route through it so every fenced timing in
the repo shares a single measurement code path.
"""
from __future__ import annotations

import math
import statistics
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


class Counter:
    """A monotonically increasing count."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def add(self, n: int = 1) -> None:
        self.value += n


class Histogram:
    """Streaming distribution: exact count/sum/min/max plus a bounded
    reservoir the quantiles are computed over (Vitter's algorithm R with a
    deterministic LCG, so identical observation streams give identical
    snapshots).  Quantiles are nearest-rank over the retained sample —
    exact while ``count <= cap``."""

    __slots__ = ("count", "total", "min", "max", "_sample", "_cap", "_lcg")

    def __init__(self, cap: int = 2048):
        self.count = 0
        self.total = 0.0
        self.min = math.inf
        self.max = -math.inf
        self._sample: List[float] = []
        self._cap = cap
        self._lcg = 0x2545F4914F6CDD1D

    def observe(self, value: float) -> None:
        value = float(value)
        self.count += 1
        self.total += value
        self.min = min(self.min, value)
        self.max = max(self.max, value)
        if len(self._sample) < self._cap:
            self._sample.append(value)
            return
        # deterministic reservoir replacement (64-bit LCG)
        self._lcg = (self._lcg * 6364136223846793005 + 1442695040888963407) \
            & 0xFFFFFFFFFFFFFFFF
        j = self._lcg % self.count
        if j < self._cap:
            self._sample[j] = value

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def quantile(self, q: float) -> float:
        """Nearest-rank quantile over the retained sample (q in [0, 1])."""
        if not self._sample:
            return 0.0
        vals = sorted(self._sample)
        rank = max(1, math.ceil(q * len(vals)))
        return vals[min(rank, len(vals)) - 1]

    def snapshot(self) -> Dict[str, float]:
        if not self.count:
            return {"count": 0}
        return {"count": self.count, "mean": self.mean,
                "min": self.min, "max": self.max,
                "p50": self.quantile(0.50), "p90": self.quantile(0.90),
                "p99": self.quantile(0.99)}

    def describe(self) -> str:
        if not self.count:
            return "n=0"
        return (f"n={self.count} mean={self.mean:.1f} p50="
                f"{self.quantile(.5):.1f} p90={self.quantile(.9):.1f} "
                f"p99={self.quantile(.99):.1f} max={self.max:.1f}")


class MetricsRegistry:
    """Named counters + histograms with text and dict export."""

    def __init__(self):
        self._counters: Dict[str, Counter] = {}
        self._hists: Dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            c = self._counters[name] = Counter()
        return c

    def histogram(self, name: str) -> Histogram:
        h = self._hists.get(name)
        if h is None:
            h = self._hists[name] = Histogram()
        return h

    def snapshot(self) -> Dict[str, Any]:
        return {
            "counters": {k: c.value
                         for k, c in sorted(self._counters.items())},
            "histograms": {k: h.snapshot()
                           for k, h in sorted(self._hists.items())},
        }

    def describe(self) -> str:
        lines = []
        if self._counters:
            lines.append("counters: " + " ".join(
                f"{k}={c.value}" for k, c in sorted(self._counters.items())))
        for k, h in sorted(self._hists.items()):
            lines.append(f"{k}: {h.describe()}")
        return "\n".join(lines) if lines else "metrics: (none)"


# ---------------------------------------------------------------------------
# spans + tracer
# ---------------------------------------------------------------------------


#: the prefix of every span's profiler annotation (``repro.slot_launch``)
PROFILER_PREFIX = "repro."


class Span:
    """One completed (or in-flight) traced region.  Context manager:
    entering stamps ``start_us`` and opens the span's profiler annotation
    (``repro.<name>``, the entry tags as its stats); exiting closes it,
    stamps ``dur_us`` — host time, nothing is fenced — and files the span
    with its tracer.  ``depth`` is the nesting level at entry (the
    span-tree proof the tests assert)."""

    __slots__ = ("name", "track", "tags", "start_us", "dur_us", "depth",
                 "_tracer", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, track: str, tags: dict):
        self._tracer = tracer
        self.name = name
        self.track = track
        self.tags = tags
        self.start_us: float = 0.0
        self.dur_us: Optional[float] = None
        self.depth: int = 0
        self._annotation = None

    def tag(self, **tags) -> "Span":
        self.tags.update(tags)
        return self

    def __enter__(self) -> "Span":
        tr = self._tracer
        self.depth = len(tr._stack)
        tr._stack.append(self)
        self._annotation = tr._annotate(PROFILER_PREFIX + self.name,
                                        **self.tags)
        self._annotation.__enter__()
        self.start_us = tr.now_us()
        return self

    def __exit__(self, *exc) -> None:
        self.dur_us = self._tracer.now_us() - self.start_us
        self._annotation.__exit__(*exc)
        self._annotation = None
        self._tracer._stack.pop()
        self._tracer.events.append(self)


class _NullSpan:
    """The reused no-op span NULL_TRACER hands out (overhead: one attribute
    lookup + two no-op calls per instrumented region)."""

    __slots__ = ()
    name = track = ""
    tags: dict = {}
    start_us = 0.0
    dur_us = None
    depth = 0

    def tag(self, **tags) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()


class Tracer:
    """Nested host-time span recorder + metrics.

    Timestamps are µs since tracer construction (``time.perf_counter``
    based).  Spans nest per the call stack (single-threaded, like the
    executor) and each one is also a ``repro.<name>`` annotation in any
    ``jax.profiler`` trace being captured; retroactive spans (``span_at``)
    and instants are recorded in memory only, on named *tracks* — so
    per-request admit→retire spans live on a "requests" track beside the
    "exec" track's launches."""

    enabled = True

    def __init__(self):
        from jax.profiler import TraceAnnotation

        self._annotate = TraceAnnotation
        self._t0 = time.perf_counter()
        self.events: List[Span] = []
        self._stack: List[Span] = []
        self.metrics = MetricsRegistry()
        self._plan_ids: Dict[int, int] = {}

    # -- time ----------------------------------------------------------
    def now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    # -- recording -----------------------------------------------------
    def span(self, name: str, track: str = "exec", **tags) -> Span:
        return Span(self, name, track, tags)

    def span_at(self, name: str, start_us: float, end_us: float,
                track: str = "exec", **tags) -> Span:
        """File an already-elapsed span (e.g. a request's admit→retire
        lifetime, closed at retirement)."""
        sp = Span(self, name, track, tags)
        sp.start_us = start_us
        sp.dur_us = max(0.0, end_us - start_us)
        self.events.append(sp)
        return sp

    def instant(self, name: str, track: str = "exec", **tags) -> Span:
        """A zero-duration marker (fault, straggler, candidate scores)."""
        sp = Span(self, name, track, tags)
        sp.start_us = self.now_us()
        self.events.append(sp)
        return sp

    def plan_id(self, plan) -> int:
        """Small stable id for a plan object (plans are cached and live as
        long as their CompiledStack, so id() aliasing is not a concern)."""
        pid = self._plan_ids.get(id(plan))
        if pid is None:
            pid = len(self._plan_ids)
            self._plan_ids[id(plan)] = pid
        return pid

    # -- export --------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """Machine-readable state: span count and metrics (counters +
        histogram quantiles)."""
        return {"spans": len(self.events),
                "metrics": self.metrics.snapshot()}

    def describe(self) -> str:
        lines = [f"trace: {len(self.events)} spans"]
        lines += self.metrics.describe().splitlines()
        return "\n".join(lines)


class NullTracer:
    """The disabled tracer: every hook is a no-op, ``enabled`` is False so
    instrumented sites skip tag and metric work entirely.  One shared
    instance (``NULL_TRACER``) serves every untraced stack."""

    enabled = False

    def __init__(self):
        self.events: List[Span] = ()  # immutable: nothing ever records
        self.metrics = MetricsRegistry()

    def now_us(self) -> float:
        return 0.0

    def span(self, name: str, track: str = "exec", **tags) -> _NullSpan:
        return _NULL_SPAN

    def span_at(self, name, start_us, end_us, track="exec",
                **tags) -> _NullSpan:
        return _NULL_SPAN

    def instant(self, name, track="exec", **tags) -> _NullSpan:
        return _NULL_SPAN

    def plan_id(self, plan) -> int:
        return 0

    def snapshot(self) -> Dict[str, Any]:
        return {"spans": 0, "metrics": self.metrics.snapshot()}

    def describe(self) -> str:
        return "trace: disabled"


NULL_TRACER = NullTracer()


def as_tracer(tracer: Optional[Tracer]) -> Tracer:
    """Normalize an optional tracer kwarg: None -> the shared no-op."""
    return NULL_TRACER if tracer is None else tracer


# ---------------------------------------------------------------------------
# the one benchmark timer (and the serving path's one clock)
# ---------------------------------------------------------------------------


def monotonic_s() -> float:
    """Monotonic seconds — the serving/runtime layers' one wall-clock for
    deadlines and tick durations.  ``analysis.repolint`` (rule
    timing-outside-obs) bans direct ``time.*`` calls on those paths so
    every measurement funnels through this module's discipline; interval
    consumers call this instead."""
    return time.monotonic()


def fence(value):
    """``jax.block_until_ready`` as a function, for callers that time
    around device work (the fault-tolerant train loop); traced spans never
    fence."""
    import jax

    return jax.block_until_ready(value)


def measure_samples(fn: Callable, *args, repeats: int = 3, warmup: int = 1,
                    **kwargs) -> List[float]:
    """The raw samples behind ``measure_us``: ``warmup`` untimed calls
    (compile/plan-cache exclusion), then ``repeats`` calls each fenced with
    ``jax.block_until_ready``, returned as a list of µs.  Callers that need
    more than one summary statistic (the calibration replay harness records
    median AND p90 per signature) consume this directly so every timed
    number in the repo still originates from this one code path."""
    import jax

    for _ in range(max(0, warmup)):
        fn(*args, **kwargs)
    ts = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        ts.append((time.perf_counter() - t0) * 1e6)
    return ts


def measure_us(fn: Callable, *args, repeats: int = 3, warmup: int = 1,
               reduce: str = "median", **kwargs) -> float:
    """Time ``fn(*args)``: ``warmup`` untimed calls (compile/plan-cache
    exclusion), then ``repeats`` calls each fenced with
    ``jax.block_until_ready``, reduced by ``median`` (default) or ``min``.
    Returns µs."""
    if reduce not in ("median", "min"):
        raise ValueError(f"measure_us: reduce={reduce!r} invalid; "
                         "allowed: median, min")
    ts = measure_samples(fn, *args, repeats=repeats, warmup=warmup, **kwargs)
    red = statistics.median if reduce == "median" else min
    return red(ts)


def slot_signature(family: str, H: int, G: int, B: int, chunk_len: int,
                   dtype: str, directions: Sequence[str] = ("fwd",),
                   chained: bool = False, precision: str = "fp32") -> str:
    """The canonical slot-signature string every layer tags launches with
    (and the measured-cost table keys on): family, G-batch width, padded B,
    H, T-stripe, dtype, direction mix, precision, chained flag.  The
    precision token (``|pint8`` / ``|pbf16``) is emitted only for
    non-fp32, so pre-existing persisted signatures stay valid — and an
    int8 measurement can never key an fp32 lookup."""
    dirs = "+".join(sorted(set(directions)))
    sig = f"{family}|H{H}|G{G}|B{B}|bt{chunk_len}|{dtype}|{dirs}"
    if precision != "fp32":
        sig += f"|p{precision}"
    return sig + "|chained" if chained else sig


__all__ = ["Tracer", "NullTracer", "NULL_TRACER", "as_tracer", "Span",
           "PROFILER_PREFIX", "Counter", "Histogram", "MetricsRegistry",
           "measure_us", "measure_samples", "monotonic_s", "fence",
           "slot_signature"]
