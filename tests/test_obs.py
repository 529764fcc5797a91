"""runtime.obs unit tests: span nesting, histogram quantiles on known
data, the no-op tracer path, each span's profiler annotation, and the
shared bench timer."""
import pytest

from repro.runtime.obs import (PROFILER_PREFIX, Counter, Histogram,
                               MetricsRegistry, NULL_TRACER, NullTracer,
                               Tracer, as_tracer, measure_us, slot_signature)


# ---------------------------------------------------------------------------
# counters + histograms
# ---------------------------------------------------------------------------


def test_counter():
    c = Counter()
    assert c.value == 0
    c.add()
    c.add(4)
    assert c.value == 5


def test_histogram_quantiles_known_data():
    h = Histogram()
    for v in range(1, 101):  # 1..100: nearest-rank quantiles are exact
        h.observe(float(v))
    assert h.count == 100
    assert h.quantile(0.5) == 50.0
    assert h.quantile(0.9) == 90.0
    assert h.quantile(0.99) == 99.0
    assert h.quantile(1.0) == 100.0
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["min"] == 1.0 and snap["max"] == 100.0
    assert snap["mean"] == pytest.approx(50.5)
    assert snap["p50"] == 50.0 and snap["p90"] == 90.0


def test_histogram_reservoir_is_bounded_but_stats_exact():
    h = Histogram(cap=64)
    n = 10_000
    for v in range(n):
        h.observe(float(v))
    assert h.count == n                      # full count kept
    assert len(h._sample) == 64              # memory bounded
    assert h.min == 0.0 and h.max == n - 1   # exact extremes
    assert h.mean == pytest.approx((n - 1) / 2)
    # reservoir quantile is approximate but must stay in range
    assert 0.0 <= h.quantile(0.5) <= n - 1


def test_histogram_empty():
    h = Histogram()
    assert h.quantile(0.5) == 0.0
    assert h.snapshot()["count"] == 0


def test_metrics_registry_reuses_instruments():
    m = MetricsRegistry()
    assert m.counter("a") is m.counter("a")
    assert m.histogram("b") is m.histogram("b")
    m.counter("a").add(2)
    m.histogram("b").observe(3.0)
    snap = m.snapshot()
    assert snap["counters"]["a"] == 2
    assert snap["histograms"]["b"]["count"] == 1
    assert "a" in m.describe() and "b" in m.describe()


# ---------------------------------------------------------------------------
# spans + tracer
# ---------------------------------------------------------------------------


def test_span_nesting_depth_and_timing():
    tr = Tracer()
    with tr.span("outer", a=1):
        with tr.span("inner"):
            pass
    # inner files first (exits first), depths record the nesting
    names = [(s.name, s.depth) for s in tr.events]
    assert names == [("inner", 1), ("outer", 0)]
    inner, outer = tr.events
    assert outer.tags == {"a": 1}
    assert outer.start_us <= inner.start_us
    assert (inner.start_us + inner.dur_us
            <= outer.start_us + outer.dur_us + 1e-6)


def test_span_tag_and_span_at_and_instant():
    tr = Tracer()
    with tr.span("s") as sp:
        sp.tag(extra="x")
    assert tr.events[0].tags == {"extra": "x"}
    sp = tr.span_at("req", 10.0, 25.0, track="requests", uid=7)
    assert sp.dur_us == 15.0 and sp.track == "requests"
    tr.instant("fault", slot=3)
    assert tr.events[-1].dur_us is None  # instants have no duration


def test_plan_id_stable_and_sequential():
    tr = Tracer()
    a, b = object(), object()
    assert tr.plan_id(a) == 0
    assert tr.plan_id(b) == 1
    assert tr.plan_id(a) == 0


def test_slot_signature_format():
    assert (slot_signature("lstm", 64, 2, 1, 12, "float32")
            == "lstm|H64|G2|B1|bt12|float32|fwd")
    assert (slot_signature("gru", 96, 1, 4, 1, "bfloat16",
                           directions=("fwd", "bwd"), chained=True)
            == "gru|H96|G1|B4|bt1|bfloat16|bwd+fwd|chained")


# ---------------------------------------------------------------------------
# the no-op path
# ---------------------------------------------------------------------------


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    assert isinstance(NULL_TRACER, NullTracer)
    sp = NULL_TRACER.span("x", a=1)
    assert sp is NULL_TRACER.span("y")  # one reused span object
    with sp as s:
        s.tag(b=2)
    assert NULL_TRACER.events == ()     # nothing ever recorded
    NULL_TRACER.instant("x")
    NULL_TRACER.span_at("x", 0.0, 1.0)
    snap = NULL_TRACER.snapshot()
    assert set(snap) == {"spans", "metrics"} and snap["spans"] == 0
    assert not hasattr(NULL_TRACER, "fence")


def test_as_tracer():
    assert as_tracer(None) is NULL_TRACER
    tr = Tracer()
    assert as_tracer(tr) is tr


# ---------------------------------------------------------------------------
# profiler annotations
# ---------------------------------------------------------------------------


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each enter and
    exit with the annotation's name and tags."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **tags):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name, tags))

            def __exit__(self, *exc):
                log.append(("exit", name, exc[0]))

        return _Ann()


def test_span_enters_and_exits_its_profiler_annotation():
    tr = Tracer()
    tr._annotate = ann = _Annotations()
    with tr.span("forward", B=2):
        with tr.span("slot_launch", slot=0, sig="lstm|H8"):
            pass
    tr.span_at("request", 0.0, 1.0, uid=3)   # retroactive: memory only
    tr.instant("launch_fault", slot=0)       # instants: memory only
    assert PROFILER_PREFIX == "repro."
    assert ann.log == [
        ("enter", "repro.forward", {"B": 2}),
        ("enter", "repro.slot_launch", {"slot": 0, "sig": "lstm|H8"}),
        ("exit", "repro.slot_launch", None),
        ("exit", "repro.forward", None),
    ]


def test_span_closes_its_annotation_when_the_region_raises():
    tr = Tracer()
    tr._annotate = ann = _Annotations()
    with pytest.raises(KeyError):
        with tr.span("outer"):
            with tr.span("inner"):
                raise KeyError("boom")
    assert [(k, n) for k, n, _ in ann.log] == [
        ("enter", "repro.outer"), ("enter", "repro.inner"),
        ("exit", "repro.inner"), ("exit", "repro.outer")]
    assert ann.log[2][2] is KeyError
    assert tr._stack == []                   # the span stack unwound
    assert [s.name for s in tr.events] == ["inner", "outer"]


def test_null_tracer_opens_no_annotation(monkeypatch):
    import jax.profiler

    def refuse(*a, **k):
        raise AssertionError("the untraced path opened an annotation")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    with NULL_TRACER.span("forward", B=1):
        with NULL_TRACER.span("slot_launch", slot=0):
            pass
    assert NULL_TRACER.events == ()


# ---------------------------------------------------------------------------
# the shared bench timer
# ---------------------------------------------------------------------------


def test_measure_us_warmup_excluded_and_positive():
    calls = []

    def fn(x):
        calls.append(x)
        return x

    us = measure_us(fn, 1, repeats=3, warmup=2, reduce="median")
    assert us >= 0.0
    assert len(calls) == 5  # 2 warmup + 3 timed

    assert measure_us(fn, 1, repeats=2, reduce="min") >= 0.0


def test_measure_us_rejects_bad_reduce():
    with pytest.raises(ValueError):
        measure_us(lambda: None, reduce="mean")


def test_tracer_describe_mentions_spans():
    tr = Tracer()
    with tr.span("s"):
        pass
    assert "1 spans" in tr.describe()
