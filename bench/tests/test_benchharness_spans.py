"""The host-phase reduction (``harness/spans.py``): self time of nested
``repro.*`` spans, idle gaps charged to the innermost span of either
prefix, clipping to the window; on synthetic planes, on the recorded chip
trace, and on a CPU capture of a traced forward.  And the phases
command's closed loop with tracing off and on."""
import dataclasses
import itertools
from pathlib import Path

import jax
import pytest

from bench import phases
from bench.harness import spans, trace

#: two engine steps of BYSDNE served on one TPU v5 lite, each in a
#: ``bench.step`` span (``test_benchharness_trace.py``)
RECORDED = (Path(__file__).resolve().parents[1] / "testdata"
            / "two_steps.xplane.pb")

SEED = 2 ** 31 + 7


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def _planes(host_events, ops=(), other_thread=()):
    host = Plane("/host:CPU", [Line("python", host_events),
                               Line("worker", list(other_thread))])
    dev = Plane("/device:TPU:0", [Line("XLA Ops", [
        Ev(f"%fusion.{i} = f32[8]{{0}} fusion(%p)", a, d)
        for i, (a, d) in enumerate(ops)])])
    return [host, dev]


def test_self_time_of_nested_repro_spans():
    # forward [100, 900) holds hoist [150, 250), slot_launch [300, 700)
    # with a fallback_rung [400, 600) inside it, scatter [700, 800)
    ph = spans.reduce_planes(_planes([
        Ev("bench.window", 0, 1000),
        Ev("bench.forward", 50, 900),
        Ev("repro.forward", 100, 800),
        Ev("repro.hoist", 150, 100),
        Ev("repro.slot_launch", 300, 400),
        Ev("repro.fallback_rung", 400, 200),
        Ev("repro.scatter", 700, 100),
        Ev("PjitFunction(x)", 160, 10),          # no prefix: not a span
    ], ops=[(0, 10)], other_thread=[Ev("repro.plan", 100, 800)]))
    ns = 1e-9
    assert ph.span_seconds["repro.forward"] == pytest.approx(800 * ns)
    assert ph.span_self_seconds["repro.forward"] == pytest.approx(200 * ns)
    assert ph.span_self_seconds["repro.slot_launch"] == pytest.approx(
        200 * ns)
    assert ph.span_self_seconds["repro.fallback_rung"] == pytest.approx(
        200 * ns)
    assert ph.span_self_seconds["repro.hoist"] == pytest.approx(100 * ns)
    # a span of another thread is nobody's child
    assert ph.span_self_seconds["repro.plan"] == pytest.approx(800 * ns)
    assert ph.span_self_seconds["bench.forward"] == pytest.approx(100 * ns)
    assert ph.span_counts == {"bench.forward": 1, "repro.forward": 1,
                              "repro.hoist": 1, "repro.slot_launch": 1,
                              "repro.fallback_rung": 1, "repro.scatter": 1,
                              "repro.plan": 1}
    # the program's self times add up to its outer span
    own = sum(v for k, v in ph.span_self_seconds.items()
              if k.startswith(spans.PHASE) and k != "repro.plan")
    assert own == pytest.approx(ph.span_seconds["repro.forward"])


def test_idle_gap_is_charged_to_the_innermost_repro_span():
    ph = spans.reduce_planes(_planes([
        Ev("bench.window", 0, 1000),
        Ev("bench.forward", 0, 1000),
        Ev("repro.forward", 10, 980),
        Ev("repro.hoist", 100, 300),
        Ev("repro.slot_launch", 400, 300),
    ], ops=[(0, 150), (350, 100), (600, 400)]))
    # idle [150, 350) mid 250 in hoist, [450, 600) mid 525 in slot_launch
    assert ph.longest_gaps == [("repro.hoist", pytest.approx(200e-9)),
                               ("repro.slot_launch",
                                pytest.approx(150e-9))]
    assert "bench.forward" not in ph.gap_seconds
    # the same planes through trace.py charge both to bench.forward
    s = trace.reduce_planes(_planes([
        Ev("bench.window", 0, 1000), Ev("bench.forward", 0, 1000),
        Ev("repro.hoist", 100, 300)], ops=[(0, 150), (350, 100),
                                          (600, 400)]))
    assert {n for n, _ in s.longest_gaps} == {"bench.forward"}


def test_spans_crossing_the_window_are_clipped():
    ph = spans.reduce_planes(_planes([
        Ev("bench.window", 100, 500),
        Ev("repro.forward", 0, 200),       # [0, 200) -> [100, 200)
        Ev("repro.hoist", 50, 100),        # [50, 150) -> [100, 150)
        Ev("repro.forward", 400, 300),     # [400, 700) -> [400, 600)
        Ev("repro.scatter", 550, 100),     # [550, 650) -> [550, 600)
        Ev("repro.pack", 700, 50),         # outside: not counted
    ], ops=[(100, 10)]))
    ns = 1e-9
    assert ph.span_seconds["repro.forward"] == pytest.approx(300 * ns)
    assert ph.span_self_seconds["repro.forward"] == pytest.approx(200 * ns)
    assert ph.span_seconds["repro.hoist"] == pytest.approx(50 * ns)
    assert ph.span_seconds["repro.scatter"] == pytest.approx(50 * ns)
    assert ph.span_counts["repro.forward"] == 2
    assert "repro.pack" not in ph.span_counts


def test_a_trace_without_the_window_is_refused():
    with pytest.raises(ValueError):
        spans.reduce_planes(_planes([Ev("repro.forward", 0, 10)]))


def test_recorded_chip_trace_gaps_match_trace_reduction():
    """With no repro.* span in it, the recorded trace's gaps are charged
    exactly as ``trace.py`` charges them."""
    planes = list(jax.profiler.ProfileData.from_file(str(RECORDED)).planes)
    ph = spans.reduce_planes(planes, window="bench.step")
    s = trace.reduce_planes(planes, window="bench.step")
    assert ph.longest_gaps == s.longest_gaps
    assert ph.gap_seconds == pytest.approx(s.gap_seconds)
    assert not any(k.startswith(spans.PHASE) for k in ph.span_counts)


def test_cpu_capture_of_a_traced_forward(tmp_path):
    """A real profile: the phases of a traced forward inside bench spans
    add up to its repro.forward span, which bench.forward encloses."""
    from repro import rnn
    from bench.harness.offline import make_params
    from bench.harness.window import annotate, seeded_input

    cfg = dict(hidden_size=16, input_size=8, num_layers=2,
               bidirectional=True, weight_dtype="float32")
    cs = rnn.compile(make_params(cfg, SEED),
                     rnn.ExecutionPolicy(interpret=True, trace=True))
    xs = seeded_input(SEED, 0, (2, 12, 8))
    cs.forward(xs)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with annotate("bench.window"):
            for _ in range(2):
                with annotate("bench.forward"):
                    jax.block_until_ready(cs.forward(xs))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    ph = spans.reduce_planes(
        jax.profiler.ProfileData.from_file(str(path)).planes)
    n_slots = len(cs.plan.slots)
    for name in ("repro.hoist", "repro.pack", "repro.slot_launch",
                 "repro.scatter"):
        assert ph.span_counts[name] == 2 * n_slots
        assert ph.span_self_seconds[name] > 0
    assert ph.span_counts["repro.forward"] == 2
    own = sum(v for k, v in ph.span_self_seconds.items()
              if k.startswith(spans.PHASE))
    assert own == pytest.approx(ph.span_seconds["repro.forward"], rel=1e-6)
    assert own <= ph.span_seconds["bench.forward"]


def test_phases_cost_windows_run_both_stacks(small_cell):
    cell = small_cell("eesen.offline")
    outs = list(itertools.islice(
        phases.measure(cell, SEED, 0.2, 1, None, interpret=True), 2))
    assert [(o["window"], o["trace"]) for o in outs] == [
        ("cost", False), ("cost", True)]
    for o in outs:
        assert o["calls"] >= 1 and o["frames_per_s"] > 0


def test_phases_refuses_to_run_without_a_chip(capsys):
    assert phases.main(["--config", "eesen", "--traffic",
                        "offline_b64_t300", "--seconds", "1"]) == 2
    assert "no TPU" in capsys.readouterr().err
