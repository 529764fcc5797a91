"""The trace reduction: busy union, idle gaps charged to the host span
open at the time, kernel time by name; on synthetic planes and on a small
trace recorded on the chip."""
import dataclasses
from pathlib import Path

import pytest

from bench.harness import trace

#: two engine steps of BYSDNE served on one TPU v5 lite (a prefill wave
#: of one 16-frame prompt, then a decode tick), each in a ``bench.step``
#: span, and a ``bench.wait`` after them
RECORDED = (Path(__file__).resolve().parents[1] / "testdata"
            / "two_steps.xplane.pb")


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


def _planes():
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 0, 1000),
        Ev("bench.step", 100, 400),
        Ev("bench.wait", 600, 300),
        Ev("PjitFunction(x)", 120, 10),
    ])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Ops", [
            Ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 150, 100),
            Ev("%lstm_seq.3 = (f32[1,8]{1,0}) custom-call(%x)", 200, 150),
            Ev("%copy.2 = f32[8]{0} copy(%lstm_seq.3)", 550, 50),
            Ev("%lstm_decode.1 = (f32[5,8]{1,0}) custom-call(%y)", 950,
               100)]),  # the last crosses the window's end
        Line("XLA Modules", [Ev("jit_step", 150, 200)]),
    ])
    return [host, dev, Plane("/device:TPU:0 SparseCore", [])]


def test_synthetic_union_idle_and_attribution():
    s = trace.reduce_planes(_planes())
    assert s.window_s == pytest.approx(1e-6)
    # busy: [150, 350), [550, 600) and [950, 1000) clipped to the window
    assert s.busy_s == pytest.approx(300e-9)
    assert s.n_devices == 1
    assert s.kernel_seconds("%lstm_seq") == pytest.approx(150e-9)
    assert s.kernel_seconds("%lstm_decode") == pytest.approx(50e-9)
    assert s.kernel_count("%lstm_seq") == 1
    assert s.kernel_count("%copy") == 1  # an operand is not the kernel
    # idle [0,150) window, [350,550) step (mid 450), [600,950) wait
    assert s.gap_seconds["bench.window"] == pytest.approx(150e-9)
    assert s.gap_seconds["bench.step"] == pytest.approx(200e-9)
    assert s.gap_seconds["bench.wait"] == pytest.approx(350e-9)
    assert s.longest_gaps[0] == ("bench.wait", pytest.approx(350e-9))
    bd = s.breakdown()
    assert bd["device_ops"][0][0] == "%lstm_seq"
    assert len(bd["idle_gaps"]) <= 10


def test_a_trace_without_window_or_device_is_refused():
    planes = _planes()
    with pytest.raises(ValueError):
        trace.reduce_planes([planes[1]])
    with pytest.raises(ValueError):
        trace.reduce_planes([planes[0]])


def test_recorded_chip_trace():
    s = trace.reduce_file(RECORDED, window="bench.step")
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.kernel_count("%lstm_seq") >= 1
    assert s.kernel_count("%lstm_decode") >= 1
    assert s.kernel_seconds("%lstm_decode") > 0
    assert set(s.gap_seconds) <= {"bench.step", "bench.wait"}
    assert sum(s.gap_seconds.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
