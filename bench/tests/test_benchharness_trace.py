"""The trace reduction: busy union, idle gaps charged to the host span
open at the time, kernel time by name; on synthetic planes and on a small
trace recorded on the chip."""
import dataclasses
from pathlib import Path

import pytest

from bench.harness import cells, record, spans, trace, work
from bench.harness.window import Outcome

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
    stats: tuple = ()   # (name, value) pairs, as ProfileData gives them


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


#: one call of a small EESEN-like stack: B x T frames of a two-layer
#: bidirectional LSTM of width 16
SHAPE = work.StackShape(hidden=16, input_size=8, layers=2, directions=2,
                        weight_bytes=4)
B, T = 2, 12
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _calls(n, cut=None, dropped=0):
    """``n`` identical offline calls 1000 ns apart, each a ``bench.input``
    [0, 100) and a ``bench.forward`` [100, 900) holding a fusion [200,
    300), ``%lstm_seq`` [400, 600) and a copy [700, 800); the window ends
    with the last call.  With ``cut`` the device record keeps only the
    operations that started before it, and its plane says ``dropped``."""
    host, ops = [], []
    for i in range(n):
        t = 1000 * i
        host += [Ev("bench.input", t, 100), Ev("bench.forward", t + 100, 800)]
        ops += [Ev(f"%fusion.{i} = f32[8]{{0}} fusion(%p)", t + 200, 100),
                Ev(f"%lstm_seq.{i} = (f32[2,8]{{1,0}}) custom-call(%x)",
                   t + 400, 200),
                Ev(f"%copy.{i} = f32[8]{{0}} copy(%y)", t + 700, 100)]
    host.append(Ev("bench.window", 0, 1000 * n - 100))
    if cut is not None:
        ops = [e for e in ops if e.start_ns < cut]
    stats = ((trace.DROPPED, dropped), ("device_type_string", "TPU v5 Lite"))
    return [Plane("/host:CPU", [Line("python", host)]),
            Plane("/device:TPU:0", [Line("XLA Ops", ops)], stats)]


def _record(summary, calls):
    """What the offline driver records for ``calls`` calls."""
    return record.RunRecord(
        peaks=PEAKS, window_s=1.0, counters={"calls": calls}, host={},
        work={"lstm_seq": work.seq_work(SHAPE, calls * B * T, calls)},
        model_flops=0.0, trace=summary,
        call_work={"lstm_seq": work.seq_work(SHAPE, B * T)})


def test_complete_trace_covers_every_call():
    s = trace.reduce_planes(_calls(4), call="bench.forward")
    assert not s.truncated and s.dropped_traces == 0
    assert s.covered_calls == 4
    assert s.window_s == pytest.approx(3900e-9)  # bench.window's end
    assert s.kernel_count("%lstm_seq") == 4
    # without a call span named, nothing is counted and nothing clipped
    s = trace.reduce_planes(_calls(4))
    assert s.covered_calls == 0 and s.readable
    assert s.window_s == pytest.approx(3900e-9)
    # the chip's own planes carry stats, and this record dropped nothing
    s = trace.reduce_file(RECORDED, window="bench.step", call="bench.step")
    assert not s.truncated and s.dropped_traces == 0
    assert s.covered_calls == 2   # its two engine steps


def test_truncated_trace_reads_as_the_calls_it_covers():
    # the record stops inside the third call: its fusion [2200, 2300) is
    # the last operation kept; the window ran on to 3900
    cut = trace.reduce_planes(_calls(4, cut=2250, dropped=5),
                              call="bench.forward")
    full = trace.reduce_planes(_calls(2), call="bench.forward")
    assert cut.truncated and cut.dropped_traces == 5
    assert cut.covered_calls == 2
    assert cut.window_s == pytest.approx(1900e-9)   # call 2's span end
    assert (cut.busy_s, cut.op_seconds, cut.op_counts) == (
        full.busy_s, full.op_seconds, full.op_counts)
    # no idle gap after the window's new end: [2300, 3900) is not idle
    assert cut.longest_gaps == full.longest_gaps
    assert cut.gap_seconds == pytest.approx(full.gap_seconds)
    assert max(g for _, g in cut.longest_gaps) == pytest.approx(400e-9)
    assert sum(cut.gap_seconds.values()) == pytest.approx(
        cut.window_s - cut.busy_s)
    # the host-phase reduction clips to the same window
    ph = spans.reduce_planes(_calls(4, cut=2250, dropped=5),
                             call="bench.forward")
    assert ph.longest_gaps == cut.longest_gaps
    assert ph.span_counts["bench.forward"] == 2

    run_cut, run_full = _record(cut, 4), _record(full, 2)
    assert record.idle_share(run_cut) == pytest.approx(
        record.idle_share(run_full))
    share = record.roofline_share(run_cut, "lstm_seq")
    assert share == pytest.approx(record.roofline_share(run_full,
                                                        "lstm_seq"))
    assert 0 < share <= 100
    # a driver whose calls differ gives no per-call work: nothing to read
    run_cut.call_work = {}
    assert record.roofline_share(run_cut, "lstm_seq") is None
    assert record.idle_share(run_cut) == pytest.approx(
        record.idle_share(run_full))


def test_work_is_scaled_to_the_covered_calls():
    cut = trace.reduce_planes(_calls(4, cut=2250, dropped=5),
                              call="bench.forward")
    got = record.traced_work(_record(cut, 4), "lstm_seq")
    assert got == pytest.approx(work.seq_work(SHAPE, 2 * B * T, 2))
    full = trace.reduce_planes(_calls(4), call="bench.forward")
    run = _record(full, 4)
    assert record.traced_work(run, "lstm_seq") == run.work["lstm_seq"]


def test_truncated_trace_without_a_covered_call_reads_nothing():
    # the record stops inside the first call
    s = trace.reduce_planes(_calls(4, cut=250, dropped=3),
                            call="bench.forward")
    assert s.truncated and s.covered_calls == 0 and not s.readable
    run = _record(s, 4)
    assert record.idle_share(run) is None
    assert record.roofline_share(run, "lstm_seq") is None
    assert record.traced_work(run, "lstm_seq") is None
    # a record with no call span named covers no call either
    s = trace.reduce_planes(_calls(4, cut=2250, dropped=3))
    assert s.covered_calls == 0 and not s.readable


@pytest.mark.parametrize("cut,readable", [(2250, True), (250, False)])
def test_result_line_reports_the_trace_coverage(monkeypatch, cut,
                                                readable):
    import jax

    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    from bench import run as bench_run

    s = trace.reduce_planes(_calls(4, cut=cut, dropped=5),
                            call="bench.forward")
    rec = _record(s, 4)
    rec.counters.update(launches=800)
    out = Outcome(attempted=8, failed=0, setup_s=1.0, metrics={},
                  checks={"max_gap": (0.0, 1.5e-3)}, problems=[],
                  memory_peak=0, record=rec)
    cell = cells.load_cell("eesen.offline", Path(__file__).resolve()
                           .parents[2])
    line = bench_run.result_line(cell, out, jax.devices(), trace=True)
    dev = line["device"]
    assert (dev["trace_calls"], dev["calls"], dev["dropped_traces"]) == (
        s.covered_calls, 4, 5)
    assert ("busy_s" in dev, "window_s" in dev, "breakdown" in line) == (
        (readable,) * 3)
    got = {k for k in line["metrics"]}
    shares = {"device_idle_share.offline", "lstm_seq_roofline.offline"}
    assert (shares <= got) == readable
    assert "launches_per_call.offline" in got
    assert list(line)[-1] == "checks"
