"""What one run leaves for the per-layer metric readers, and the helpers
those readers share."""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

from bench.harness.trace import TraceSummary
from bench.harness.work import least_time_s

#: each kernel's device operation, as the profiler names it on the chip:
#: the Pallas custom call carries the name of the jitted function that
#: wraps it (``kernels/lstm_cell/ops.py``), e.g. ``%lstm_seq.1 = (...)
#: custom-call(...)``; read by hand from a trace of TPU v5 lite
KERNEL_OPS = {"lstm_seq": "%lstm_seq", "lstm_decode": "%lstm_decode"}


@dataclasses.dataclass
class RunRecord:
    peaks: dict
    window_s: float                       # host clock, the measured loop
    counters: Dict[str, float]            # program counters, window deltas
    host: Dict[str, list]                 # host-clock samples
    work: Dict[str, Tuple[float, float]]  # kernel -> (flops, bytes)
    model_flops: float                    # model FLOPs of the window's frames
    trace: Optional[TraceSummary] = None
    #: kernel -> (flops, bytes) of one call, where every call of the
    #: window does the same work; what a truncated trace's covered calls
    #: are counted by
    call_work: Dict[str, Tuple[float, float]] = dataclasses.field(
        default_factory=dict)


def _trace(run: RunRecord) -> Optional[TraceSummary]:
    t = run.trace
    return t if t is not None and t.readable else None


def traced_work(run: RunRecord, kernel: str):
    """(flops, bytes) of the kernel's work in the calls the trace covers:
    the whole window's on a complete trace, the covered calls' on a
    truncated one; None where that is not known."""
    t = _trace(run)
    if t is None:
        return None
    if not t.truncated:
        return run.work.get(kernel)
    if kernel not in run.call_work:
        return None
    flops, nbytes = run.call_work[kernel]
    return flops * t.covered_calls, nbytes * t.covered_calls


def idle_share(run: RunRecord) -> Optional[float]:
    t = _trace(run)
    if t is None:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def roofline_share(run: RunRecord, kernel: str) -> Optional[float]:
    """Least time the chip could take for the kernel's work over the
    kernel's device time in the trace, in %, both over the calls the trace
    covers; None when the kernel did not run (a share is never reported
    as 0) or its work there is not known."""
    work = traced_work(run, kernel)
    if work is None:
        return None
    seconds = run.trace.kernel_seconds(KERNEL_OPS[kernel])
    if seconds <= 0:
        return None
    least, _ = least_time_s(*work, run.peaks)
    return 100.0 * least / seconds


def mfu(run: RunRecord) -> Optional[float]:
    """Model FLOPs of the frames done over the window, as a share of the
    chip's bfloat16 peak, in %."""
    if run.model_flops <= 0:
        return None
    return 100.0 * run.model_flops / run.window_s / run.peaks[
        "bf16_flops_per_s"]
