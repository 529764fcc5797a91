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


def idle_share(run: RunRecord) -> Optional[float]:
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)


def roofline_share(run: RunRecord, kernel: str) -> Optional[float]:
    """Least time the chip could take for the kernel's work over the
    kernel's device time in the trace, in %; None when the kernel did not
    run (a share is never reported as 0)."""
    if run.trace is None or kernel not in run.work:
        return None
    seconds = run.trace.kernel_seconds(KERNEL_OPS[kernel])
    if seconds <= 0:
        return None
    least, _ = least_time_s(*run.work[kernel], run.peaks)
    return 100.0 * least / seconds


def mfu(run: RunRecord) -> Optional[float]:
    """Model FLOPs of the frames done over the window, as a share of the
    chip's bfloat16 peak, in %."""
    if run.model_flops <= 0:
        return None
    return 100.0 * run.model_flops / run.window_s / run.peaks[
        "bf16_flops_per_s"]
