"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and per-layer metrics are found by
name from ``BENCHMARK.json`` (``bench/harness/cells.py``).  Set-up (weights
from the seed, compilation, warm-up of the cell's own shapes) counts as
``setup_s``; then the window runs for ``--seconds``.  With ``--trace 0``
the result carries the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the profiler and the result carries its per-layer
metrics, the device's busy time and a breakdown, all over the calls the
device record covers: ``device`` gives their count (``trace_calls``)
beside the window's (``calls``) and the profiler's ``dropped_traces``.

Every run checks what the timed path produced against the plain reference
(``bench/reference``) and prints each number compared beside its limit,
last on standard error and last in the result line.  The last line of
standard output is the result, one JSON object.  Without a TPU, or with
fewer chips than the cell asks for, the run exits with 2 and prints no
result.
"""
from __future__ import annotations

import time

CLOCK0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
# the TPU runtime's own log files would otherwise go to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for _p in (CHECKOUT, CHECKOUT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench.harness import cells, device  # noqa: E402
from bench.harness.stats import percentile  # noqa: E402
from bench.harness.work import load_peaks  # noqa: E402

DRIVERS = {"closed": "bench.harness.offline", "open": "bench.harness.serve"}
TRACE_DIR = CHECKOUT / ".bench_trace"


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _number(x):
    """A JSON number, or null where the reading is not finite."""
    x = float(x)
    return x if x == x and abs(x) != float("inf") else None


def result_line(cell, out, devices, trace: bool) -> dict:
    """The contract's result object; the compared numbers come last."""
    rec = out.record
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = m.read(rec)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}
    else:
        units = {m.name: m.unit for m in cell.end_to_end}
        metrics = {name: {"value": _number(v), "unit": units[name]}
                   for name, v in out.metrics.items()
                   if name in units and _number(v) is not None}
        metrics["setup_s"] = {"value": float(out.setup_s), "unit": "s"}
    dev = device.describe(devices, out.memory_peak)
    line = {"correct": out.correct, "attempted": int(out.attempted),
            "failed": int(out.failed), "metrics": metrics, "device": dev}
    if trace and rec.trace is not None:
        t = rec.trace
        if t.readable:
            dev["busy_s"] = t.busy_s
            dev["window_s"] = t.window_s
            line["breakdown"] = t.breakdown()
        # how much of the window the device record covers (trace.py)
        dev.update(trace_calls=t.covered_calls,
                   calls=int(rec.counters["calls"]),
                   dropped_traces=t.dropped_traces)
    line["checks"] = {name: {"value": _number(v), "limit": lim}
                      for name, (v, lim) in out.checks.items()}
    return line


def main(argv=None) -> int:
    args = _args(argv)
    cell = cells.load_cell(args.workload, CHECKOUT)
    try:
        devices = device.require_tpu(cell.chips)
    except device.NoChip as err:
        print(f"bench: {err}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 2
    peaks = load_peaks(CHECKOUT / "bench" / "peaks.json",
                       devices[0].device_kind)
    device.enable_compile_cache(CHECKOUT)
    compiles = device.CompileCounter()
    import importlib

    driver = importlib.import_module(DRIVERS[cell.traffic["loop"]])
    out = driver.run(cell, args.seed, args.seconds,
                     TRACE_DIR / args.workload if args.trace else None,
                     devices, compiles, CLOCK0)
    out.record.peaks = peaks
    line = result_line(cell, out, devices, bool(args.trace))

    counters = " ".join(f"{k}={v}" for k, v in out.record.counters.items())
    print(f"bench: {cell.name} seed={args.seed} window="
          f"{out.record.window_s:.3f}s {counters}", file=sys.stderr)
    late = out.record.host.get("late_ms")
    if late:
        # how late the open-loop generator submitted requests
        print(f"bench: submit lateness p50 {percentile(late, 50):.3f} ms, "
              f"max {max(late):.3f} ms", file=sys.stderr)
    for problem in out.problems:
        print(f"bench: NOT CORRECT: {problem}", file=sys.stderr)
    for name, (v, lim) in out.checks.items():
        print(f"bench: check {name} = {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
