"""The split of an offline cell's host time across the program's phases,
and what tracing costs, in one process on the chip.

    python3 bench/phases.py --config <config> --traffic <mix> \\
        --seconds <s> --pairs <n> [--seed <n>]

Builds the configuration's stack from the seed twice, with
``ExecutionPolicy(trace=False)`` and with ``trace=True``, and calls each
once at the mix's shape.  Then, with the mix's closed loop of forward
calls (as ``harness/offline.py`` runs it):

1. ``--pairs`` pairs of windows of ``--seconds`` each, the profiler off,
   alternating which stack runs first: frames per second with tracing off
   and on (the cost of tracing is their ratio);
2. one window of the traced stack under the profiler: per forward call,
   the self time of each ``repro.*`` span (``harness/spans.py``) beside
   the host time of the ``bench.forward`` spans, the device's busy time,
   and the ten longest idle gaps, each charged to the innermost span.

Prints one JSON line per window, the profiled one last.  The benchmark's
own runs (``bench/run.py``) never run this.
"""
from __future__ import annotations

import argparse
import glob
import json
import shutil
import statistics
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
for _p in (CHECKOUT, CHECKOUT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402

from bench.harness import cells, device, spans, trace  # noqa: E402
from bench.harness.offline import make_params  # noqa: E402
from bench.harness.window import annotate, now, seeded_input  # noqa: E402

PROFILE_DIR = CHECKOUT / ".bench_trace" / "phases"


def closed_loop(cs, seed: int, in_shape, seconds: float) -> dict:
    """Forward calls back to back for ``seconds``, as the offline cells
    run them; frames per second over the window."""
    calls = 0
    with annotate("bench.window"):
        t0 = now()
        while True:
            with annotate("bench.input"):
                xs = seeded_input(seed, calls, in_shape)
            with annotate("bench.forward"):
                jax.block_until_ready(cs.forward(xs))
            t1 = now()
            calls += 1
            if t1 - t0 >= seconds:
                break
    frames = calls * in_shape[0] * in_shape[1]
    return {"calls": calls, "window_s": t1 - t0,
            "frames_per_s": frames / (t1 - t0)}


def profiled_loop(cs, seed: int, in_shape, seconds: float,
                  directory: Path) -> dict:
    """``closed_loop`` under the profiler, reduced to host ms per call by
    span and the device's busy time."""
    shutil.rmtree(directory, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        out = closed_loop(cs, seed, in_shape, seconds)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(directory / "plugins/profile/*/*.xplane.pb"))
    planes = list(jax.profiler.ProfileData.from_file(path).planes)
    shutil.rmtree(directory, ignore_errors=True)
    dev = trace.reduce_planes(planes, call="bench.forward")
    if not dev.readable:
        raise RuntimeError("the device record covers no whole call")
    ph = spans.reduce_planes(planes, call="bench.forward")
    # per call of those the device record covers (all, unless truncated)
    calls = dev.covered_calls

    def per_call(d):
        return {k: v * 1e3 / calls for k, v in sorted(d.items())}

    phases = {k: v for k, v in ph.span_self_seconds.items()
              if k.startswith(spans.PHASE)}
    forward_s = ph.span_seconds.get("bench.forward", 0.0)
    out.update(
        trace_calls=calls, dropped_traces=dev.dropped_traces,
        busy_s=dev.busy_s, traced_window_s=dev.window_s,
        idle_share=100.0 * (1.0 - dev.busy_s / dev.window_s),
        self_ms_per_call=per_call(phases),
        total_ms_per_call=per_call(ph.span_seconds),
        counts_per_call={k: v / calls for k, v in
                         sorted(ph.span_counts.items())},
        self_sum_over_forward=(sum(phases.values()) / forward_s
                               if forward_s else None),
        idle_ms_per_call=per_call(ph.gap_seconds),
        longest_gaps=ph.longest_gaps)
    return out


def measure(cell, seed: int, seconds: float, pairs: int,
            profile_dir: Path, interpret: bool = False):
    """Yield one dict per window: the cost pairs, then the profiled one."""
    from repro import rnn

    cfg, mix = cell.config, cell.traffic
    in_shape = (int(mix["batch"]), int(mix["frames"]), cfg["input_size"])
    params = make_params(cfg, seed)
    stacks = {trace_on: rnn.compile(params, rnn.ExecutionPolicy(
        interpret=interpret, trace=trace_on)) for trace_on in (False, True)}
    warm = seeded_input(seed, 1 << 30, in_shape)
    for cs in stacks.values():
        jax.block_until_ready(cs.forward(warm))
    for i in range(pairs):
        for trace_on in ((False, True) if i % 2 == 0 else (True, False)):
            out = closed_loop(stacks[trace_on], seed, in_shape, seconds)
            yield dict(out, window="cost", pair=i, trace=trace_on)
    yield dict(profiled_loop(stacks[True], seed, in_shape, seconds,
                             profile_dir), window="profiled", trace=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--pairs", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = cells.from_files(f"{args.config}.{args.traffic}",
                            f"bench/configs/{args.config}.json",
                            args.traffic, CHECKOUT)
    if cell.traffic["loop"] != "closed":
        print("phases: only closed-loop (offline) mixes", file=sys.stderr)
        return 2
    try:
        devices = device.require_tpu(cell.chips)
    except device.NoChip as err:
        print(f"phases: {err}", file=sys.stderr)
        return 2
    device.enable_compile_cache(CHECKOUT)
    rates = {False: [], True: []}
    for out in measure(cell, args.seed, args.seconds, args.pairs,
                       PROFILE_DIR):
        out["device"] = devices[0].device_kind
        if out["window"] == "cost":
            rates[out["trace"]].append(out["frames_per_s"])
        print(json.dumps(out), flush=True)
    if args.pairs:
        off, on = (statistics.median(rates[k]) for k in (False, True))
        print(json.dumps({"window": "summary", "frames_per_s_off": off,
                          "frames_per_s_on": on,
                          "tracing_cost": 1.0 - on / off}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
