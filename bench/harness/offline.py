"""Offline cells: a closed loop of back-to-back whole-sequence calls.

Set-up makes the weights from the seed, compiles the stack and runs one
call at the window's shape, so every program the window uses is built.
The window then calls ``CompiledStack.forward`` back to back on inputs
drawn from the seed, call by call, until ``seconds`` have passed; the call
under way at that moment completes and counts.  Frames per second is the
frames of all completed calls over the time from the window's start to
the last call's end.  Each call runs in a ``bench.forward`` span, the
unit a truncated trace is counted in (``harness/trace.py``); every call
does the same work.

Correctness: the outputs of a sample of calls (drawn from the seed, call 0
always among them) are kept on the device; after the window and the
memory reading, the stack is freed and the reference runs over the same
inputs.  Any launch that degraded down the program's fallback ladder, or
any fault it recorded, fails the run too.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

import jax

from bench.harness import check, stats
from bench.harness.device import memory_peak_bytes
from bench.harness.record import RunRecord
from bench.harness.window import (Outcome, annotate, now, profiled,
                                  seeded_input)
from bench.harness.work import StackShape, model_flops_per_frame, seq_work
from bench.reference import lstm as reference

#: calls whose outputs are compared: call 0 and a seeded draw of
#: ``KEEP_DRAWN`` more among the first ``KEEP_FROM``
KEEP_FROM, KEEP_DRAWN = 8, 2


def kept_calls(seed: int) -> set:
    rng = np.random.default_rng([seed % (1 << 63), 5])
    return {0} | {int(i) for i in rng.choice(np.arange(1, KEEP_FROM),
                                             KEEP_DRAWN, replace=False)}


def make_params(config: dict, seed: int):
    return reference.make_params(
        seed, hidden=config["hidden_size"], input_size=config["input_size"],
        layers=config["num_layers"], bidirectional=config["bidirectional"],
        dtype=config["weight_dtype"])


def run(cell, seed: int, seconds: float, profile_dir, devices, compiles,
        clock0: float, interpret: bool = False,
        control: Optional[str] = None) -> Outcome:
    """One run of an offline cell.  ``control`` (a reference mode of
    ``bench/reference/lstm.py``) adds the control's readings over the same
    inputs; the benchmark's runs leave it None."""
    from repro import rnn

    cfg, mix = cell.config, cell.traffic
    shape = StackShape.of(cfg)
    B, T = int(mix["batch"]), int(mix["frames"])
    in_shape = (B, T, shape.input_size)
    params = make_params(cfg, seed)
    cs = rnn.compile(params, rnn.ExecutionPolicy(interpret=interpret,
                                                 trace=False))
    keep = kept_calls(seed)

    # set-up: the one shape the window uses (inputs of another index)
    jax.block_until_ready(cs.forward(seeded_input(seed, 1 << 30, in_shape)))
    s0 = dict(vars(cs.stats))
    c0 = compiles.snapshot()
    setup_s = now() - clock0

    kept, calls = {}, 0
    with profiled(profile_dir, call="bench.forward") as tr:
        with annotate("bench.window"):
            t0 = now()
            while True:
                with annotate("bench.input"):
                    xs = seeded_input(seed, calls, in_shape)
                with annotate("bench.forward"):
                    ys = jax.block_until_ready(cs.forward(xs))
                t1 = now()
                if calls in keep:
                    kept[calls] = ys
                calls += 1
                if t1 - t0 >= seconds:
                    break
    window = t1 - t0
    frames = calls * B * T
    mem = memory_peak_bytes(devices)
    st = vars(cs.stats)
    counters = {k: st[k] - s0[k] for k in
                ("launches", "forward_calls", "plans_built",
                 "degraded_launches", "faults_total")}
    counters["compiles"] = compiles.snapshot() - c0
    counters["calls"] = calls
    del cs

    problems = []
    if counters["degraded_launches"] or counters["faults_total"]:
        problems.append(f"{counters['degraded_launches']} degraded launches, "
                        f"{counters['faults_total']} faults in the window")
    spec, precision = mix["check"], cfg["matmul_precision"]
    outs, refs, ctrls = [], [], []
    for i in sorted(kept):
        xs = seeded_input(seed, i, in_shape)
        refs += list(np.asarray(reference.stack_forward(
            params, xs, precision=precision)))
        outs += list(np.asarray(kept[i]))
        if control:
            ctrls += list(np.asarray(reference.stack_forward(
                params, xs, mode=control, precision=precision)))
    values = check.readings(outs, refs)
    checks = check.compared(values, spec["limits"])
    control_values = check.readings(ctrls, refs) if control else {}
    failed = calls * B if problems else sum(
        g > spec["limits"].get("max_gap", float("inf"))
        for g in check.answer_gaps(outs, refs))

    record = RunRecord(
        peaks={}, window_s=window,
        counters=counters, host={},
        work={"lstm_seq": seq_work(shape, frames, calls)},
        model_flops=frames * model_flops_per_frame(shape), trace=tr.summary,
        call_work={"lstm_seq": seq_work(shape, B * T)})
    return Outcome(attempted=calls * B, failed=failed,
                   setup_s=setup_s,
                   metrics={"offline_frames_per_s": stats.rate(frames,
                                                               window)},
                   checks=checks, problems=problems, memory_peak=mem,
                   record=record, readings=values,
                   control_readings=control_values)
