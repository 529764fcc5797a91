"""Serving cells: open-loop arrivals into ``RecurrentServingEngine``.

Set-up makes the weights from the seed, builds the engine and drives a
fixed warm-up (the same for every seed): for each wave size k from 1 to
``max_batch``, ``warmup_waves`` waves of k prompts with lengths taken in
turn from the mix's own length quantiles, each request asking for two
fed-back frames (none where the mix decodes nothing), so prefill waves of
every size, every slot and decode ticks of every active-row count compile
before the window.

The window submits each request when it falls due (``harness.traffic``),
steps the engine while it holds work, and otherwise sleeps until the next
due time.  After the last arrival it drains, for at most ``DRAIN_S``
seconds; a request still unfinished then has failed.  Time to first frame
runs from a request's due time to the return of the ``step()`` that
delivered its prompt outputs; a failed request counts with the time at
which the run gave up on it.  Each frame a request's client receives is
stamped with the return of the ``step()`` that delivered it, and the frame
gaps are the differences between a request's consecutive stamps, the
prompt outputs first.

Each engine step runs in a ``bench.step`` span, the unit a truncated
trace is counted in (``harness/trace.py``).  Steps differ in their work,
so on a truncated trace the kernels' roofline shares read nothing; the
idle share covers the covered steps.

Correctness: a sample of finished requests drawn from the seed, the
longest request among them, is compared teacher-forced with the reference
(``harness.check``).  A request that failed, a degraded launch or a
recorded fault fails the run.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

import jax

from bench.harness import check, stats, traffic
from bench.harness.device import memory_peak_bytes
from bench.harness.offline import make_params
from bench.harness.record import RunRecord
from bench.harness.window import Outcome, annotate, now, profiled
from bench.harness.work import (StackShape, decode_work, model_flops_per_frame,
                                seq_work)
from bench.reference import lstm as reference

DRAIN_S = 60.0
WARMUP_SEED = 0x5EED


def model_config(config: dict):
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=config["name"], family="rnn", n_layers=config["num_layers"],
        d_model=config["hidden_size"], n_heads=1, n_kv_heads=1, d_ff=0,
        vocab_size=0, lstm_hidden=config["hidden_size"],
        lstm_input=config["input_size"],
        bidirectional=config["bidirectional"], scan_layers=False,
        dtype=config["weight_dtype"])


def _busy(engine) -> bool:
    return bool(engine.queue) or any(s is not None for s in engine.slots)


def warm_up(engine, mix: dict, x_dim: int) -> int:
    """Drive the fixed warm-up waves; returns the requests it served."""
    from repro.serving.recurrent import RecurrentRequest

    rng = np.random.default_rng(WARMUP_SEED)
    n_per = int(mix.get("warmup_waves", 1))
    per_k = n_per * int(mix["max_batch"])
    lengths = traffic.quantile_sizes(mix["prompt_frames"], per_k)
    # two fed-back frames take every request of a wave through two decode
    # ticks with all k rows active; a mix without decode asks for none
    new = 2 if max(traffic.quantile_sizes(mix["new_frames"], 2)) else 0
    uid = -1
    for k in range(1, int(mix["max_batch"]) + 1):
        for w in range(n_per):
            for j in range(k):
                t = lengths[(w * k + j * n_per + k) % per_k]
                engine.submit(RecurrentRequest(
                    uid=uid, frames=rng.standard_normal(
                        (t, x_dim), dtype=np.float32), max_new_frames=new))
                uid -= 1
            while _busy(engine):
                engine.step()
    served = -1 - uid
    done = engine.done[-served:] if served else []
    bad = [c.uid for c in done if c.status != "ok"]
    if bad:
        raise RuntimeError(f"warm-up requests {bad} did not finish ok")
    engine.done.clear()
    return served


class Observer:
    """Stamps what each request's client received after every step."""

    def __init__(self):
        self.admitted: Dict[int, float] = {}
        self.stamps: Dict[int, List[float]] = {}
        self.done = {}
        self._seen_done = 0

    def after_step(self, engine, t: float) -> None:
        for s, req in enumerate(engine.slots):
            if req is not None:
                self._deliver(req.uid, len(engine.generated[s]), t)
        fresh = engine.done[self._seen_done:]
        self._seen_done = len(engine.done)
        for comp in fresh:
            self._deliver(comp.uid, len(comp.generated), t)
            self.done[comp.uid] = comp

    def _deliver(self, uid: int, frames: int, t: float) -> None:
        if uid not in self.admitted:
            self.admitted[uid] = t
            self.stamps[uid] = [t]
        got = self.stamps[uid]
        got.extend([t] * (1 + frames - len(got)))


def run(cell, seed: int, seconds: float, profile_dir, devices, compiles,
        clock0: float, interpret: bool = False,
        control: Optional[str] = None) -> Outcome:
    """One run of a serving cell; ``control`` as in ``offline.run``."""
    from repro.serving.recurrent import (RecurrentRequest,
                                         RecurrentServingEngine)

    cfg, mix = cell.config, cell.traffic
    shape = StackShape.of(cfg)
    params = make_params(cfg, seed)
    engine = RecurrentServingEngine(model_config(cfg), params,
                                    max_batch=int(mix["max_batch"]),
                                    interpret=interpret)
    reqs = traffic.open_loop(mix, seed, seconds, shape.input_size)
    warm_up(engine, mix, shape.input_size)
    st0 = dict(vars(engine.compiled.stats))
    e0 = {k: getattr(engine, k) for k in
          ("prefill_waves", "packed_launches", "decode_ticks")}
    c0 = compiles.snapshot()
    setup_s = now() - clock0

    obs = Observer()
    tick_ms, late_ms, queue_at_last, steps = [], [], 0, 0
    n, nxt = len(reqs), 0
    with profiled(profile_dir, call="bench.step") as tr:
        with annotate("bench.window"):
            t0 = now()
            while True:
                t = now() - t0
                if nxt < n and reqs[nxt].due_s <= t:
                    with annotate("bench.submit"):
                        while nxt < n and reqs[nxt].due_s <= t:
                            r = reqs[nxt]
                            late_ms.append(1e3 * (t - r.due_s))
                            engine.submit(RecurrentRequest(
                                uid=r.index, frames=r.prompt,
                                max_new_frames=r.new_frames))
                            nxt += 1
                        if nxt == n:
                            queue_at_last = len(engine.queue)
                if _busy(engine):
                    waves = engine.prefill_waves
                    with annotate("bench.step"):
                        a = now()
                        engine.step()
                        b = now()
                    steps += 1
                    if engine.prefill_waves == waves:
                        tick_ms.append(1e3 * (b - a))
                    with annotate("bench.observe"):
                        obs.after_step(engine, b - t0)
                elif nxt < n:
                    with annotate("bench.wait"):
                        time.sleep(max(0.0, reqs[nxt].due_s - (now() - t0)))
                else:
                    break
                if now() - t0 > reqs[-1].due_s + DRAIN_S:
                    break
            t_end = now() - t0
    window = t_end
    mem = memory_peak_bytes(devices)
    st = vars(engine.compiled.stats)
    counters = {k: st[k] - st0[k] for k in
                ("launches", "plans_built", "degraded_launches",
                 "faults_total")}
    counters.update({k: getattr(engine, k) - e0[k] for k in e0})
    counters["compiles"] = compiles.snapshot() - c0
    counters["calls"] = steps
    del engine

    # ---- end-to-end metrics over every request due in the window
    failed_uids = [r.index for r in reqs
                   if r.index not in obs.done
                   or obs.done[r.index].status != "ok"]
    ttff = [1e3 * (obs.admitted.get(r.index, t_end) - r.due_s)
            if r.index not in failed_uids else 1e3 * (t_end - r.due_s)
            for r in reqs]
    gaps = [1e3 * (b - a) for r in reqs if r.index not in failed_uids
            for a, b in zip(obs.stamps[r.index], obs.stamps[r.index][1:])]
    metrics = {"ttff_p90_ms": stats.percentile(ttff, 90)}
    if any(r.new_frames for r in reqs):
        metrics["frame_gap_p99_ms"] = stats.percentile(gaps, 99)

    problems = []
    if failed_uids:
        problems.append(f"{len(failed_uids)} requests failed or never "
                        f"finished (first uids {failed_uids[:5]})")
    if counters["degraded_launches"] or counters["faults_total"]:
        problems.append(f"{counters['degraded_launches']} degraded launches, "
                        f"{counters['faults_total']} faults in the window")

    # ---- correctness, teacher-forced over a seeded sample
    spec = mix["check"]
    ok = [r for r in reqs if r.index not in failed_uids]
    values = {"max_gap": float("inf"), "rms_gap": float("inf")}
    control_values = {}
    if ok:
        longest = max(ok, key=lambda r: len(r.prompt) + r.new_frames)
        rng = np.random.default_rng([seed % (1 << 63), 6])
        rest = [r for r in ok if r is not longest]
        pick = [longest] + [rest[i] for i in rng.choice(
            len(rest), min(len(rest), int(spec["sample"]) - 1),
            replace=False)]
        seqs, outs = [], []
        for r in pick:
            comp = obs.done[r.index]
            seqs.append(check.fed_back_inputs(r.prompt, comp.outputs,
                                              comp.generated))
            outs.append(np.concatenate([comp.outputs, comp.generated]))
        # one fixed check shape per mix: every seed draws the same sizes
        length = max(len(r.prompt) + r.new_frames for r in reqs)
        xs = jax.numpy.asarray(check.pad_batch(seqs, length,
                                               int(spec["sample"])))
        ref = np.asarray(reference.stack_forward(
            params, xs, precision=cfg["matmul_precision"]))
        refs = [ref[i, :len(o)] for i, o in enumerate(outs)]
        values = check.readings(outs, refs)
        if control:
            ctrl = np.asarray(reference.stack_forward(
                params, xs, mode=control, precision=cfg["matmul_precision"]))
            control_values = check.readings(
                [ctrl[i, :len(o)] for i, o in enumerate(outs)], refs)
    checks = check.compared(values, spec["limits"])

    frames_in = sum(len(r.prompt) for r in reqs)
    frames_out = sum(r.new_frames for r in reqs)
    record = RunRecord(
        peaks={}, window_s=window,
        counters=counters,
        host={"tick_ms": tick_ms, "late_ms": late_ms, "ttff_ms": ttff,
              "queue_at_last_arrival": [queue_at_last],
              "drain_s": [t_end - reqs[-1].due_s]},
        work={"lstm_seq": seq_work(shape, frames_in,
                                   counters["prefill_waves"]),
              "lstm_decode": decode_work(shape, frames_out,
                                         counters["decode_ticks"])},
        model_flops=(frames_in + frames_out) * model_flops_per_frame(shape),
        trace=tr.summary)
    return Outcome(attempted=n, failed=len(failed_uids), setup_s=setup_s,
                   metrics=metrics, checks=checks, problems=problems,
                   memory_peak=mem, record=record, readings=values,
                   control_readings=control_values)
