"""Tracing threaded through compile -> forward -> prefill -> decode and
the serving engine.  The claims: a traced run emits the expected nested
span tree, four sibling phases per slot (hoist, pack, slot_launch,
scatter), each a ``repro.*`` annotation in a jax.profiler capture, and
never fences; tracing OFF leaves outputs bit-identical (and binds the
shared no-op tracer); the fault trail is a ring buffer."""
import collections
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import rnn
from repro.configs.sharp_lstm import lstm_config
from repro.models.layers.lstm import init_lstm_stack
from repro.rnn.compiled import StackStats
from repro.runtime.obs import NULL_TRACER
from repro.serving import RecurrentRequest, RecurrentServingEngine

H, T, L = 48, 8, 2
CFG = lstm_config(H, layers=L)


def _stack(seed=0):
    return init_lstm_stack(jax.random.PRNGKey(seed), CFG, jnp.float32)


def _xs(seed=1, B=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, T, H)) * 0.5


def _traced_session(cs):
    """forward + prefill + 3 feedback decode ticks (the demo's shape)."""
    xs = _xs()
    cs.forward(xs)
    ys, state = cs.prefill(xs)
    y_t = ys[:, -1:]
    for _ in range(3):
        y_t, state = cs.decode(y_t, state)
    return y_t


def test_traced_run_emits_expected_span_tree():
    cs = rnn.compile(_stack(), rnn.ExecutionPolicy(interpret=True,
                                                   trace=True))
    _traced_session(cs)
    tr = cs.tracer
    assert tr.enabled and tr is not NULL_TRACER

    names = {s.name for s in tr.events}
    assert {"forward", "prefill", "decode_tick", "plan", "hoist", "pack",
            "slot_launch", "scatter", "plan_candidates"} <= names
    # nesting: the API-level spans are roots, the per-slot work nests
    for s in tr.events:
        if s.name in ("forward", "prefill", "decode_tick"):
            assert s.depth == 0
        if s.name in PHASES + ("plan",):
            assert s.depth >= 1
    # every launch span carries its slot signature and a real duration
    launches = [s for s in tr.events if s.name == "slot_launch"]
    assert launches
    for s in launches:
        assert s.tags["sig"].startswith("lstm|H48|")
        assert s.dur_us > 0.0
    # the 3 chained decode launches share one signature
    assert sum("|chained" in s.tags["sig"] for s in launches) == 3

    # metrics: the decode tick histogram saw the 3 ticks' host time; the
    # fenced per-signature launch table is gone
    snap = tr.snapshot()
    assert snap["metrics"]["histograms"]["decode_tick_us"]["count"] == 3
    assert set(snap) == {"spans", "metrics"}
    assert not any(k.startswith("launch_us/")
                   for k in snap["metrics"]["histograms"])
    # describe() surfaces the observability section through the facade
    assert "observability:" in cs.describe()


PHASES = ("hoist", "pack", "slot_launch", "scatter")


def _phases_by_slot(events, root: str):
    """{slot index: [phase names in time order]} under the last ``root``
    span, with each phase's depth."""
    (top,) = [s for s in events if s.name == root][-1:]
    t0, t1 = top.start_us, top.start_us + top.dur_us
    slots = collections.defaultdict(list)
    for s in sorted(events, key=lambda s: s.start_us):
        if s.name in PHASES and t0 <= s.start_us <= t1:
            slots[s.tags["slot"]].append((s.name, s.depth))
    return slots


def test_each_packed_slot_has_four_sibling_phases():
    cs = rnn.compile(_stack(), rnn.ExecutionPolicy(interpret=True,
                                                   trace=True))
    cs.forward(_xs())
    slots = _phases_by_slot(cs.tracer.events, "forward")
    assert sorted(slots) == [sl.index for sl in cs.plan.slots]
    for phases in slots.values():
        # siblings, directly under forward, in execution order
        assert phases == [(n, 1) for n in PHASES]


def test_chained_decode_slot_has_the_same_four_phases():
    cs = rnn.compile(_stack(), rnn.ExecutionPolicy(interpret=True,
                                                   trace=True))
    ys, state = cs.prefill(_xs())
    cs.decode(ys[:, -1:], state)
    assert cs.last_decode_plan.slots[0].chained
    slots = _phases_by_slot(cs.tracer.events, "decode_tick")
    assert slots == {0: [(n, 1) for n in PHASES]}


def _host_events(profile_dir):
    (path,) = glob.glob(str(profile_dir / "plugins/profile/*/*.xplane.pb"))
    names = collections.Counter()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    names[e.name] += 1
    return names


@pytest.mark.parametrize("trace", [True, False])
def test_profiler_capture_holds_the_repro_phases(tmp_path, trace):
    """What an operator sees: ``trace=True`` plus a jax.profiler capture
    puts each host phase in the .xplane.pb as a ``repro.*`` event."""
    cs = rnn.compile(_stack(), rnn.ExecutionPolicy(interpret=True,
                                                   trace=trace))
    xs = _xs()
    cs.forward(xs)  # plan and compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(cs.forward(xs))
    finally:
        jax.profiler.stop_trace()
    names = _host_events(tmp_path)
    if not trace:
        assert names == {}
        return
    n_slots = len(cs.plan.slots)
    assert names["repro.forward"] == 1
    for phase in PHASES:
        assert names[f"repro.{phase}"] == n_slots


def test_traced_forward_never_fences(monkeypatch):
    cs = rnn.compile(_stack(), rnn.ExecutionPolicy(interpret=True,
                                                   trace=True))
    xs = _xs()
    cs.forward(xs)
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(x)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    ys, state = cs.prefill(xs)
    cs.forward(xs)
    cs.decode(ys[:, -1:], state)
    assert calls == []
    assert {"forward", "prefill", "decode_tick", "slot_launch"} <= {
        s.name for s in cs.tracer.events}


def test_trace_off_is_bit_identical_and_binds_null_tracer():
    stack, xs = _stack(), _xs()
    off = rnn.compile(stack, rnn.ExecutionPolicy(interpret=True))
    on = rnn.compile(stack, rnn.ExecutionPolicy(interpret=True, trace=True))
    assert off.tracer is NULL_TRACER  # one shared inert instance

    np.testing.assert_array_equal(np.asarray(off.forward(xs)),
                                  np.asarray(on.forward(xs)))
    _, st_off = off.prefill(xs)
    _, st_on = on.prefill(xs)
    for k in st_off:
        np.testing.assert_array_equal(np.asarray(st_off[k]),
                                      np.asarray(st_on[k]))
    y_off, _ = off.decode(xs[:, -1:], st_off)
    y_on, _ = on.decode(xs[:, -1:], st_on)
    np.testing.assert_array_equal(np.asarray(y_off), np.asarray(y_on))
    assert off.tracer.events == ()  # nothing recorded on the no-op path


def test_planner_candidate_scores_in_trace():
    cs = rnn.compile(_stack(), rnn.ExecutionPolicy(interpret=True,
                                                   trace=True))
    cs.forward(_xs())
    (cand,) = [s for s in cs.tracer.events if s.name == "plan_candidates"]
    assert "chosen" in cand.tags
    # the rejected alternatives ride along, scored
    assert len(cand.tags["candidates"]) >= 1
    for c in cand.tags["candidates"]:
        assert c["est_cycles"] > 0 and c["schedule"]
    # the chosen candidate is the argmin of the scores
    best = min(cand.tags["candidates"], key=lambda c: c["est_cycles"])
    assert cand.tags["chosen"] == f"{best['schedule']}@bt{best['block_t']}"


@pytest.mark.chaos
def test_fallback_rungs_and_faults_in_trace():
    pol = rnn.ExecutionPolicy(interpret=True, on_fault="fallback",
                              trace=True)
    cs = rnn.compile(_stack(), pol)
    base = np.asarray(rnn.compile(_stack(),
                                  rnn.ExecutionPolicy(interpret=True))
                      .forward(_xs()))
    cs.fault.arm(range(8), through_level=0, once=False)
    np.testing.assert_allclose(np.asarray(cs.forward(_xs())), base,
                               atol=1e-5)

    tr = cs.tracer
    rungs = [s for s in tr.events if s.name == "fallback_rung"]
    faults = [s for s in tr.events if s.name == "launch_fault"]
    assert rungs and faults
    assert {s.tags["rung"] for s in rungs} == {"per_step"}
    assert all(s.tags["rung"] == "fused" for s in faults)
    n_slots = len(cs.plan.slots)
    assert tr.metrics.counter("launch_faults").value == n_slots
    assert tr.metrics.counter("degraded_launches").value == n_slots


@pytest.mark.chaos
def test_fault_trail_is_a_ring_buffer(monkeypatch):
    monkeypatch.setattr(StackStats, "MAX_FAULT_TRAIL", 3)
    pol = rnn.ExecutionPolicy(interpret=True, on_fault="fallback")
    cs = rnn.compile(_stack(), pol)
    cs.fault.arm(range(64), through_level=0, once=False)  # every launch
    xs = _xs()
    for _ in range(4):
        cs.forward(xs)  # n_slots fault entries per call, forever
    n_slots = len(cs.plan.slots)
    assert cs.stats.faults_total == 4 * n_slots  # true count survives
    assert len(cs.stats.faults) == 3             # memory stays bounded
    # the trail keeps the MOST RECENT entries
    assert cs.stats.faults == ["degraded slot %d: fused->per_step" % i
                               for i in range(n_slots)][-3:] \
        or len(set(cs.stats.faults)) <= 3
    assert f"{cs.stats.faults_total} faults" in cs.describe()


def test_traced_serving_engine_records_request_lifetimes():
    params = _stack()
    eng = RecurrentServingEngine(CFG, params, max_batch=2, interpret=True,
                                 trace=True)
    rng = np.random.default_rng(0)
    for uid in range(3):  # 3 requests through 2 slots: two admission waves
        eng.submit(RecurrentRequest(
            uid=uid, frames=rng.standard_normal((6, H)).astype(np.float32),
            max_new_frames=2))
    done = eng.run_to_completion()
    assert len(done) == 3

    tr = eng.tracer
    assert tr is eng.compiled.tracer and tr.enabled
    admits = [s for s in tr.events if s.name == "admit"]
    assert len(admits) == eng.prefill_waves >= 2
    reqs = [s for s in tr.events if s.name == "request"]
    assert {s.tags["uid"] for s in reqs} == {0, 1, 2}
    for s in reqs:
        assert s.track == "requests"
        assert s.tags["status"] == "ok"
        assert s.tags["ticks"] >= 1 and s.dur_us > 0
    assert tr.metrics.counter("requests_ok").value == 3
    # serving gauges observed every tick
    snap = tr.snapshot()["metrics"]["histograms"]
    assert snap["slot_occupancy"]["count"] == eng.decode_ticks
    assert snap["queue_depth"]["count"] == eng.decode_ticks
