"""repro.rnn.compile: one planned execution path (ISSUE-4 tentpole).

Covers the acceptance criteria: a mixed-family (lstm/gru) stack through
``compile().forward()`` is oracle-equal to the per-layer sequential
reference AND its plan wavefronts across families (fewer launches than the
per-layer-cell count); prefill/decode resume exactly; plans are cached;
``import repro`` exposes the facade."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import rnn
from repro.configs.sharp_lstm import lstm_config, reduced
from repro.core import gru
from repro.core import schedules as sch
from repro.kernels.common import pallas_launch_count
from repro.models.layers.lstm import init_lstm_layer, init_lstm_stack

H = 48
POL = rnn.ExecutionPolicy(interpret=True)


def _mixed_stack(seed=3):
    """lstm -> gru -> lstm, one hidden width (the heterogeneous case the
    old run_stack could not wavefront)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"layers": [init_lstm_layer(k1, H, H, jnp.float32),
                       gru.init_gru_layer(k2, H, H, jnp.float32),
                       init_lstm_layer(k3, H, H, jnp.float32)]}


def _xs(B=2, T=12, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, T, H)) * 0.5


# ---------------------------------------------------------------------------
# heterogeneous stacks (ISSUE-4 satellite + acceptance criterion)
# ---------------------------------------------------------------------------


def test_mixed_stack_matches_sequential_reference():
    stack = _mixed_stack()
    xs = _xs()
    cs = rnn.compile(stack, POL)
    assert cs.families == ("lstm", "gru", "lstm") and cs.heterogeneous
    ys = cs.forward(xs)
    ref = sch.reference_stack(stack, xs, "unfolded")
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ref), atol=1e-4)
    # the research schedules agree too (same stack, per-layer library)
    np.testing.assert_allclose(
        np.asarray(sch.reference_stack(stack, xs, "sequential")),
        np.asarray(ref), atol=1e-4)


def test_mixed_stack_wavefronts_across_families():
    """The plan is a genuine cross-family wavefront: same-family cells of
    one wave merge into G-batched launches, so the launch count is
    strictly below the per-layer-cell count L·nk (what per-(layer, chunk)
    dispatch would issue), and both families appear in the slot timeline."""
    stack = _mixed_stack()
    xs = _xs(T=12)
    cs = rnn.compile(stack, rnn.ExecutionPolicy(
        schedule="wavefront", block_t=4, interpret=True))
    p = cs.lower(2, 12)
    ip = p.item(0)
    assert ip.schedule == "wavefront" and ip.nk == 3
    assert {s.family for s in p.slots} == {"lstm", "gru"}
    assert any(s.g > 1 for s in p.slots)  # lstm layers 0+2 share a wave
    assert p.launches < ip.item.L * ip.nk == 9
    # wavefront invariant holds per cell
    for s in p.slots:
        for c in s.cells:
            assert c.layer + c.chunk == s.wave
    # structural proof: the jaxpr launches exactly plan.launches kernels
    n = pallas_launch_count(lambda pr, x: rnn.CompiledStack(
        pr, cs.policy).forward(x), stack, xs)
    assert n == p.launches
    ys = cs.forward(xs)
    ref = sch.reference_stack(stack, xs, "unfolded")
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ref), atol=1e-4)


def test_mixed_stack_prefill_decode_resume_exactly():
    """prefill's (h, c) state resumes a mixed stack's decode bit-exactly
    against running the extended sequence in one shot (gru rows of "c" are
    zeros by contract)."""
    stack = _mixed_stack()
    xs = _xs(T=9)
    cs = rnn.compile(stack, POL)
    ys, st = cs.prefill(xs)
    assert st["h"].shape == (3, 2, H) and st["c"].shape == (3, 2, H)
    assert float(jnp.max(jnp.abs(st["c"][1]))) == 0.0  # gru layer: no c
    y1, st1 = cs.decode(ys[:, -1], st)
    full = sch.reference_stack(
        stack, jnp.concatenate([xs, ys[:, -1:]], axis=1), "unfolded")
    np.testing.assert_allclose(np.asarray(y1[:, 0]),
                               np.asarray(full[:, -1]), atol=1e-4)
    # mixed decode is the documented per-layer fallback: L launches
    assert cs.last_decode_plan.launches == 3


# ---------------------------------------------------------------------------
# homogeneous paths: parity with the dispatcher + chained decode
# ---------------------------------------------------------------------------


def test_facade_adds_zero_launches_vs_direct_dispatch():
    """compile().forward() is the SAME plan/execute pipeline as direct
    dispatch.plan/execute — zero facade overhead (the BENCH_dispatch
    ``facade`` row asserts this too)."""
    from repro.dispatch import WorkItem, execute, plan

    cfg = lstm_config(64, layers=3)
    stack = init_lstm_stack(jax.random.PRNGKey(0), cfg, jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 64)) * 0.5
    direct_plan = plan([WorkItem.from_config(cfg, T=24, uid=0)])
    n_direct = pallas_launch_count(
        lambda pr, x: execute(direct_plan, {0: pr}, {0: x}, interpret=True),
        stack, xs)
    cs = rnn.compile(stack, POL)
    n_facade = pallas_launch_count(
        lambda pr, x: rnn.CompiledStack(pr, POL).forward(x), stack, xs)
    assert n_facade == n_direct == cs.lower(1, 24).launches
    np.testing.assert_array_equal(
        np.asarray(cs.forward(xs)),
        np.asarray(execute(direct_plan, {0: stack}, {0: xs},
                           interpret=True)[0]))


@pytest.mark.parametrize("family", ["lstm", "gru"])
def test_homogeneous_decode_is_one_chained_launch(family):
    if family == "lstm":
        stack = init_lstm_stack(jax.random.PRNGKey(0),
                                lstm_config(H, layers=3), jnp.float32)
    else:
        stack = gru.init_gru_stack(jax.random.PRNGKey(0), H, H, 3,
                                   jnp.float32)
    cs = rnn.compile(stack, POL)
    xs = _xs(T=7)
    ys, st = cs.prefill(xs)
    y1, st1 = cs.decode(ys[:, -1], st)
    assert cs.last_decode_plan.launches == 1  # one chained slot per tick
    full = sch.reference_stack(
        stack, jnp.concatenate([xs, ys[:, -1:]], axis=1), "unfolded")
    np.testing.assert_allclose(np.asarray(y1[:, 0]),
                               np.asarray(full[:, -1]), atol=1e-4)
    # ... and a second tick reuses the cached decode plan
    before = cs.stats.decode_plans_built
    cs.decode(y1[:, 0], st1)
    assert cs.stats.decode_plans_built == before


def test_multi_request_prefill_packs_one_plan():
    """A list of ragged prompts = the serving admission wave: one plan,
    cross-B-packed, each request's output and state exact vs solo."""
    stack = init_lstm_stack(jax.random.PRNGKey(0),
                            lstm_config(H, layers=2), jnp.float32)
    cs = rnn.compile(stack, POL)
    seqs = [_xs(B=1, T=t, seed=10 + t) for t in (12, 12, 8)]
    res = cs.prefill(seqs)
    assert len(res) == 3
    assert cs.plan.launches < cs.plan.naive_launches  # genuinely packed
    for xs_i, (ys_i, st_i) in zip(seqs, res):
        solo_y, solo_st = rnn.compile(stack, POL).prefill(xs_i)
        np.testing.assert_array_equal(np.asarray(ys_i), np.asarray(solo_y))
        np.testing.assert_array_equal(np.asarray(st_i["h"]),
                                      np.asarray(solo_st["h"]))


def test_plan_cache_and_stats_accounting():
    stack = init_lstm_stack(jax.random.PRNGKey(0),
                            lstm_config(H, layers=2), jnp.float32)
    cs = rnn.compile(stack, POL)
    xs = _xs(T=10)
    cs.forward(xs)
    p1 = cs.plan
    cs.forward(xs)             # same shape: cache hit
    assert cs.plan is p1
    assert cs.stats.plans_built == 1 and cs.stats.forward_calls == 2
    assert cs.stats.launches == 2 * p1.launches
    assert cs.stats.est_cycles > 0
    cs.prefill(xs)             # same shape through prefill: SAME cache key
    assert cs.plan is p1 and cs.stats.plans_built == 1
    cs.forward(_xs(T=5))       # new shape: one more plan
    assert cs.stats.plans_built == 2
    assert "CompiledStack" in cs.describe()


def test_block_t_honored_under_auto_schedule():
    """Regression: ExecutionPolicy.block_t used to be dropped whenever
    schedule stayed "auto" — the documented stripe override must pin the
    wavefront stripe there too."""
    stack = init_lstm_stack(jax.random.PRNGKey(0),
                            lstm_config(H, layers=3), jnp.float32)
    cs = rnn.compile(stack, rnn.ExecutionPolicy(block_t=4, interpret=True))
    p = cs.lower(2, 12)
    ip = p.item(0)
    assert ip.block_t == 4 and ip.nk == 3 and ip.schedule == "wavefront"
    xs = _xs(T=12)
    np.testing.assert_allclose(
        np.asarray(cs.forward(xs)),
        np.asarray(sch.reference_stack(stack, xs)), atol=1e-4)


def test_mixed_dtype_prefill_keeps_per_request_signatures():
    """Regression: a mixed-precision admission wave used to stamp every
    item with the first request's dtype — items must carry their own, so
    f32 and bf16 cells never share a launch signature."""
    stack = init_lstm_stack(jax.random.PRNGKey(0),
                            lstm_config(H, layers=2), jnp.float32)
    cs = rnn.compile(stack, POL)
    seqs = [_xs(B=1, T=8), _xs(B=1, T=8).astype(jnp.bfloat16)]
    res = cs.prefill(seqs)
    dts = [ip.item.dtype for ip in cs.plan.items]
    assert dts == ["float32", "bfloat16"]
    for s in cs.plan.slots:  # no cross-dtype merges
        assert len({dts[c.uid] for c in s.cells}) == 1
        assert s.dtype == dts[s.cells[0].uid]
    # each request still exact vs its solo run
    for xs_i, (ys_i, _) in zip(seqs, res):
        solo_y, _ = rnn.compile(stack, POL).prefill(xs_i)
        np.testing.assert_array_equal(np.asarray(ys_i), np.asarray(solo_y))


def test_prefill_rejects_stateless_schedules():
    """Review fix: prefill under a forced reference/per_step schedule used
    to silently execute the per-layer fused path (different schedule AND
    launch accounting than the plan reports) — it must refuse instead."""
    stack = init_lstm_stack(jax.random.PRNGKey(0),
                            lstm_config(H, layers=2), jnp.float32)
    cs = rnn.compile(stack, rnn.ExecutionPolicy(schedule="unfolded"))
    with pytest.raises(ValueError, match="no .* state surface"):
        cs.prefill(_xs(T=5))
    # forward still runs the requested reference schedule
    assert cs.forward(_xs(T=5)).shape == (2, 5, H)


def test_plan_cache_is_bounded_lru():
    """Review fix: ragged admission waves must not grow the plan cache
    without bound (long-running serving)."""
    stack = init_lstm_stack(jax.random.PRNGKey(0),
                            lstm_config(H, layers=1), jnp.float32)
    cs = rnn.compile(stack, POL)
    cs.MAX_CACHED_PLANS = 4
    for t in range(3, 10):
        cs.lower(1, t)
    assert len(cs._plans) == 4
    assert cs.lower(1, 9) is cs._plans[next(reversed(cs._plans))].plan  # hit


def test_forced_reference_schedules_run_and_match():
    stack = init_lstm_stack(jax.random.PRNGKey(0),
                            lstm_config(H, layers=2), jnp.float32)
    xs = _xs(T=9)
    ref = sch.reference_stack(stack, xs, "intergate")
    for s in ("sequential", "batch", "intergate", "unfolded"):
        cs = rnn.compile(stack, rnn.ExecutionPolicy(schedule=s))
        np.testing.assert_allclose(np.asarray(cs.forward(xs)),
                                   np.asarray(ref), atol=1e-5)
        assert cs.plan.item(0).schedule == s
        assert cs.plan.launches == 0  # pure-jnp reference: no kernels


def test_compile_from_config_and_families():
    cfg = reduced()
    cs = rnn.compile(cfg, POL)
    assert cs.families == ("lstm",) * cfg.n_layers
    ys = cs.forward(_xs(T=6))
    assert ys.shape == (2, 6, H)
    cg = rnn.compile(cfg, POL, rnn_family="gru")
    assert cg.families == ("gru",) * cfg.n_layers
    assert cg.forward(_xs(T=6)).shape == (2, 6, H)


def test_2d_input_auto_batches():
    stack = init_lstm_stack(jax.random.PRNGKey(0),
                            lstm_config(H, layers=2), jnp.float32)
    cs = rnn.compile(stack, POL)
    xs = _xs(B=1, T=7)
    np.testing.assert_array_equal(np.asarray(cs.forward(xs[0])),
                                  np.asarray(cs.forward(xs)[0]))


# ---------------------------------------------------------------------------
# bidirectional stacks: interleaved wavefront through the facade (ISSUE-5)
# ---------------------------------------------------------------------------


def _bi_cfg(L=3, hidden=H):
    return dataclasses.replace(lstm_config(hidden, layers=L),
                               bidirectional=True, dtype="float32")


def test_bidirectional_forward_bit_identical_and_launch_proof():
    """The acceptance criterion end to end: compile().forward() on a
    bidirectional stack is BIT-identical to reference_stack and plans
    strictly fewer launches than 2·L·⌈T/bt⌉ — structurally proven on the
    compiled facade, not just the planner."""
    cfg, T, bt, L = _bi_cfg(L=3), 12, 4, 3
    cs = rnn.compile(cfg, rnn.ExecutionPolicy(
        schedule="wavefront", block_t=bt, interpret=True))
    xs = _xs(B=2, T=T)
    ys = cs.forward(xs)
    assert ys.shape == (2, T, 2 * H)
    ref = sch.reference_stack(cs.params, xs, "fused")
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(ref))
    np.testing.assert_allclose(
        np.asarray(ys), np.asarray(sch.reference_stack(cs.params, xs)),
        atol=1e-4)
    p = cs.plan
    nk = p.item(0).nk
    assert p.launches < 2 * L * nk == 2 * L * (T // bt)
    n = pallas_launch_count(
        lambda pr, x: rnn.CompiledStack(pr, cs.policy).forward(x),
        cs.params, xs)
    assert n == p.launches
    # every slot's fwd/bwd pair merged: one G=2 launch per wave here
    assert all(s.g == 2 for s in p.slots) and len(p.slots) == L * nk


def test_bidirectional_prefill_returns_per_direction_state():
    cfg = _bi_cfg(L=2)
    cs = rnn.compile(cfg, POL)
    xs = _xs(B=1, T=9)
    ys, st = cs.prefill(xs)
    assert set(st) == {"fwd", "bwd"}
    assert st["fwd"]["h"].shape == (2, 1, H)
    assert st["bwd"]["c"].shape == (2, 1, H)
    np.testing.assert_array_equal(np.asarray(ys),
                                  np.asarray(cs.forward(xs)))


def test_bidirectional_decode_raises_with_pointer():
    cfg = _bi_cfg(L=2)
    cs = rnn.compile(cfg, POL)
    with pytest.raises(ValueError, match=r"forward\(\)/prefill\(\)"):
        cs.decode(jnp.zeros((1, 1, H)), {"h": jnp.zeros((2, 1, H))})


def test_plan_cache_keys_carry_direction_info():
    """ISSUE-5: cache keys distinguish uni and bidirectional timelines
    explicitly (not just by stack identity)."""
    uni = rnn.compile(init_lstm_stack(jax.random.PRNGKey(0),
                                      lstm_config(H, layers=2), jnp.float32),
                      POL)
    bi = rnn.compile(_bi_cfg(L=2), POL)
    uni.lower(1, 8)
    bi.lower(1, 8)
    (uk,), (bk,) = uni._plans.keys(), bi._plans.keys()
    assert uk != bk
    assert "uni" in uk and "bi" in bk


# ---------------------------------------------------------------------------
# clear errors + the repro package facade (ISSUE-4 satellites)
# ---------------------------------------------------------------------------


def test_clear_errors():
    stack = init_lstm_stack(jax.random.PRNGKey(0),
                            lstm_config(H, layers=2), jnp.float32)
    cs = rnn.compile(stack, POL)
    with pytest.raises(ValueError, match=r"\(B, T, 48\)"):
        cs.forward(jnp.zeros((2, 5, 7)))
    with pytest.raises(ValueError, match="T=0"):
        cs.forward(jnp.zeros((2, 0, H)))
    with pytest.raises(TypeError, match="ModelConfig"):
        rnn.compile([1, 2, 3])
    with pytest.raises(ValueError, match="recurrent"):
        from repro.configs import get_config

        rnn.compile(get_config("starcoder2-3b"))
    bi = dataclasses.replace(reduced(), bidirectional=True)
    cbi = rnn.compile(bi, POL)
    with pytest.raises(ValueError, match="decode"):
        cbi.decode(jnp.zeros((1, 1, H)), {"h": jnp.zeros((2, 1, H))})


def test_repro_package_exposes_rnn_lazily():
    import repro

    assert repro.rnn.compile is rnn.compile            # lazy attr access
    assert "rnn" in dir(repro) and "dispatch" in dir(repro)
    from repro import rnn as rnn2                      # submodule import

    assert rnn2 is rnn
    with pytest.raises(AttributeError, match="no attribute"):
        repro.not_a_module
