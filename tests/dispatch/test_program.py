"""One program per cached plan (``dispatch.PlanProgram``).

The claims: a plan's program returns what the eager walk of the same plan
returns — outputs and collected state, bit for bit — for a bidirectional
forward, a ragged cross-B prefill wave, a chained decode tick and an
int8 + block-sparse stack; a second call at a cached signature builds no
program, and evicting a plan drops its program; no weight transform
leaves a tracer behind; a program that fails falls back to the eager
ladder under ``on_fault="fallback"`` and raises ``LaunchError`` under
"raise"; ``check_finite`` inside a program names exactly the poisoned
request."""
import dataclasses
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import rnn
from repro.configs.sharp_lstm import lstm_config
from repro.core.perfmodel import MXU_ROWS
from repro.dispatch import execute
from repro.kernels.lstm_cell import ops
from repro.models.layers.lstm import init_lstm_stack
from repro.runtime.errors import LaunchError, NonFiniteStateError

H = 32
POL = rnn.ExecutionPolicy(interpret=True)


def _stack(L=2, bidir=False):
    cfg = lstm_config(H, layers=L)
    if bidir:
        cfg = dataclasses.replace(cfg, bidirectional=True)
    return init_lstm_stack(jax.random.PRNGKey(0), cfg, jnp.float32)


def _xs(B=2, T=10, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, T, H)) * 0.5


def _sparse_stack():
    stack = _stack()
    out = {"layers": [dict(lay) for lay in stack["layers"]]}
    for li, tiles in {0: (0, 2), 1: (1, 3)}.items():
        U = np.array(out["layers"][li]["U"])
        for t in tiles:
            U[t * MXU_ROWS:(t + 1) * MXU_ROWS] = 0.0
        out["layers"][li]["U"] = jnp.asarray(U)
    return out


def _equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)


def _eager(cs, params, inputs, **kw):
    """The eager walk of the plan ``cs`` ran last (no program)."""
    return execute(cs.plan, params, inputs, interpret=True, **kw)


# ---------------------------------------------------------------------------
# the program equals the eager walk
# ---------------------------------------------------------------------------


def test_bidirectional_forward_equals_eager_walk():
    cs = rnn.compile(_stack(bidir=True), POL)
    xs = _xs()
    ys = cs.forward(xs)
    assert cs.stats.programs_built == 1
    _equal(ys, _eager(cs, {0: cs.params}, {0: xs})[0])


def test_ragged_cross_b_prefill_wave_equals_eager_walk():
    cs = rnn.compile(_stack(), POL)
    seqs = [_xs(B=1, T=12, seed=1), _xs(B=2, T=6, seed=2),
            _xs(B=1, T=3, seed=3)]
    res = cs.prefill(seqs)
    # the wave packs ragged rows: some slot pads a row (b_valid)
    assert any(b < s.B for s in cs.plan.slots for b in s.group_b)
    outs, states = _eager(cs, {i: cs.params for i in range(3)},
                          dict(enumerate(seqs)), collect_state=True)
    for i, (ys, st) in enumerate(res):
        _equal(ys, outs[i])
        _equal(st, states[i])


def test_chained_decode_tick_equals_eager_walk():
    cs = rnn.compile(_stack(L=3), POL)
    xs = _xs(B=2, T=5)
    _, state = cs.prefill(xs)
    y, st = cs.decode(xs[:, :1], state)
    plan = cs.last_decode_plan
    assert plan.slots[0].chained
    outs, states = execute(plan, {0: cs.params}, {0: xs[:, :1]},
                           interpret=True, collect_state=True,
                           init_state={0: state},
                           prepared={0: cs._prepared})
    _equal(y, outs[0])
    _equal(st, states[0])


def test_int8_block_sparse_stack_equals_eager_walk():
    cs = rnn.compile(_sparse_stack(), rnn.ExecutionPolicy(
        interpret=True, precision="int8", sparsity="block"))
    xs = _xs()
    ys, st = cs.prefill(xs)
    assert cs.plan.items[0].item.tile_map is not None
    outs, states = _eager(cs, {0: cs.params}, {0: xs}, collect_state=True)
    _equal(ys, outs[0])
    _equal(st, states[0])


# ---------------------------------------------------------------------------
# caching
# ---------------------------------------------------------------------------


def test_cached_signature_builds_no_program():
    cs = rnn.compile(_stack(), POL)
    xs = _xs()
    ys, state = cs.prefill(xs)
    y, state = cs.decode(ys[:, -1:], state)
    cs.forward(xs)
    built = cs.stats.programs_built
    assert built == 3  # prefill, decode, forward (collect_state differs)
    for _ in range(3):
        cs.forward(xs)
        ys, _ = cs.prefill(xs)
        y, state = cs.decode(y, state)
    assert cs.stats.programs_built == built
    assert cs.stats.plans_built == 2
    assert f"{built} programs built" in cs.describe()


def test_evicting_a_plan_drops_its_program():
    cs = rnn.compile(_stack(L=1), POL)
    cs.MAX_CACHED_PLANS = 2
    cs.forward(_xs(T=3))
    first = weakref.ref(cs._plans[next(iter(cs._plans))])
    cs.forward(_xs(T=4))
    cs.forward(_xs(T=5))   # evicts T=3's plan
    assert len(cs._plans) == 2
    gc.collect()
    assert first() is None
    cs.forward(_xs(T=3))   # a miss again: plan and program rebuilt
    assert cs.stats.programs_built == 4


def test_weight_transforms_leave_no_tracer_behind():
    """The int8 / row-compacted U operands are trace-time values of one
    program: nothing the stack keeps across calls holds a tracer."""
    cs = rnn.compile(_sparse_stack(), rnn.ExecutionPolicy(
        interpret=True, precision="int8", sparsity="block"))
    xs = _xs()
    with jax.checking_leaks():
        ys, state = cs.prefill(xs)
        cs.forward(xs)
        cs.decode(ys[:, -1:], state)
    kept = jax.tree_util.tree_leaves(
        [vars(cs), [vars(e) for e in cs._plans.values()]])
    assert not any(isinstance(x, jax.core.Tracer) for x in kept)
    assert cs.stats.programs_built == 3


# ---------------------------------------------------------------------------
# the fault contract
# ---------------------------------------------------------------------------


def _refuse_tracing(monkeypatch):
    """Make ``lstm_seq`` fail whenever it is traced into a program; the
    eager walk calls it with concrete arrays and is untouched."""
    real = ops.lstm_seq

    def lstm_seq(U, xw, *args, **kw):
        if isinstance(xw, jax.core.Tracer):
            raise RuntimeError("refused lowering")
        return real(U, xw, *args, **kw)

    monkeypatch.setattr(ops, "lstm_seq", lstm_seq)


@pytest.mark.chaos
@pytest.mark.parametrize("on_fault", ["fallback", "raise"])
def test_failed_program_falls_back_or_raises(monkeypatch, on_fault):
    xs = _xs()
    healthy = rnn.compile(_stack(), POL).forward(xs)
    cs = rnn.compile(_stack(), rnn.ExecutionPolicy(interpret=True,
                                                   on_fault=on_fault))
    _refuse_tracing(monkeypatch)
    if on_fault == "raise":
        with pytest.raises(LaunchError) as e:
            cs.forward(xs)
        assert e.value.slot == 0 and e.value.level == "fused"
        assert "refused lowering" in str(e.value)
        assert cs.stats.forward_calls == 0
        return
    _equal(cs.forward(xs), healthy)
    s = cs.stats
    assert s.program_fallbacks == 1 and s.faults_total == 1
    assert s.degraded_launches == 0 and s.programs_built == 0
    assert "eager walk" in s.faults[0]
    assert "DEGRADED" in cs.describe()


@pytest.mark.chaos
def test_check_finite_in_program_names_the_poisoned_request():
    cs = rnn.compile(_stack(), rnn.ExecutionPolicy(interpret=True,
                                                   check_finite=True))
    seqs = [_xs(B=1, seed=s) for s in (1, 2, 3)]
    seqs[1] = seqs[1].at[0, 0, 0].set(jnp.nan)
    with pytest.raises(NonFiniteStateError) as e:
        cs.prefill(seqs)
    assert cs.stats.programs_built == 1
    assert e.value.uids == (1,) and e.value.where == "slot state"
    # the eager walk names the same request at the same slot
    p = cs._plans[next(iter(cs._plans))].plan
    with pytest.raises(NonFiniteStateError) as eager:
        execute(p, {i: cs.params for i in range(3)}, dict(enumerate(seqs)),
                interpret=True, collect_state=True, check_finite=True)
    assert (eager.value.uids, eager.value.slot) == (e.value.uids,
                                                   e.value.slot)
