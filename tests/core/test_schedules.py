"""The paper's core equivalence claim: all four schedules compute the same
LSTM, differing only in dependence structure."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hyp import given, settings, st

from repro.configs.sharp_lstm import reduced
from repro.core import schedules as sch
from repro.kernels.lstm_cell.ops import as_cell_kernel
from repro.models.layers.lstm import (init_lstm_layer, init_lstm_stack,
                                      reference_unroll)

# this module intentionally exercises the DEPRECATED run_layer/run_stack
# shims — ISSUE-4 keeps them passing through repro.rnn.compile; the
# warnings are the contract, not noise worth failing on here (the shim
# tests in tests/rnn/test_shims.py assert they fire)
pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _mk(B, T, H, seed=0):
    key = jax.random.PRNGKey(seed)
    params = init_lstm_layer(key, H, H, jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, T, H)) * 0.5
    return params, xs


@pytest.mark.parametrize("schedule", sch.SCHEDULES)
def test_layer_matches_reference(schedule):
    params, xs = _mk(2, 9, 48)
    out = sch.run_layer(params, xs, schedule)
    ref = reference_unroll(params, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(B=st.integers(1, 3), T=st.integers(1, 12), H=st.sampled_from([16, 40, 64]),
       schedule=st.sampled_from(sch.SCHEDULES))
def test_property_schedule_equivalence(B, T, H, schedule):
    params, xs = _mk(B, T, H, seed=H + T)
    out = sch.run_layer(params, xs, schedule)
    ref = sch.run_layer(params, xs, "intergate")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_batch_tile_sizes():
    params, xs = _mk(2, 5, 48)
    ref = reference_unroll(params, xs)
    for tc in (16, 48, 100, 4 * 48):
        out = sch.run_layer(params, xs, "batch", tile_cols=tc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_unfolded_with_pallas_cell_kernel():
    """The fused Pallas cell drops into the unfolded scan unchanged."""
    params, xs = _mk(2, 6, 64)
    ref = reference_unroll(params, xs)
    out = sch.run_layer(params, xs, "unfolded",
                        cell_kernel=as_cell_kernel(interpret=True))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_bidirectional_stack():
    cfg = dataclasses.replace(reduced(), bidirectional=True)
    key = jax.random.PRNGKey(0)
    stack = init_lstm_stack(key, cfg, jnp.float32)
    xs = jax.random.normal(key, (2, 7, cfg.lstm_hidden))
    ref = sch.run_stack(stack, xs, "intergate")
    assert ref.shape == (2, 7, 2 * cfg.lstm_hidden)
    for s in sch.SCHEDULES:
        np.testing.assert_allclose(np.asarray(sch.run_stack(stack, xs, s)),
                                   np.asarray(ref), atol=1e-5)


def test_fused_layer_matches_reference():
    """The sequence-fused Pallas path (one launch) == ground truth."""
    params, xs = _mk(2, 9, 48)
    out = sch.run_layer(params, xs, "fused", interpret=True)
    ref = reference_unroll(params, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("T,block_t", [(1, 0), (7, 3), (11, 4), (12, 16)])
def test_wavefront_matches_unfolded(T, block_t):
    """Stack-level equivalence: L+nk-1 anti-diagonal slots == serial L·T."""
    cfg = reduced()
    stack = init_lstm_stack(jax.random.PRNGKey(0), cfg, jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, T, cfg.lstm_input)) * 0.5
    ref = sch.run_stack(stack, xs, "unfolded")
    out = sch.run_stack(stack, xs, "wavefront", block_t=block_t,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_wavefront_slot_launch_count():
    """A wavefront stack issues exactly L + ceil(T/bt) - 1 fused launches —
    one G-batched kernel per anti-diagonal slot."""
    from repro.kernels.common import pallas_launch_count
    cfg = reduced()
    L, T, bt = cfg.n_layers, 12, 4
    stack = init_lstm_stack(jax.random.PRNGKey(0), cfg, jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, T, cfg.lstm_input)) * 0.5
    n = pallas_launch_count(
        lambda s, x: sch.run_stack(s, x, "wavefront", block_t=bt,
                                   interpret=True), stack, xs)
    assert n == sch.wavefront_slots(L, T, bt) == L + T // bt - 1


def test_wavefront_bidirectional_interleaves():
    """Bidirectional + wavefront no longer falls back (ISSUE-5): the shim
    lowers to the dispatcher's interleaved fwd/bwd timeline and must still
    match the per-step reference."""
    cfg = dataclasses.replace(reduced(), bidirectional=True)
    stack = init_lstm_stack(jax.random.PRNGKey(0), cfg, jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (2, 7, cfg.lstm_hidden)) * 0.5
    ref = sch.run_stack(stack, xs, "intergate")
    out = sch.run_stack(stack, xs, "wavefront", block_t=4, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_unfolded_hoists_input_gemm():
    """Structural check: unfolded's jaxpr has exactly ONE big input GEMM
    outside the scan, while intergate multiplies W inside the loop."""
    params, xs = _mk(1, 8, 32)
    unf = jax.make_jaxpr(lambda p, x: sch.run_layer(p, x, "unfolded"))(params, xs)

    def outer(jaxpr):
        # the plan runs as one jitted program: see through the jit calls
        for e in jaxpr.eqns:
            if e.primitive.name in ("jit", "pjit"):
                yield from outer(e.params["jaxpr"].jaxpr)
            else:
                yield e

    eqns = list(outer(unf.jaxpr))
    # the (B,T,X)@(X,4H) einsum appears before the scan: find a dot with a
    # T-sized operand outside any scan
    body_eqns = [e for e in eqns if e.primitive.name == "scan"]
    assert len(body_eqns) == 1
    scan_eqn = body_eqns[0]
    inner = scan_eqn.params["jaxpr"].jaxpr
    outer_dots = [e for e in eqns if e.primitive.name == "dot_general"]
    inner_dots = [e for e in inner.eqns if e.primitive.name == "dot_general"]
    assert len(outer_dots) >= 1  # hoisted W GEMM
    assert len(inner_dots) == 1  # only U·h remains serial
