"""``CompiledStack`` stages each W and U in bfloat16 where the sequence
kernel feeds the MXU bfloat16 operands.

The choice is read at every call (``kernels.common.mxu_bf16_operands``:
compiled for the TPU, at the caller's default matmul precision) and only
chooses the parameters a forward or prefill program binds: the plans stay
the float32 plans.  On the CPU the kernels run interpreted, where it is
off; the choice is checked with ``interpret=False`` (nothing runs), and
the execution side with the stack's reading of the predicate forced, the
kernel still interpreted (it then upcasts the staged U, and the input
GEMM computes on the staged W)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import lstm as plain
from repro import rnn
from repro.dispatch import stage_weights
from repro.rnn import compiled


def _params(H=32, L=3, bidirectional=False, seed=18):
    return plain.make_params(seed, hidden=H, input_size=H, layers=L,
                             bidirectional=bidirectional, dtype="float32")


def _xs(B=2, T=12, X=32, seed=5):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, T, X))


def _dtypes(params):
    return {str(half[k].dtype) for l in params["layers"]
            for half in ((l["fwd"], l["bwd"]) if "fwd" in l else (l,))
            for k in ("W", "U")}


def _rounded(params):
    """``params`` with W and U rounded through bfloat16, kept float32: what
    a stack computes on its staged weights when the kernel upcasts them."""
    def half(p):
        return dict(p, **{k: p[k].astype(jnp.bfloat16).astype(jnp.float32)
                          for k in ("W", "U")})

    return dict(params, layers=[
        dict(l, fwd=half(l["fwd"]), bwd=half(l["bwd"])) if "fwd" in l
        else half(l) for l in params["layers"]])


def test_compiled_stacks_plan_for_bf16_u_at_default_precision():
    """Compiled for the TPU at the default precision a stack binds its W
    and U staged in bfloat16, staged once; a caller's ``highest`` precision,
    int8 weights, block sparsity and interpret mode bind the float32
    stack.  The plan is one cache entry either way, its staged bytes
    counted at 4 bytes a weight."""
    params = _params()
    cs = rnn.compile(params, rnn.ExecutionPolicy(interpret=False))
    staged = cs._seq_params()
    assert _dtypes(staged) == {"bfloat16"}
    assert cs._seq_params() is staged
    with jax.default_matmul_precision("highest"):
        assert cs._seq_params() is cs.params
        f32 = cs.lower(64, 24)
    assert cs.lower(64, 24) is f32 and len(cs._plans) == 1
    assert f32.u_bytes_staged % (32 * 4 * 32 * 4) == 0
    for policy in (rnn.ExecutionPolicy(interpret=False, precision="int8"),
                   rnn.ExecutionPolicy(interpret=False, sparsity="block"),
                   rnn.ExecutionPolicy(interpret=True)):
        other = rnn.compile(params, policy)
        assert other._seq_params() is other.params


def test_stage_u_adds_bf16_u():
    """Each layer's (and direction's) W and U are cast to bfloat16; b is
    the same array."""
    for bidir in (False, True):
        params = _params(L=2, bidirectional=bidir)
        out = stage_weights(params)
        for lay, src in zip(out["layers"], params["layers"]):
            for half, ref in ([(lay["fwd"], src["fwd"]),
                               (lay["bwd"], src["bwd"])] if bidir
                              else [(lay, src)]):
                for k in ("W", "U"):
                    assert half[k].dtype == jnp.bfloat16
                    np.testing.assert_array_equal(
                        np.asarray(half[k]),
                        np.asarray(ref[k].astype(jnp.bfloat16)))
                assert half["b"] is ref["b"]


@pytest.mark.parametrize("bidir", [False, True])
def test_forward_and_prefill_bind_u_staged_once(monkeypatch, bidir):
    """With the predicate forced on, forward and prefill run the float32
    plan on weights cast once per stack, and compute exactly what the
    float32 path computes on W and U rounded through bfloat16 (the
    interpreted kernel upcasts the staged U); decode keeps the float32
    stack."""
    params = _params(L=2, bidirectional=bidir)
    xs = _xs()
    want_plan = rnn.compile(params, rnn.ExecutionPolicy(interpret=True)) \
        .lower(*xs.shape[:2])
    monkeypatch.setattr(compiled, "mxu_bf16_operands",
                        lambda interpret: True)
    cs = rnn.compile(params, rnn.ExecutionPolicy(interpret=True))
    ys = cs.forward(xs)
    assert cs.plan.describe() == want_plan.describe()
    staged = cs._staged
    assert _dtypes(staged) == {"bfloat16"}
    cs.forward(xs)
    cs.prefill(xs)
    assert cs._staged is staged and cs.stats.programs_built == 2
    assert cs.stats.u_bytes_staged == 3 * want_plan.u_bytes_staged

    want = rnn.compile(_rounded(params),
                       rnn.ExecutionPolicy(interpret=True)).forward(xs)
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(want))
    if not bidir:
        _, state = cs.prefill(xs)
        cs.decode(xs[:, :1], state)
        assert cs._prepared["Us"].dtype == jnp.float32


def test_bf16_precision_stack_keeps_its_bf16_u(monkeypatch):
    """A ``precision="bf16"`` stack already holds bfloat16 values in U:
    the staged bfloat16 U reaches the kernel as it is, and the outputs are
    the unstaged stack's on the rounded W bit for bit."""
    params = _params(L=2)
    xs = _xs()
    want = rnn.compile(_rounded(params), rnn.ExecutionPolicy(
        interpret=True, precision="bf16")).forward(xs)
    monkeypatch.setattr(compiled, "mxu_bf16_operands",
                        lambda interpret: True)
    seen = []
    from repro.kernels.lstm_cell import ops

    real = ops.lstm_seq

    def spy(U4, *a, **kw):
        seen.append(U4.dtype)
        return real(U4, *a, **kw)

    monkeypatch.setattr(ops, "lstm_seq", spy)
    got = rnn.compile(params, rnn.ExecutionPolicy(
        interpret=True, precision="bf16")).forward(xs)
    assert set(seen) == {jnp.dtype(jnp.bfloat16)}
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("schedule", ["sequential", "per_step"])
def test_external_schedules_read_the_staged_u(monkeypatch, schedule):
    """A plan whose items run outside the slot timeline (a forced
    reference or per-step schedule) binds the staged stack too: its
    schedules compute on W and U rounded through bfloat16, as one-pass
    bfloat16 dots of the float32 weights would."""
    params = _params(L=2)
    xs = _xs()
    pol = rnn.ExecutionPolicy(interpret=True, schedule=schedule)
    want = rnn.compile(_rounded(params), pol).forward(xs)
    monkeypatch.setattr(compiled, "mxu_bf16_operands",
                        lambda interpret: True)
    cs = rnn.compile(params, pol)
    got = cs.forward(xs)
    assert cs.plan.external and _dtypes(cs._staged) == {"bfloat16"}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-6)
