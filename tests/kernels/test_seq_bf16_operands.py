"""The sequence kernel's bfloat16 MXU operands.

Where the recurrent dot runs as one bfloat16 pass (compiled for the TPU at
the default matmul precision, ``kernels.common.mxu_bf16_operands``), a
bfloat16 U feeds the MXU as it is and h is cast to bfloat16 before each
dot; everywhere else the kernel upcasts U and dots in float32, as it
always did.  On the CPU the kernel runs interpreted, so the bfloat16 step
is checked here with the predicate forced, against a plain recurrence
with bfloat16-rounded dot operands; on a TPU the staged path must equal
the float32-U kernel bit for bit (the MXU's f32 mode rounds its operands
to the same bfloat16 values).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tiling import select_time_block
from repro.kernels import common
from repro.kernels.lstm_cell import kernel
from repro.kernels.lstm_cell.ops import lstm_seq

on_tpu = pytest.mark.skipif(jax.default_backend() != "tpu",
                            reason="needs a TPU: compares MXU results")


def _mk(G, B, T, H, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    U4 = jax.random.normal(ks[0], (G, H, 4, H), jnp.float32) * H ** -0.5
    xw = jax.random.normal(ks[1], (G, B, T, 4, H), jnp.float32)
    h0 = jax.random.normal(ks[2], (G, B, H), jnp.float32) * 0.5
    c0 = jax.random.normal(ks[3], (G, B, H), jnp.float32) * 0.5
    return U4, xw, h0, c0


def _rounded_recurrence(U4, xw, h0, c0):
    """The LSTM recurrence with each step's dot on bfloat16-rounded h and
    U, accumulated in float32; gates, c and h in float32."""
    G, Hr, _, H = U4.shape
    U = U4.reshape(G, Hr, 4 * H).astype(jnp.bfloat16)

    def one(U, xw, h0, c0):
        def step(carry, xw_t):
            h, c = carry
            gates = xw_t.reshape(-1, 4 * H) + jnp.dot(
                h.astype(jnp.bfloat16), U,
                preferred_element_type=jnp.float32)
            i, f, g, o = (gates[:, k * H:(k + 1) * H] for k in range(4))
            c = jax.nn.sigmoid(i) * jnp.tanh(g) + jax.nn.sigmoid(f) * c
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        (h, c), hs = jax.lax.scan(step, (h0, c0), jnp.swapaxes(xw, 0, 1))
        return jnp.swapaxes(hs, 0, 1), h, c

    return jax.vmap(one)(U, xw, h0, c0)


def _dot_operand_dtypes(fn, *args):
    """The operand dtypes of every dot inside the kernels ``fn`` launches."""
    found = []

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            if inside and eqn.primitive.name == "dot_general":
                found.append(tuple(str(v.aval.dtype) for v in eqn.invars))
            for sub in common._subjaxprs(list(eqn.params.values())):
                walk(sub, inside or eqn.primitive.name == "pallas_call")

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return found


def test_mxu_bf16_operands_reads_mode_and_precision():
    assert not common.mxu_bf16_operands(True)
    assert common.mxu_bf16_operands(False)
    for level, one_pass in [("bfloat16", True), ("default", True),
                            ("highest", False), ("float32", False),
                            ("high", False), ("BF16_BF16_F32", False)]:
        with jax.default_matmul_precision(level):
            assert common.mxu_bf16_operands(False) is one_pass, level
            assert not common.mxu_bf16_operands(True)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("G,B,T,H,bt", [(2, 8, 8, 40, 8), (1, 4, 13, 24, 5)])
def test_bf16_step_matches_rounded_recurrence(monkeypatch, G, B, T, H, bt,
                                              masked):
    """The bfloat16 step, forced under interpret: bfloat16 U and the cast
    h give the plain recurrence on bfloat16-rounded dot operands with a
    float32 accumulate, to 1e-6 — across stripes, a ragged T edge and
    masked rows."""
    monkeypatch.setattr(kernel, "mxu_bf16_operands", lambda interpret: True)
    U4, xw, h0, c0 = _mk(G, B, T, H, seed=G * 100 + T)
    b_mask = None
    if masked:
        b_mask = common.ragged_b_mask(G, B, [B - 1] * G)
    Us = U4.astype(jnp.bfloat16)
    hs, h_n, c_n = kernel.lstm_seq_pallas(
        Us, xw, h0, c0, block_t=bt, interpret=True, b_mask=b_mask)
    assert _dot_operand_dtypes(
        lambda *a: kernel.lstm_seq_pallas(*a, block_t=bt, interpret=True),
        Us, xw, h0, c0) == [("bfloat16", "bfloat16")]
    hr, hnr, cnr = _rounded_recurrence(U4, xw, h0, c0)
    rows = slice(None) if not masked else slice(0, B - 1)
    for got, want in [(hs, hr), (h_n, hnr), (c_n, cnr)]:
        np.testing.assert_allclose(np.asarray(got)[:, rows],
                                   np.asarray(want)[:, rows], rtol=0,
                                   atol=1e-6)
    if masked:   # the padded row is an exact no-op
        np.testing.assert_array_equal(np.asarray(h_n)[:, B - 1],
                                      np.asarray(h0)[:, B - 1])


def test_interpret_keeps_the_f32_dot():
    """Interpreted, every weight dtype dots in float32, so a bfloat16 U is
    upcast as before and the outputs equal the float32 kernel's on the
    upcast U bit for bit."""
    U4, xw, h0, c0 = _mk(2, 4, 9, 32, seed=3)
    Ub = U4.astype(jnp.bfloat16)
    want = lstm_seq(Ub.astype(jnp.float32), xw, h0, c0, block_t=4,
                    interpret=True)
    for U in (U4, Ub):
        assert _dot_operand_dtypes(
            lambda *a: kernel.lstm_seq_pallas(*a, block_t=4, interpret=True),
            U, xw, h0, c0) == [("float32", "float32")]
        if U is U4:
            continue
        got = lstm_seq(U, xw, h0, c0, block_t=4, interpret=True)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_int8_and_highest_keep_the_f32_dot():
    """The int8 payload keeps its f32 upcast, and so does a bfloat16 U
    under a caller's ``highest`` precision."""
    from repro.kernels.quant import quantize_per_gate

    U4, xw, h0, c0 = _mk(1, 4, 5, 32, seed=4)
    q, s = quantize_per_gate(U4[0])
    assert _dot_operand_dtypes(
        lambda *a: kernel.lstm_seq_pallas(*a, block_t=5, interpret=False,
                                          u_scales=s[None]),
        q[None], xw, h0, c0) == [("float32", "float32")]
    with jax.default_matmul_precision("highest"):
        assert _dot_operand_dtypes(
            lambda *a: kernel.lstm_seq_pallas(*a, block_t=5,
                                              interpret=False),
            U4.astype(jnp.bfloat16), xw, h0, c0) == [("float32", "float32")]
    assert _dot_operand_dtypes(
        lambda *a: kernel.lstm_seq_pallas(*a, block_t=5, interpret=False),
        U4.astype(jnp.bfloat16), xw, h0, c0) == [("bfloat16", "bfloat16")]


@on_tpu
@pytest.mark.parametrize("G,B,T,H,bt", [
    (2, 64, 8, 340, 8),                                   # an EESEN slot
    (1, 64, 75, 1024, select_time_block(75, 64, 1024)),   # a GMAT layer
], ids=["eesen_slot", "gmat_layer"])
def test_staged_u_bit_equal_to_f32_u_on_chip(G, B, T, H, bt):
    """On the TPU, U staged in bfloat16 gives hs, h_T and c_T bit for bit
    equal to the float32-U kernel's, whose MXU rounds the same operands,
    at the stripe the planner gives each launch."""
    if H == 1024:
        assert bt == 19
    U4, xw, h0, c0 = _mk(G, B, T, H, seed=H + T)
    staged = lstm_seq(U4.astype(jnp.bfloat16), xw, h0, c0, block_t=bt,
                      interpret=False)
    plain = lstm_seq(U4, xw, h0, c0, block_t=bt, interpret=False)
    for got, want in zip(staged, plain):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
