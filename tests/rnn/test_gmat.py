"""SHARP Table 5 GMAT (17 unidirectional LSTM layers, width 1024) on the
normal path.

On the CPU in interpret mode, at a small width: a GMAT-shaped stack
through ``rnn.compile(...).forward`` against both plain references, and a
chunk walked in VMEM stripes against one whole-layer launch.  At the
published widths, planning only (nothing runs): the plan, its VMEM
footprints and its weight loads; EESEN's plan; the refusals where no
kernel fits.  The chip-side comparison at full size is the benchmark's
``gmat.offline`` cell (PERF.md)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import lstm as plain
from repro import rnn
from repro.analysis.plancheck import check_plan
from repro.core import schedules as sch
from repro.core.tiling import (SEQ_VMEM_CAPACITY, select_time_block,
                               seq_fits)
from repro.dispatch import WorkItem, execute, plan
from repro.runtime.errors import PlanRejected

GMAT_L, GMAT_T, GMAT_H = 17, 75, 1024


def _params(H, L, seed=1707):
    """Seeded random weights in the served layout (the benchmark's own
    generator: truncated normals over sqrt(fan-in), bias N(0, 0.1^2))."""
    return plain.make_params(seed, hidden=H, input_size=H, layers=L,
                             bidirectional=False, dtype="float32")


def _xs(B, T, X, seed=17):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, T, X))


def _gmat_item(B=64, T=GMAT_T, uid=0):
    return WorkItem(uid=uid, family="lstm", B=B, T=T, H=GMAT_H, L=GMAT_L,
                    X=GMAT_H, share=0)


def _u_bytes(H, L):
    return L * H * 4 * H * 4


def test_gmat_shaped_stack_matches_both_references():
    """17 layers at H32, B4, T16 through the planned path.  Both
    references compute in float32 on the CPU, as the program does; they
    differ from it only in rounding order (the kernel contracts the c
    update into a fused multiply-add, the references' scans do not), so
    1e-5 of the output's scale bounds 17 layers of float32 rounding,
    while a wrong gate, state or chunk boundary moves outputs by 1e-2 or
    more."""
    params = _params(32, GMAT_L)
    xs = _xs(4, 16, 32)
    cs = rnn.compile(params, rnn.ExecutionPolicy(interpret=True))
    ys = np.asarray(cs.forward(xs))
    assert ys.shape == (4, 16, 32)
    assert cs.stats.launches == cs.plan.launches > 0

    oracle = np.asarray(sch.reference_stack(params, xs))
    np.testing.assert_allclose(ys, oracle, rtol=0, atol=1e-5)
    textbook = np.asarray(plain.stack_forward(params, xs,
                                              precision="highest"))
    np.testing.assert_allclose(ys, textbook, rtol=0, atol=1e-5)


def test_chunk_walked_in_stripes_equals_one_whole_layer_launch():
    """GMAT's published plan walks each 75-frame chunk in stripes of 19
    (three full grid steps and a ragged fourth).  The same chunk:stripe
    ratio at a small width is bit-identical to launching each layer once
    with the whole T in a single grid step: the state crosses stripes in
    VMEM scratch, and the T-edge mask makes the padded tail a no-op."""
    H, L, B = 32, 3, 4
    params = _params(H, L)
    xs = _xs(B, GMAT_T, H)
    item = WorkItem(uid=0, family="lstm", B=B, T=GMAT_T, H=H, L=L, X=H)
    p = plan([item], schedule="wavefront", block_t=GMAT_T)
    assert [s.chunk_len for s in p.slots] == [GMAT_T] * L
    striped = dataclasses.replace(p, slots=tuple(
        dataclasses.replace(s, stripe=19) for s in p.slots))
    check_plan(striped)
    ys = execute(striped, {0: params}, {0: xs}, interpret=True)[0]

    y = xs
    for layer in params["layers"]:
        y = sch.run_layer_fused(layer, y, block_t=GMAT_T, interpret=True)
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(y))


def test_gmat_plans_at_its_published_widths():
    """B64 x T75 at H1024, L17: the plan passes every plancheck rule,
    each launch fits the VMEM capacity at its stripe, and each layer's
    16 MiB U is staged at most 4 times a call (each load then amortised
    over at least 19 frames)."""
    p = plan([_gmat_item()])
    check_plan(p)
    for s in p.slots:
        assert seq_fits(s.footprint()) and s.vmem_limit() <= \
            SEQ_VMEM_CAPACITY
        assert 1 <= s.stripe <= s.chunk_len
    assert p.u_bytes_staged <= 4 * _u_bytes(GMAT_H, GMAT_L)
    assert max(s.g for s in p.slots) <= 2   # what the AOT guards compile
    # as planned today: one chunk and one U load per layer, 19-frame
    # stripes (AOT-compiled for v5e in tests/kernels/test_tpu_compile.py)
    assert p.launches == GMAT_L
    assert {(s.chunk_len, s.stripe, s.g) for s in p.slots} == {(75, 19, 1)}
    assert p.u_bytes_staged == _u_bytes(GMAT_H, GMAT_L)


def test_eesen_plan_is_pinned():
    """EESEN B64 x T300 (bidirectional, H340, L5) keeps its plan: 200
    launches of 8-frame chunks (a 4-frame remainder), each one stripe,
    fwd and bwd G-merged, at the default 16 MiB scoped VMEM.  Its step
    is memory bound, and whole-layer chunks (5 launches) ran 44% slower
    on the chip (PERF.md), so its cells stay one stripe long; each
    layer-direction's U then loads 38 times a call."""
    it = WorkItem(uid=0, family="lstm", B=64, T=300, H=340, L=5, X=120,
                  bidirectional=True, share=0)
    p = plan([it])
    check_plan(p)
    assert p.launches == 200 and p.item(0).block_t == 8
    assert {(s.chunk_len, s.stripe) for s in p.slots} == {(8, 8), (4, 4)}
    assert {s.vmem_limit() for s in p.slots} == {16 * 2**20}
    assert p.u_bytes_staged == 38 * 2 * _u_bytes(340, 5)


def test_a_width_whose_u_overflows_vmem_is_refused():
    """A 256 MiB U (H4096, float32) leaves no stripe at all: planning
    refuses it by name, with no one-frame fallback."""
    it = WorkItem(uid=0, family="lstm", B=8, T=16, H=4096, L=2)
    with pytest.raises(PlanRejected, match="H4096"):
        plan([it])
    with pytest.raises(PlanRejected, match="capacity"):
        select_time_block(16, 8, 4096)


def test_gmat_decode_is_refused_by_the_planner():
    """Chained decode at H1024 streams a layer's W and U (32 MiB, two
    buffers each) through a kernel that gets the 16 MiB default scoped
    VMEM: the planner refuses it before anything is traced or compiled.
    Shapes alone suffice — the refusal comes before any weight is read."""
    sds = jax.ShapeDtypeStruct
    layer = {"W": sds((GMAT_H, 4 * GMAT_H), jnp.float32),
             "U": sds((GMAT_H, 4 * GMAT_H), jnp.float32),
             "b": sds((4 * GMAT_H,), jnp.float32)}
    cs = rnn.compile({"layers": [layer] * GMAT_L},
                     rnn.ExecutionPolicy(interpret=True))
    state = {"h": jnp.zeros((GMAT_L, 2, GMAT_H)),
             "c": jnp.zeros((GMAT_L, 2, GMAT_H))}
    with pytest.raises(PlanRejected, match="no chained decode kernel fits"):
        cs.decode(jnp.zeros((2, 1, GMAT_H)), state)
    assert cs.stats.decode_calls == 0 and cs.stats.programs_built == 0


def test_u_bytes_staged_counts_each_layers_loads():
    """``StackStats.u_bytes_staged`` adds the plan's staged U bytes on
    every call: with one chunk per layer that is each layer's U once a
    call, and a window's delta over calls and the stack's U bytes reads
    the loads per layer."""
    H, L, B, T = 32, 3, 2, 12
    params = _params(H, L)
    cs = rnn.compile(params, rnn.ExecutionPolicy(interpret=True,
                                                 block_t=T))
    xs = _xs(B, T, H)
    cs.forward(xs)
    assert cs.plan.u_bytes_staged == _u_bytes(H, L)
    cs.forward(xs)
    assert cs.stats.u_bytes_staged == 2 * _u_bytes(H, L)
    assert "MiB of U staged" in cs.describe()

    chunked = rnn.compile(params, rnn.ExecutionPolicy(interpret=True,
                                                      block_t=T // 3))
    chunked.forward(xs)
    assert chunked.stats.u_bytes_staged == 3 * _u_bytes(H, L)
    assert chunked.stats.u_bytes_staged == sum(
        s.g * s.u_block_bytes for s in chunked.plan.slots)


def test_plan_span_and_describe_record_each_slots_launch_shape():
    """The ``plan`` span carries, slot by slot, the chunk, the stripe, the
    VMEM footprint and the scoped limit the kernel requests; the plan's
    ``describe()`` prints them on each slot line."""
    H, L, B, T = 32, 3, 2, 12
    cs = rnn.compile(_params(H, L), rnn.ExecutionPolicy(interpret=True,
                                                        trace=True))
    cs.forward(_xs(B, T, H))
    p = cs.plan
    (sp,) = [e for e in cs.tracer.events if e.name == "plan"]
    assert sp.tags["chunks"] == tuple(s.chunk_len for s in p.slots)
    assert sp.tags["stripes"] == tuple(s.stripe for s in p.slots)
    assert sp.tags["footprints"] == tuple(s.footprint() for s in p.slots)
    assert sp.tags["vmem_limits"] == tuple(s.vmem_limit() for s in p.slots)
    text = p.describe()
    for s in p.slots:
        assert (f"c{s.chunk_len} bt{s.stripe} vmem "
                f"{s.footprint() / 2**20:.2f}/"
                f"{s.vmem_limit() / 2**20:.0f}MiB") in text
