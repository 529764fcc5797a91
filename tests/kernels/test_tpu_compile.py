"""Ahead-of-time compile of the recurrent kernels for a described TPU v5e.

Interpret mode never runs the TPU compiler, so these tests compile the main
path's kernels with ``interpret=False`` for one chip of a described (not
attached) ``v5e:2x2`` topology, at the widths ``chip_smoke.py`` serves: the
paper's H340 stacks (BYSDNE, EESEN).  A refused lowering, an illegal block
shape or a VMEM overflow fails here, at no chip time.  Nothing runs, so
nothing here says anything about results or speed.

The topology is described inside a fixture, never at import time: only one
process may hold the TPU compiler library, and every test worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tiling import (cdiv, select_time_block, seq_block_footprint,
                               seq_fits)
from repro.kernels.gru_cell.ops import gru_decode, gru_seq
from repro.kernels.lstm_cell.ops import lstm_decode, lstm_seq

H = 340          # BYSDNE / EESEN hidden width
GATES = {"lstm": 4, "gru": 3}
#: (weight dtype, activation dtype): all-f32 (EESEN), all-bf16, and the
#: served BYSDNE mix — bf16 weights under f32 frames and state
DTYPES = {"fp32": ("float32", "float32"), "bf16": ("bfloat16", "bfloat16"),
          "bf16w": ("bfloat16", "float32")}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args, **kwargs):
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Mosaic kernel
    return compiled


def _seq_args(sh, family, G, B, T, wdt, adt, *, mask=False, quant=False):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=sh)

    g = GATES[family]
    args = [s((G, H, g, H), "int8" if quant else wdt),
            s((G, B, T, g, H), adt), s((G, B, H), adt)]
    if family == "lstm":
        args.append(s((G, B, H), "float32"))
    kw = {}
    if mask:
        kw["b_valid"] = s((G,), "int32")
    if quant:
        kw["u_scales"] = s((G, g), "float32")
    return args, kw


def _seq_fn(family, block_t):
    op = lstm_seq if family == "lstm" else gru_seq

    def fn(*args, **kw):
        return op(*args, **kw, block_t=block_t, interpret=False)

    return fn


@pytest.mark.parametrize("mask", [False, True], ids=["dense", "b_mask"])
@pytest.mark.parametrize("G", [1, 2])
@pytest.mark.parametrize("dtypes", list(DTYPES))
@pytest.mark.parametrize("family", ["lstm", "gru"])
def test_seq_kernel_compiles(one_chip, family, dtypes, G, mask):
    """T=30 in stripes of 8: several t-blocks and a ragged T edge."""
    args, kw = _seq_args(one_chip, family, G, 4, 30, *DTYPES[dtypes],
                         mask=mask)
    _compile(_seq_fn(family, 8), *args, **kw)


@pytest.mark.parametrize("family", ["lstm", "gru"])
def test_int8_seq_kernel_compiles(one_chip, family):
    args, kw = _seq_args(one_chip, family, 2, 4, 30, "float32", "float32",
                         quant=True)
    _compile(_seq_fn(family, 8), *args, **kw)


@pytest.mark.parametrize("wdt,precision", [("float32", "fp32"),
                                           ("bfloat16", "bf16")])
@pytest.mark.parametrize("family", ["lstm", "gru"])
def test_seq_kernel_compiles_at_vmem_budget_edge(one_chip, family, wdt,
                                                 precision):
    """The widest stripe the planner's VMEM capacity admits at H340, where
    the kernel asks for nearly all of it as its scoped limit: B=7 pads to
    8 sublanes and the gate lanes 4H=1360 to 1408, and a T longer than
    any stripe makes the walk take the capacity's stripe (one fewer grid
    step would not fit)."""
    G, B, T = 2, 7, 4096
    g = GATES[family]
    bt = select_time_block(T, B, H, gates=g, precision=precision)
    steps = cdiv(T, bt)
    assert steps > 1 and not seq_fits(seq_block_footprint(
        cdiv(T, steps - 1), B, H, gates=g, precision=precision))
    args, kw = _seq_args(one_chip, family, G, B, T, wdt, "float32")
    _compile(_seq_fn(family, bt), *args, **kw)


@pytest.mark.parametrize("T", [8, 4])
def test_eesen_slot_compiles(one_chip, T):
    """EESEN B=8 T=300 plans 8-step stripes, G<=2, and a 4-step remainder."""
    args, kw = _seq_args(one_chip, "lstm", 2, 8, T, "float32", "float32")
    _compile(_seq_fn("lstm", T), *args, **kw)


@pytest.mark.parametrize("dtypes", list(DTYPES))
@pytest.mark.parametrize("family", ["lstm", "gru"])
def test_decode_kernel_compiles(one_chip, family, dtypes):
    """One chained decode tick through L=5 layers at H340."""
    wdt, adt = DTYPES[dtypes]
    L, B, g = 5, 4, GATES[family]

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    args = [s((B, g, H), adt), s((L, H, g, H), wdt), s((L, g, H), wdt),
            s((L, H, g, H), wdt), s((L, B, H), adt)]
    if family == "lstm":
        args.append(s((L, B, H), "float32"))
    op = lstm_decode if family == "lstm" else gru_decode
    _compile(lambda *a: op(*a, interpret=False), *args)


def _stack_shapes(sh, Hw, X, L, bidirectional):
    """A float32 stack's parameter shapes, placed on the described chip."""
    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sh)

    def half(x):
        return {"W": s((x, 4 * Hw)), "U": s((Hw, 4 * Hw)), "b": s((4 * Hw,))}

    if bidirectional:
        return {"layers": [{"fwd": half(X if l == 0 else 2 * Hw),
                            "bwd": half(X if l == 0 else 2 * Hw)}
                           for l in range(L)]}
    return {"layers": [half(X if l == 0 else Hw) for l in range(L)]}


def _plan_program(sh, params, B, T, X, bound=None):
    """A compiled stack's plan at (B, T), and the plan's program lowered
    over ``bound`` (default ``params``: the float32 stack)."""
    from repro import rnn
    from repro.dispatch import PlanProgram

    plan = rnn.compile(params, rnn.ExecutionPolicy(interpret=False)) \
        .lower(B, T)
    lowered = PlanProgram(plan, interpret=False).lower(
        {0: params if bound is None else bound},
        {0: jax.ShapeDtypeStruct((B, T, X), jnp.float32, sharding=sh)})
    return plan, lowered


def test_eesen_plan_program_compiles(one_chip):
    """The whole EESEN plan program — every slot's hoist, pack, launch and
    scatter as one jitted program — for bidirectional H340 L5 at B64 in
    float32, at the default precision and under a caller's ``highest``
    (where a compiled stack binds its float32 U).  T60 has the slot
    structure of the benchmark's T300 (8-step stripes, a ragged remainder,
    interleaved fwd/bwd waves) with fewer chunks, so the compile stays
    short."""
    B, T, X, L = 64, 60, 120, 5
    params = _stack_shapes(one_chip, H, X, L, True)
    for level in ("default", "highest"):
        with jax.default_matmul_precision(level):
            _, lowered = _plan_program(one_chip, params, B, T, X)
            assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("G", [1, 2])
@pytest.mark.parametrize("family", ["lstm", "gru"])
def test_h1024_seq_kernel_compiles(one_chip, family, G):
    """GMAT's width: one float32 U is 16 MiB, the default scoped VMEM of a
    v5e kernel, so the kernel must ask for the limit its footprint needs.
    B64 with the stripe the planner picks for GMAT's 75-frame chunks
    (19: three full grid steps and a ragged fourth), at G=1 (GMAT's plan)
    and G=2 (the next cell's U fetched at the g boundary)."""
    Hw, B, T, g = 1024, 64, 75, GATES[family]
    bt = select_time_block(T, B, Hw, gates=g)
    if family == "lstm":
        assert bt == 19

    def s(shape, dt="float32"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    args = [s((G, Hw, g, Hw)), s((G, B, T, g, Hw)), s((G, B, Hw))]
    if family == "lstm":
        args.append(s((G, B, Hw)))
    _compile(_seq_fn(family, bt), *args)


def test_gmat_plan_program_compiles(one_chip):
    """The whole GMAT plan program — 17 unidirectional H1024 layers at B64
    in float32, every hoisted input GEMM and lstm_seq launch — at T16, a
    chunk of 16 frames per layer, so the compile stays short; at the
    default precision and under a caller's ``highest``."""
    B, T, Hw, L = 64, 16, 1024, 17
    params = _stack_shapes(one_chip, Hw, Hw, L, False)
    for level in ("default", "highest"):
        with jax.default_matmul_precision(level):
            plan, lowered = _plan_program(one_chip, params, B, T, Hw)
            assert plan.launches == L
            assert lowered.compile().as_text().count("tpu_custom_call") \
                >= L


@pytest.mark.parametrize("G", [1, 2])
def test_h1024_staged_seq_kernel_compiles(one_chip, G):
    """GMAT's width with U staged in bfloat16, at the stripe the planner
    picks (it budgets U at 4 bytes a weight, so 19 frames as for float32
    U), at G=1 (GMAT's plan) and G=2."""
    Hw, B, T = 1024, 64, 75
    bt = select_time_block(T, B, Hw)
    assert bt == 19

    def s(shape, dt="float32"):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    _compile(_seq_fn("lstm", bt), s((G, Hw, 4, Hw), "bfloat16"),
             s((G, B, T, 4, Hw)), s((G, B, Hw)), s((G, B, Hw)))


#: a convert to bfloat16 in the program as traced (StableHLO), before
#: XLA's own passes add theirs (such as the hoist GEMM's operands)
_STAGING = re.compile(r"stablehlo\.convert.*-> tensor<[^>]*bf16>")
#: a convert to bfloat16 in the compiled program, with its result's shape
_CAST = re.compile(r"= bf16\[([0-9,]*)\]\S* convert\(")


@pytest.mark.parametrize("cell", ["eesen", "gmat"])
def test_benchmark_plan_programs_take_staged_u(one_chip, cell):
    """The benchmark cells' whole plan programs at their published shapes
    (EESEN B64 x T300, GMAT B64 x T75) compile over the stack a compiled
    stack binds at the default precision (``dispatch.stage_weights``):
    every W and U argument is bfloat16, no call casts one (neither the
    traced nor the compiled program converts to bfloat16 an array of a
    weight's shape), and the plans keep their launches — 200 and 17 — and
    GMAT its 19-frame stripe."""
    from repro.dispatch import stage_weights

    Hw, X, L, T, bidir, launches = {
        "eesen": (H, 120, 5, 300, True, 200),
        "gmat": (1024, 1024, 17, 75, False, 17)}[cell]
    B = 64
    params = _stack_shapes(one_chip, Hw, X, L, bidir)
    staged = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(stage_weights, params))
    plan, lowered = _plan_program(one_chip, params, B, T, X, bound=staged)
    assert plan.launches == launches
    if cell == "gmat":
        assert {s.stripe for s in plan.slots} == {19}
    weights = {}
    for path, a in jax.tree_util.tree_leaves_with_path(lowered.args_info):
        if path[-1] in (jax.tree_util.DictKey("W"), jax.tree_util.DictKey("U")):
            weights.setdefault(path[-1].key, []).append(a)
    assert {k: len(v) for k, v in weights.items()} == {
        "W": L * (2 if bidir else 1), "U": L * (2 if bidir else 1)}
    assert {str(a.dtype) for v in weights.values() for a in v} == \
        {"bfloat16"}
    assert {a.shape for a in weights["U"]} == {(Hw, 4 * Hw)}
    assert not _STAGING.search(lowered.as_text())
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= launches
    shapes = {tuple(a.shape) for v in weights.values() for a in v}
    casts = {tuple(int(d) for d in m.split(",") if d)
             for m in _CAST.findall(text)}
    assert not {tuple(d for d in c if d != 1) for c in casts} & shapes
