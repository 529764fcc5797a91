"""SHARP's four LSTM schedules as real JAX computation orders (paper §5, Fig. 8).

All four produce numerically equivalent outputs (property-tested against
``models.layers.lstm.reference_unroll``); they differ in *dependence
structure*, which is what the paper is about:

  sequential  one gate after another per time step; the cell/hidden update
              waits for the last (output) gate.  [BrainWave/TPU-style]
  batch       same order but the weight matrix is dispatched in column tiles
              (MVM partial sums accumulated tile by tile) — models the
              tiled-dispatch pipeline of Fig. 8.b.
  intergate   all four gates issued as one fused GEMM per step (the 4H gate
              axis is SHARP's "processing all gates simultaneously");
              hides the intra-sequence dependency.  [E-PUR-style]
  unfolded    SHARP's contribution: the input half W·x_t of EVERY step is
              hoisted out of the recurrence into one sequence-parallel GEMM;
              the scan keeps only U·h_{t-1} + the pointwise tail.  On TPU the
              hoisted GEMM is MXU-dense and, once the data dependence is cut,
              XLA's scheduler overlaps it with the serial tail — the paper's
              across-sequence overlap.
  fused       unfolded taken to its endpoint: the recurrent scan itself moves
              inside ONE Pallas kernel launch (kernels.lstm_cell.lstm_seq),
              with (h, c) resident in VMEM scratch for all T steps and the
              hoisted xw streamed in T-block stripes — the per-step dispatch
              and the state HBM round-trip both disappear.  One pallas_call
              per layer invocation instead of T.

Stack-level scheduling additionally accepts

  wavefront   layer l at time t depends only on layer l-1 at time t, so an
              L-layer stack over T steps (chunked into nk T-blocks) runs as
              L + nk - 1 anti-diagonal *slots* instead of L·nk serial cell
              evaluations.  Each slot gathers its active (layer, chunk)
              cells — a contiguous run of layers — and executes them as ONE
              G-batched sequence-fused kernel launch; each cell's input half
              (the hoisted GEMM against the previous layer's just-produced
              chunk) is issued in the same slot and carries no recurrent
              dependence, so it overlaps with the serial tail exactly as in
              the paper's Fig. 8.d, now across layers as well as time.
              Bidirectional stacks run an *interleaved* wavefront: each
              layer's fwd walk visits chunks ascending and its bwd walk
              descending, the two directions of a wave sharing one
              G-batched launch (the concat dependency — layer l+1's chunk
              k needs both directions' chunk k of layer l — shapes the
              timeline; see dispatch/README.md "Bidirectional").

``tile`` (from core.tiling) controls the dispatch granularity of the
batch/unfolded paths, mirroring the reconfigurable tile-engine;
``core.tiling.select_time_block`` (via the autotune table) picks the fused
paths' T-stripe within the sequence kernels' VMEM capacity.

NOTE — front-end status: the per-schedule implementations here remain the
reference library (they ARE the paper's contribution and stay property-
tested), but the dispatch wrappers ``run_layer``/``run_stack`` are
DEPRECATED shims over the one planned execution path, ``repro.rnn``:

    from repro import rnn
    rnn.compile(stack_params, rnn.ExecutionPolicy(schedule="wavefront",
                                                  block_t=4)).forward(xs)

Every call — batch, serving, single layer — lowers to dispatch.WorkItems
and runs through dispatch.planner/executor, so wavefront packing, cross-B
merging, and plan caching apply uniformly (the stack-level ``wavefront``
schedule is now literally the dispatcher's packed slot timeline; the old
LSTM-only ``run_stack_wavefront`` is retired).  ``reference_stack`` below
is the non-deprecated pure-jnp oracle tests and benchmarks compare against.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.unfolded import unfold
from repro.kernels.common import cdiv
from repro.models.layers.lstm import cell_update

# NOTE: repro.kernels.lstm_cell.ops imports repro.core.autotune; importing
# it lazily inside the fused/wavefront paths keeps repro.core's package
# import acyclic regardless of which side is imported first.

SCHEDULES = ("sequential", "batch", "intergate", "unfolded", "fused")
STACK_SCHEDULES = SCHEDULES + ("wavefront",)


# ---------------------------------------------------------------------------
# single layer
# ---------------------------------------------------------------------------


def _init_state(B: int, H: int, dtype):
    return jnp.zeros((B, H), dtype), jnp.zeros((B, H), jnp.float32)


def run_layer_sequential(params, xs):
    """One gate at a time; update strictly after the O gate (Fig. 8.a)."""
    B, T, X = xs.shape
    H = params["U"].shape[0]
    W, U, b = params["W"], params["U"], params["b"]

    def step(carry, x_t):
        h, c = carry
        gates = []
        for g in range(4):  # i, f, g, o — strictly in order
            Wg = jax.lax.dynamic_slice_in_dim(W, g * H, H, axis=1)
            Ug = jax.lax.dynamic_slice_in_dim(U, g * H, H, axis=1)
            bg = jax.lax.dynamic_slice_in_dim(b, g * H, H, axis=0)
            gates.append(x_t @ Wg + h @ Ug + bg)
        h_new, c_new = cell_update(jnp.concatenate(gates, axis=-1), c)
        h_new = h_new.astype(xs.dtype)
        return (h_new, c_new), h_new

    (_, _), hs = jax.lax.scan(step, _init_state(B, H, xs.dtype), xs.swapaxes(0, 1))
    return hs.swapaxes(0, 1)


def run_layer_batch(params, xs, tile_cols: int = 0):
    """Tiled dispatch: the 4H gate axis is processed in column tiles whose
    partial results stream into the accumulator (Fig. 8.b)."""
    B, T, X = xs.shape
    H = params["U"].shape[0]
    W, U, b = params["W"], params["U"], params["b"]
    tc = tile_cols or min(4 * H, 512)
    n_tiles = -(-4 * H // tc)

    def step(carry, x_t):
        h, c = carry
        parts = []
        for i in range(n_tiles):  # tile-by-tile dispatch
            lo = i * tc
            w = min(tc, 4 * H - lo)
            Wt = jax.lax.dynamic_slice_in_dim(W, lo, w, axis=1)
            Ut = jax.lax.dynamic_slice_in_dim(U, lo, w, axis=1)
            bt = jax.lax.dynamic_slice_in_dim(b, lo, w, axis=0)
            parts.append(x_t @ Wt + h @ Ut + bt)
        h_new, c_new = cell_update(jnp.concatenate(parts, axis=-1), c)
        h_new = h_new.astype(xs.dtype)
        return (h_new, c_new), h_new

    (_, _), hs = jax.lax.scan(step, _init_state(B, H, xs.dtype), xs.swapaxes(0, 1))
    return hs.swapaxes(0, 1)


def run_layer_intergate(params, xs):
    """All four gates fused per step (Fig. 8.c)."""
    B, T, X = xs.shape
    H = params["U"].shape[0]

    def step(carry, x_t):
        h, c = carry
        gates = x_t @ params["W"] + h @ params["U"] + params["b"]
        h_new, c_new = cell_update(gates, c)
        h_new = h_new.astype(xs.dtype)
        return (h_new, c_new), h_new

    (_, _), hs = jax.lax.scan(step, _init_state(B, H, xs.dtype), xs.swapaxes(0, 1))
    return hs.swapaxes(0, 1)


def run_layer_unfolded(params, xs, cell_kernel=None):
    """SHARP: hoisted input GEMM + recurrent-only scan (Fig. 8.d).

    ``cell_kernel``: optional fused recurrent-step implementation with
    signature (U, b_zeros, xw_t, h, c) -> (h, c) — the Pallas lstm_cell
    kernel plugs in here.
    """
    B, T, X = xs.shape
    H = params["U"].shape[0]
    # ---- sequence-parallel input half: one big GEMM for every t ----------
    xw = jnp.einsum("btx,xg->btg", xs, params["W"]) + params["b"]

    if cell_kernel is None:
        def cell(xw_t, h, c):
            gates = xw_t + h @ params["U"]
            h2, c2 = cell_update(gates, c)
            return h2.astype(xs.dtype), c2
    else:
        def cell(xw_t, h, c):
            return cell_kernel(params["U"], xw_t, h, c)

    def step(carry, xw_t):
        h, c = carry
        h, c = cell(xw_t, h, c)
        return (h, c), h

    (_, _), hs = jax.lax.scan(step, _init_state(B, H, xs.dtype), xw.swapaxes(0, 1))
    return hs.swapaxes(0, 1)


def run_layer_fused(params, xs, block_t: int = 0, interpret=None,
                    seq_kernel=None, return_state: bool = False):
    """Sequence-fused schedule: the whole recurrence in ONE kernel launch.

    The input half is hoisted exactly as in ``unfolded`` (routed through
    core.unfolded.unfold), but the scan is replaced by the Pallas
    sequence kernel: state stays in VMEM scratch, xw streams in T-stripes.
    ``return_state``: also return the exact t=T (h, c) — the dispatcher's
    serving-prefill path needs it.
    """
    from repro.kernels.lstm_cell.ops import as_seq_kernel

    B, T, X = xs.shape
    H = params["U"].shape[0]
    kern = seq_kernel or as_seq_kernel(interpret=interpret, block_t=block_t)

    def input_fn(xs):
        return jnp.einsum("btx,xg->btg", xs, params["W"]) + params["b"]

    def seq_fn(state, pre):
        h0, c0 = state
        hs, h_n, c_n = kern(params["U"], pre, h0, c0)
        return (h_n.astype(xs.dtype), c_n), hs.astype(xs.dtype)

    state, hs = unfold(input_fn, None, xs, _init_state(B, H, xs.dtype),
                       seq_fn=seq_fn)
    return (hs, state) if return_state else hs


LAYER_FNS = {
    "sequential": run_layer_sequential,
    "batch": run_layer_batch,
    "intergate": run_layer_intergate,
    "unfolded": run_layer_unfolded,
    "fused": run_layer_fused,
}

# implementation-specific escape hatches the ExecutionPolicy surface does
# not (and should not) carry — a shim call using one of these goes straight
# to the reference implementation instead of through repro.rnn.compile
_IMPL_ONLY_KW = ("tile_cols", "cell_kernel", "seq_kernel", "return_state")


def _deprecated(old: str, new: str) -> None:
    warnings.warn(
        f"repro.core.schedules.{old} is deprecated; use {new} "
        "(see src/repro/rnn/README.md for the migration table)",
        DeprecationWarning, stacklevel=3)


def run_layer(params, xs, schedule: str = "unfolded", **kw):
    """DEPRECATED shim over the unified front-end (kept so pre-facade
    callers keep working): routes through ``repro.rnn.compile`` unless an
    implementation-specific kwarg (tile_cols/cell_kernel/...) pins it to
    the reference implementation directly."""
    _deprecated(
        "run_layer(params, xs, schedule)",
        "repro.rnn.compile({'layers': [params]}, "
        "ExecutionPolicy(schedule=...)).forward(xs)")
    if any(k in kw for k in _IMPL_ONLY_KW):
        if schedule not in LAYER_FNS:
            raise ValueError(
                f"unknown schedule {schedule!r}; options {SCHEDULES}")
        return LAYER_FNS[schedule](params, xs, **kw)
    from repro.rnn import ExecutionPolicy, compile as _compile

    pol = ExecutionPolicy(schedule=schedule, block_t=kw.pop("block_t", 0),
                          interpret=kw.pop("interpret", None))
    if kw:
        raise TypeError(f"run_layer: unexpected kwargs {sorted(kw)}")
    return _compile({"layers": [params]}, pol).forward(xs)


# ---------------------------------------------------------------------------
# stacks (multi-layer, optional bidirectional — EESEN-style)
# ---------------------------------------------------------------------------


def run_stack(stack_params, xs, schedule: str = "unfolded", **kw):
    """DEPRECATED shim over the unified front-end.  stack_params from
    models.layers.lstm.init_lstm_stack (or core.gru.init_gru_stack, or a
    mixed list).  xs (B,T,X).

    All schedules — including ``wavefront``, whose LSTM-only hand-rolled
    loop this shim retired — now lower to dispatch.WorkItems and execute
    through the planner/executor, exactly like ``repro.rnn.compile``."""
    _deprecated(
        "run_stack(stack_params, xs, schedule)",
        "repro.rnn.compile(stack_params, "
        "ExecutionPolicy(schedule=...)).forward(xs)")
    if any(k in kw for k in _IMPL_ONLY_KW):
        # escape-hatch kwargs pin each layer to its family's reference
        # implementation directly — only per-layer schedules qualify here
        def one(fam, layer, y):
            fns = _family_fns(fam)
            if schedule not in fns:
                raise ValueError(
                    f"schedule {schedule!r} has no per-layer {fam} "
                    f"reference implementation (the "
                    f"{sorted(_IMPL_ONLY_KW)} kwargs pin to one); "
                    f"{fam} options {tuple(fns)}")
            return fns[schedule](layer, y, **kw)

        return walk_stack(stack_params, xs, one)
    from repro.rnn import ExecutionPolicy, compile as _compile

    pol = ExecutionPolicy(schedule=schedule, block_t=kw.pop("block_t", 0),
                          interpret=kw.pop("interpret", None))
    if kw:
        raise TypeError(f"run_stack: unexpected kwargs {sorted(kw)}")
    return _compile(stack_params, pol).forward(xs)


# ---------------------------------------------------------------------------
# stack introspection + the pure-jnp oracle (non-deprecated)
# ---------------------------------------------------------------------------


def stack_families(stack_params):
    """Per-layer recurrence family of a parameter stack, inferred from the
    gate-axis width: U (H, 4H) -> lstm, U (H, 3H) -> gru.  Bidirectional
    layers are classified by their fwd half."""
    fams = []
    for i, layer in enumerate(stack_params["layers"]):
        half = layer.get("fwd", layer)
        H, G = half["U"].shape
        if G == 4 * H:
            fams.append("lstm")
        elif G == 3 * H:
            fams.append("gru")
        else:
            raise ValueError(
                f"layer {i}: unrecognized gate width {G} for H={H} "
                "(expected 4H lstm / 3H gru)")
    return tuple(fams)


def walk_stack(stack_params, xs, one):
    """THE per-layer stack walk (family- and bidirectional-aware), shared
    by the oracle, the shims' escape-hatch path, and the executor's
    external path: ``one(family, layer_params, y) -> y`` is applied layer
    by layer, with bidirectional layers running fwd on y and bwd on the
    time-flipped y, concatenated on the feature axis."""
    fams = stack_families(stack_params)
    y = xs
    for fam, layer in zip(fams, stack_params["layers"]):
        if "fwd" in layer:  # bidirectional
            f = one(fam, layer["fwd"], y)
            b = one(fam, layer["bwd"], jnp.flip(y, axis=1))
            y = jnp.concatenate([f, jnp.flip(b, axis=1)], axis=-1)
        else:
            y = one(fam, layer, y)
    return y


def _family_fns(fam):
    if fam == "lstm":
        return LAYER_FNS
    from repro.core import gru as gru_mod

    return gru_mod.LAYER_FNS


def reference_stack(stack_params, xs, schedule: str = "unfolded"):
    """Run a stack through the per-layer reference implementations — the
    pure-jnp oracle tests and benchmarks compare every planned execution
    against.  Family-aware per layer (mixed lstm/gru stacks run each layer
    through its own library) and bidirectional-aware.  NOT deprecated and
    NOT routed through the dispatcher — this is the ground truth the
    dispatcher must reproduce."""
    def one(fam, layer, y):
        fns = _family_fns(fam)
        if schedule not in fns:
            raise ValueError(f"unknown schedule {schedule!r}; "
                             f"{fam} options {tuple(fns)}")
        return fns[schedule](layer, y)

    return walk_stack(stack_params, xs, one)


# ---------------------------------------------------------------------------
# wavefront geometry (shared with repro.dispatch, whose planner packs
# several items' cells into one global slot timeline)
# ---------------------------------------------------------------------------


def wavefront_slots(n_layers: int, T: int, block_t: int) -> int:
    """Number of anti-diagonal slots: L + ceil(T / block_t) - 1."""
    return n_layers + cdiv(T, block_t) - 1


def wavefront_active(s: int, n_layers: int, nk: int):
    """Layer range [lo, hi] whose cells (l, k=s-l) are live in slot ``s``
    of an (n_layers x nk) wavefront; empty range when s is out of bounds."""
    lo = max(0, s - nk + 1)
    hi = min(n_layers - 1, s)
    return lo, hi
