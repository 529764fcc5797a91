"""The dispatch executor: runs a DispatchPlan through the Pallas kernels.

The packed slot timeline executes in order; each ``Slot`` becomes exactly
one G-batched sequence-fused kernel launch (kernels.lstm_cell.lstm_seq or
kernels.gru_cell.gru_seq), with each cell's hoisted input GEMM issued in
the same slot (no recurrent dependence, so it overlaps the serial tail —
the paper's Fig. 8.d across items as well as layers).  Per-(item, layer)
recurrent state lives in device arrays between slots and inside VMEM
scratch within a launch; the final chunk of every layer is launched at its
true remainder length (the kernels T-edge-mask internally), so the state
left behind after the last slot is the exact t=T state — which is what the
serving engine splices into its decode slots.

One program per plan: ``PlanProgram`` traces the whole slot walk once
into one ``jax.jit`` program (parameters and inputs are its arguments),
so every later call at the plan's signature is one host dispatch instead
of one per slice, flip, pad, stack, GEMM and scatter.  The walk below is
the one body of code for both: the program runs it under the trace, and
``execute`` runs it eagerly only as the fallback rung — without a
program, under an armed ``FaultInjector``, or after the program failed.
Per-slot trace spans therefore occur when a program is built, not on
every call.

Cross-B packing executes here too: a slot row may be several parameter-
sharing cells' batches concatenated (same U — the WorkItem.share contract),
and rows narrower than the slot's width are zero-padded and masked
in-kernel (``b_valid``) to exact no-ops.  ``chained`` slots (T=1 decode)
run a whole tick's dependent layer chain in ONE launch via the decode
kernels, the inter-layer value flowing through VMEM scratch.

Bidirectional cells execute in the packed timeline too (ISSUE-5): a "bwd"
cell walks its chunk in descending time — the executor feeds the sequence
kernel the time-reversed chunk slice and flips the produced stripe back
into original time order before storing it (pre-launch reversal; exact,
remainder chunks included, because the slice IS the chunk).  Each
direction carries its own recurrent state and its own parameter half
(layer["fwd"] / layer["bwd"]), and a deeper cell's input is the chunk of
the previous layer's fwd‖bwd feature concat.

Numerics: the per-cell math inside a G-batched launch is identical to the
G=1 launch (the kernel grid walks cells independently; padded rows are
masked no-ops), so a packed plan's outputs match per-item execution
exactly — property-tested in tests/dispatch/.

Fault isolation (ISSUE-6): every packed/chained launch runs behind a
guarded execution ladder.  Under ``on_fault="fallback"`` a launch that
raises (or that a ``runtime.errors.FaultInjector`` makes raise) re-executes
per-step — the same kernels at block_t=1, one launch per timestep (per
*layer* for chained decode slots) — and, failing that, through the
non-deprecated pure-jnp reference (``kernels.*.ref``), which is
oracle-equal by construction and cannot fail on a kernel launch.  Each
degradation is recorded in the caller's ``ExecutionReport``
(slot index, deepest rung, cause); ``on_fault="raise"`` preserves the
pre-ISSUE-6 fail-fast behaviour, wrapping the failure in a structured
``LaunchError`` naming the slot and the uids that shared the launch.
``check_finite`` additionally verifies each launch's recurrent state and
raises ``NonFiniteStateError`` naming exactly the poisoned items (a NaN is
deterministic — no rung can fix it — so this raises under either mode).
A program is the planned rung of every slot at once: under "fallback" a
program that fails completes through the eager walk, whose slots keep the
per-step and reference rungs.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.perfmodel import MXU_ROWS
from repro.dispatch.planner import DispatchPlan, ItemPlan
from repro.dispatch.workitem import GATES
from repro.kernels.quant import (bf16_roundtrip, compact_rows,
                                 active_row_indices, expand_rows,
                                 quantize_per_gate)
from repro.runtime.errors import (FALLBACK_LEVELS, ExecutionReport,
                                  FaultInjector, LaunchError,
                                  NonFiniteStateError)
from repro.runtime.obs import NULL_TRACER, as_tracer


def _hoist(layer_params, src, gates: int):
    """One cell's input half: (B, bt, X) @ (X, gates·H) + b -> (B,bt,g,H)."""
    B, bt, _ = src.shape
    H = layer_params["U"].shape[0]
    xw = (jnp.einsum("btx,xg->btg", src, layer_params["W"])
          + layer_params["b"])
    return xw.reshape(B, bt, gates, H)


def execute(plan: DispatchPlan, params: Dict[int, dict],
            inputs: Dict[int, jnp.ndarray], *,
            interpret: Optional[bool] = None,
            collect_state: bool = False,
            init_state: Optional[Dict[int, dict]] = None,
            prepared: Optional[Dict[int, dict]] = None,
            program: Optional["PlanProgram"] = None,
            on_fault: str = "raise",
            check_finite: bool = False,
            inject: Optional[FaultInjector] = None,
            report: Optional[ExecutionReport] = None,
            tracer=None):
    """Run ``plan``.  params[uid] = stack params ({"layers": [...]}),
    inputs[uid] = xs (B, T, X).  Returns outputs {uid: (B, T, H)} —
    (B, T, 2H) for bidirectional items (fwd‖bwd concat) — or
    (outputs, states) when ``collect_state``: states[uid] is
    {"h": (L,B,H)[, "c": (L,B,H)]} (exact t=T recurrent state); for
    bidirectional items a per-direction pair {"fwd": {...}, "bwd": {...}}
    (fwd is the exact t=T state, bwd the exact t=0 state — the end of its
    walk); or ``None`` for items that expose no (h[, c]) state at all —
    rglru (diagonal recurrence, no gate state surfaced) and any item
    executed through an external stateless schedule.  Callers splicing
    decode state must check for a plain {"h": ...} dict, as the serving
    engine does.

    ``program`` (a ``PlanProgram`` of this plan) runs the whole slot walk
    as one compiled program: one host dispatch per call, traced and
    compiled on its first call at a signature.  ``CompiledStack`` builds
    one per cached plan.  Without it — and whenever an armed ``inject``
    must fire per slot and per rung — the same walk runs eagerly, one op
    at a time: the fallback rung.  Under ``on_fault="fallback"`` a program
    that fails to trace, lower or run completes through the eager walk and
    its ladder, recorded in ``report`` (``program_fallbacks``); under
    "raise" the failure is a ``LaunchError``.

    ``init_state`` optionally seeds the recurrent state of packed items:
    init_state[uid] = {"h": (L,B,H)[, "c": (L,B,H)]} replaces the zero
    initial state (the serving engine's decode ticks resume from it).
    External-fallback items ignore it (their schedule surfaces start from
    zeros) — the planner never routes a decode item external.
    Bidirectional items reject it: their two walks start from opposite
    sequence ends, so there is no mid-stream resume point.

    ``prepared`` optionally carries pre-stacked decode weights per uid
    (see ``prepare_decode_stack``) so steady-state decode ticks don't
    restack unchanged parameters every tick.

    Slots whose ``precision != "fp32"`` or whose items carry a
    block-sparsity ``tile_map`` quantize / row-compact their recurrent
    weights inside the walk, once per (item, layer, direction) — in a
    program, once per trace, as part of the compiled program.

    ``collect_state`` reroutes unpacked (external) unidirectional items
    through the per-layer fused path — the only surface that returns exact
    state — so for those items the plan's per_step/per_layer launch
    accounting describes the stateless execution, not this one.

    ``on_fault``/``check_finite``/``inject``/``report`` drive the guarded
    execution ladder (module doc): "fallback" re-executes a failed
    packed/chained launch per-step, then through the pure-jnp reference,
    recording each degradation in ``report``; "raise" fails fast with a
    structured ``LaunchError``.  ``check_finite`` raises
    ``NonFiniteStateError`` naming exactly the items whose post-launch
    recurrent state went NaN/Inf at the first slot where any did: the walk
    returns one finiteness flag per (slot, item) and they are read once,
    after the call.  ``inject`` is the test-time fault hook
    (``runtime.errors.FaultInjector``).

    ``tracer`` (optional ``runtime.obs.Tracer``): every packed/chained
    slot gets four sibling spans that together cover its host work —
    ``hoist`` (each cell's source slice/flip/concat and input GEMM),
    ``pack`` (row concat + pad, the stacks, the slot's weight operands),
    ``slot_launch`` (the guarded launch, tagged with the slot signature
    and uids) and ``scatter`` (the results split back per cell).  Under a
    ``program`` the walk runs only while the program is traced, so these
    spans occur once per program, nested under the call that built it;
    every call gets one ``program`` span around the program's host
    dispatch.  Spans are host time and never fence; with
    ``ExecutionPolicy(trace=True)`` each is a ``repro.<name>`` annotation
    in a ``jax.profiler`` capture, which holds the device time beside it.
    Ladder recoveries appear as nested ``fallback_rung`` spans and
    ``launch_fault`` instants, a failed program as a ``program_fault``
    instant.  None (the default) binds the shared no-op tracer — no
    events, outputs bit-identical.
    """
    tracer = as_tracer(tracer)
    if on_fault not in ("raise", "fallback"):
        raise ValueError(f"execute: on_fault={on_fault!r} invalid; "
                         "allowed: raise, fallback")

    # fail fast, before any work: a plan may legitimately carry plan-only
    # items (ItemPlan.executable == False) for admission pricing — callers
    # filter those out before executing (see examples/dispatch_demo.py)
    plan_only = [ip.uid for ip in plan.items if not ip.executable]
    if plan_only:
        raise NotImplementedError(
            f"plan contains plan-only items (uids {plan_only}): multi-layer "
            "rglru executes through its model, not the dispatcher — filter "
            "by ItemPlan.executable before execute()")
    # state resume is a packed-timeline feature only; silently dropping a
    # caller's init_state for an external item would compute from zeros
    dropped = sorted(set(init_state or {}) & set(plan.external))
    if dropped:
        raise ValueError(
            f"init_state given for external-fallback items {dropped}: their "
            "schedule surfaces start from zero state — plan them onto the "
            "packed timeline (e.g. schedule='wavefront') to resume")
    bidir = [ip.uid for ip in plan.items
             if ip.item.bidirectional and ip.uid in (init_state or {})]
    if bidir:
        raise ValueError(
            f"init_state given for bidirectional items {bidir}: the "
            "fwd/bwd walks start from opposite sequence ends, so there "
            "is no mid-stream state to resume from")

    result = None
    if program is not None and not (inject is not None and inject.armed):
        result = _run_program(program, plan, params, inputs, init_state,
                              prepared, collect_state=collect_state,
                              check_finite=check_finite, on_fault=on_fault,
                              report=report, tracer=tracer)
    if result is None:
        result = _walk(plan, params, inputs, init_state, prepared,
                       interpret=interpret, collect_state=collect_state,
                       check_finite=check_finite, on_fault=on_fault,
                       inject=inject, report=report, tracer=tracer)
    outputs, states, finite = result
    if check_finite:
        _raise_nonfinite(plan, finite)
    return (outputs, states) if collect_state else outputs


class PlanProgram:
    """One plan's whole slot walk — hoist, pack, launch, scatter and the
    final concat — as one ``jax.jit`` program.

    The parameters, inputs, ``init_state`` and ``prepared`` decode weights
    are arguments, never closed over, so the program holds no weights and
    one compile serves every call at the plan's signature; whether each
    optional operand is present, ``collect_state`` and ``check_finite``
    are part of that signature.  Each signature traces the walk once
    (``builds`` counts the traces); later calls are one dispatch.  The
    program's ladder is the planned rung alone: a launch that fails to
    trace raises out of the program, and ``execute`` decides between
    ``LaunchError`` and the eager walk's full ladder."""

    def __init__(self, plan: DispatchPlan, *,
                 interpret: Optional[bool] = None, tracer=None):
        self.plan = plan
        self.builds = 0
        tracer = as_tracer(tracer)

        def plan_program(params, inputs, init_state, prepared, *,
                         collect_state: bool, check_finite: bool):
            out = _walk(plan, params, inputs, init_state, prepared,
                        interpret=interpret, collect_state=collect_state,
                        check_finite=check_finite, on_fault="raise",
                        inject=None, report=None, tracer=tracer)
            self.builds += 1
            return out

        self._jit = jax.jit(plan_program,
                            static_argnames=("collect_state", "check_finite"))

    def __call__(self, params, inputs, init_state=None, prepared=None, *,
                 collect_state: bool = False, check_finite: bool = False):
        """(outputs, states, finiteness flags) — ``execute``'s walk."""
        return self._jit(params, inputs, init_state, prepared,
                         collect_state=collect_state,
                         check_finite=check_finite)

    def lower(self, params, inputs, init_state=None, prepared=None, *,
              collect_state: bool = False, check_finite: bool = False):
        """``jax.jit(...).lower`` of the program — ahead-of-time compiles
        take shapes (``jax.ShapeDtypeStruct``) in place of arrays."""
        return self._jit.lower(params, inputs, init_state, prepared,
                               collect_state=collect_state,
                               check_finite=check_finite)


def _run_program(program, plan, params, inputs, init_state, prepared, *,
                 collect_state, check_finite, on_fault, report, tracer):
    """One call of ``program``; None when it failed under "fallback"."""
    try:
        with tracer.span("program", slots=len(plan.slots)):
            return program(params, inputs, init_state, prepared,
                           collect_state=collect_state,
                           check_finite=check_finite)
    except Exception as err:  # noqa: BLE001 — the program is a ladder rung
        uids = sorted(ip.uid for ip in plan.items)
        fault = err if isinstance(err, LaunchError) else LaunchError(
            f"plan program failed ({len(plan.slots)} slots, uids {uids}): "
            f"{err!r}", uids=uids, level=FALLBACK_LEVELS[0])
        if tracer.enabled:
            tracer.instant("program_fault", error=type(err).__name__)
        if on_fault != "fallback":
            if fault is err:
                raise
            raise fault from err
        if report is not None:
            report.record_program(fault)
        return None


def _walk(plan, params, inputs, init_state, prepared, *, interpret,
          collect_state, check_finite, on_fault, inject, report, tracer):
    """THE slot walk, eager or under a ``PlanProgram``'s trace: returns
    (outputs, states, finite), ``finite`` {(slot index, uid): bool array}
    when ``check_finite`` (else empty)."""
    outputs: Dict[int, jnp.ndarray] = {}
    states: Dict[int, dict] = {}
    finite: Dict[Tuple[int, int], jnp.ndarray] = {}
    weights: dict = {}  # per-walk memo: each layer transforms at most once

    # ---- external fallbacks (reference schedules / per-step / rglru /
    # T=0) — bidirectional items land here only under a forced stateless
    # schedule; their planned path is the interleaved packed timeline ----
    for ip in plan.items:
        if ip.uid not in plan.external:
            continue
        it = ip.item
        xs = inputs[it.uid]
        if it.family == "rglru":
            outputs[it.uid] = _run_rglru(ip, xs, interpret=interpret)
            if collect_state:
                states[it.uid] = None  # rglru exposes no (h, c) state
            continue
        if collect_state and not it.bidirectional:
            # state collection forces the per-layer fused path (the seq
            # kernels are the only surface that returns exact t=T state)
            outputs[it.uid], states[it.uid] = _run_stack_collect(
                it, params[it.uid], xs, interpret=interpret)
            continue
        # per_layer (the forced-"fused" shape) is the per-layer fused
        # path; everything else external runs its own named schedule
        # through the reference library
        sched = "fused" if ip.schedule in ("per_layer", "fused") \
            else ip.schedule
        outputs[it.uid] = _run_reference(
            params[it.uid], xs, sched,
            interpret=interpret, block_t=ip.block_t)
        if collect_state:
            states[it.uid] = None  # stateless external schedule

    # ---- packed wavefront timeline --------------------------------------
    # live state is keyed (layer, direction): unidirectional items only
    # ever touch direction "fwd"; a bidirectional item's two walks carry
    # independent state and parameter halves
    live: Dict[int, dict] = {}
    for ip in plan.items:
        if ip.uid in plan.external:
            continue
        it = ip.item
        dirs = ("fwd", "bwd") if it.bidirectional else ("fwd",)
        dtype = inputs[it.uid].dtype
        st0 = (init_state or {}).get(it.uid)

        def _c0(l):
            # cell state exists per LSTM layer only; a mixed stack's gru
            # layers carry None (their slots never read/write c)
            if it.families[l] != "lstm":
                return None
            if st0 is not None and "c" in st0:
                return st0["c"][l]
            return jnp.zeros((it.B, it.H), jnp.float32)

        live[it.uid] = {
            "plan": ip,
            "h": {(l, d): (st0["h"][l] if st0 is not None else
                           jnp.zeros((it.B, it.H), dtype))
                  for l in range(it.L) for d in dirs},
            "c": ({(l, d): _c0(l) for l in range(it.L) for d in dirs}
                  if "lstm" in it.families else None),
            "outs": {(l, d): [None] * ip.nk
                     for l in range(it.L) for d in dirs},
        }

    for slot in plan.slots:
        if slot.chained:
            _run_chained_slot(slot, params, inputs, live, finite,
                              interpret=interpret, prepared=prepared,
                              on_fault=on_fault, check_finite=check_finite,
                              inject=inject, report=report,
                              tracer=tracer)
            continue
        gates = GATES[slot.family]
        lstm = slot.family == "lstm"
        with tracer.span("hoist", slot=slot.index):
            # each cell's source chunk and input GEMM, row by row
            xw_rows = [[_hoist(_cell_layer_params(params, live[c.uid], c),
                               _cell_src(inputs, live[c.uid], c,
                                         slot.chunk_len), gates)
                        for c in grp] for grp in slot.groups]
        with tracer.span("pack", slot=slot.index):
            # cross-B row: parameter-sharing cells concatenate on B (same U
            # by the share contract — take the lead cell's); rows narrower
            # than the slot's width pad with zeros, masked in-kernel to
            # exact no-ops
            xw = jnp.stack([_cat_pad(rows, slot.B)
                            for rows in xw_rows])   # (G, B, bt, gates, H)
            h0 = _state_rows(slot, live, "h")       # (G, B, H)
            c0 = _state_rows(slot, live, "c") if lstm else None
            U, u_scales, u_rows = _slot_weights(slot, params, live, weights)
            b_valid = (jnp.asarray(slot.group_b, jnp.int32)
                       if any(b < slot.B for b in slot.group_b) else None)
            uids = sorted({c.uid for grp in slot.groups for c in grp})
            sig = slot.signature() if tracer.enabled else ""
        with tracer.span("slot_launch", slot=slot.index, sig=sig,
                         uids=uids):
            out, h_n, c_n = _guarded_launch(
                slot.index, uids,
                _seq_ladder(slot, U, xw, h0, c0, b_valid,
                            u_scales=u_scales, u_rows=u_rows,
                            interpret=interpret),
                on_fault=on_fault, inject=inject, report=report,
                tracer=tracer)
        with tracer.span("scatter", slot=slot.index):
            for g, grp in enumerate(slot.groups):
                off = 0
                for cell in grp:
                    st = live[cell.uid]
                    nb = st["plan"].item.B
                    key = (cell.layer, cell.direction)
                    st["h"][key] = h_n[g, off:off + nb].astype(h0.dtype)
                    if c_n is not None:
                        st["c"][key] = c_n[g, off:off + nb]
                    if check_finite:
                        _flag(finite, slot.index, cell.uid,
                              h_n[g, off:off + nb],
                              None if c_n is None else c_n[g, off:off + nb])
                    chunk = out[g, off:off + nb].astype(
                        inputs[cell.uid].dtype)
                    if cell.direction == "bwd":
                        # the kernel walked the chunk in reversed time;
                        # store the stripe back in original time order
                        chunk = jnp.flip(chunk, axis=1)
                    st["outs"][key][cell.chunk] = chunk
                    off += nb

    for uid, st in live.items():
        it = st["plan"].item
        top = jnp.concatenate(st["outs"][(it.L - 1, "fwd")], axis=1)
        if it.bidirectional:
            bwd = jnp.concatenate(st["outs"][(it.L - 1, "bwd")], axis=1)
            top = jnp.concatenate([top, bwd], axis=-1)
        outputs[uid] = top
        if collect_state:
            if it.bidirectional:
                # per-direction state: fwd's walk ends at t=T, bwd's at
                # t=0 — two exact end-of-walk states, no single t=T one
                states[uid] = {d: _dir_state(st, it, d)
                               for d in ("fwd", "bwd")}
            else:
                states[uid] = _dir_state(st, it, "fwd")

    return outputs, states, finite


def _state_rows(slot, live, part: str):
    """One sequence slot's (G, B, H) initial ``part`` ("h" or "c") state:
    each group's cells' rows concatenated on B and padded to the slot's
    width."""
    return jnp.stack([_cat_pad([live[c.uid][part][(c.layer, c.direction)]
                                for c in grp], slot.B)
                      for grp in slot.groups])


def _slot_weights(slot, params, live, memo: dict):
    """Stack one sequence slot's per-group recurrent-weight operands under
    the slot's precision and its items' block-sparsity tile maps.

    Returns ``(U, u_scales, u_rows)``: dense ``(G, H, gates, H)`` in the
    dtype the parameters hold U in (fp32, or bfloat16 where
    ``stage_weights`` staged it) with None markers for a plain slot; bf16
    round-trips fp32 values in place (still f32 storage — exact; a staged
    U already holds them); int8 swaps in the per-gate quantized payload
    plus ``u_scales (G, gates)``; a tile_map row-compacts to the
    slot-uniform ``Ha`` active-row count plus ``u_rows (G, Ha)``.  Groups
    without a tile_map in a sparse slot ride along dense (all-ones
    bitmap).
    Per-(item, layer, direction) transforms memoize in ``memo`` (one walk's)
    so the chunk slots of one layer quantize/compact the weights ONCE per
    walk — under a ``PlanProgram``, once per trace.
    """
    gates = GATES[slot.family]
    leads = [grp[0] for grp in slot.groups]
    quant = slot.precision == "int8"

    def _bitmap(cell):
        tm = live[cell.uid]["plan"].item.tile_map
        if tm is None:
            return (1,) * (-(-slot.H // MXU_ROWS))
        return tm[cell.layer]

    sparse = any(live[c.uid]["plan"].item.tile_map is not None
                 for c in leads)
    Ha = 0
    if sparse:
        # slot-uniform padded row count: the stacked (G, Ha) gather index
        # needs one Ha; padding rows are exact no-ops (kernels.quant)
        Ha = max(max(len(active_row_indices(_bitmap(c), slot.H))
                     for c in leads), 1)

    us, scales, rows = [], [], []
    for cell in leads:
        key = (cell.uid, cell.layer, cell.direction, slot.precision,
               Ha if sparse else -1)
        entry = memo.get(key)
        if entry is None:
            U = _cell_layer_params(params, live[cell.uid], cell)["U"] \
                .reshape(slot.H, gates, slot.H)
            if slot.precision == "bf16" and U.dtype == jnp.float32:
                U = bf16_roundtrip(U)
            s = None
            if quant:
                U, s = quantize_per_gate(U)
            r = None
            if sparse:
                U, r = compact_rows(U, _bitmap(cell), pad_to=Ha)
            entry = memo[key] = (U, s, r)
        us.append(entry[0])
        scales.append(entry[1])
        rows.append(entry[2])
    return (jnp.stack(us),
            jnp.stack(scales) if quant else None,
            jnp.stack(rows) if sparse else None)


# ---------------------------------------------------------------------------
# guarded execution ladder
# ---------------------------------------------------------------------------


def _guarded_launch(slot_index: int, uids, ladder, *, on_fault: str,
                    inject: Optional[FaultInjector],
                    report: Optional[ExecutionReport],
                    tracer=NULL_TRACER):
    """Run one slot's launch down the guarded execution ladder.

    ``ladder`` holds one thunk per ``FALLBACK_LEVELS`` rung, shallowest
    first.  Any exception a rung raises (including an injected one) is
    wrapped in a structured ``LaunchError``; under ``on_fault="fallback"``
    the next rung is tried, and a recovery at rung > 0 is recorded in
    ``report``.  The last rung is the pure-jnp reference — it cannot fail
    on a kernel launch, so under "fallback" only an armed-through-reference
    ``FaultInjector`` makes the error escape."""
    cause = None
    last = len(ladder) - 1
    for level, attempt in enumerate(ladder):
        try:
            if inject is not None:
                inject.maybe_fail(slot_index, level, uids)
            if level == 0:
                result = attempt()
            else:
                # recovery rungs get their own nested span so a trace shows
                # exactly where a launch's time went when it degraded
                with tracer.span("fallback_rung", slot=slot_index,
                                 rung=FALLBACK_LEVELS[level]):
                    result = attempt()
        except Exception as err:  # noqa: BLE001 — the ladder IS the boundary
            fault = err if isinstance(err, LaunchError) else LaunchError(
                f"launch failed: slot {slot_index} at ladder level "
                f"{FALLBACK_LEVELS[level]!r} "
                f"(uids {sorted(set(uids))}): {err!r}",
                uids=uids, slot=slot_index, level=FALLBACK_LEVELS[level])
            if tracer.enabled:
                tracer.instant("launch_fault", slot=slot_index,
                               rung=FALLBACK_LEVELS[level],
                               error=type(err).__name__)
                tracer.metrics.counter("launch_faults").add()
            if on_fault != "fallback" or level == last:
                raise fault from err
            cause = fault
            continue
        if level > 0:
            if report is not None:
                report.record(slot_index, level, cause)
            if tracer.enabled:
                tracer.metrics.counter("degraded_launches").add()
        return result
    raise LaunchError(
        f"guarded ladder for slot {slot_index} exhausted every rung "
        "without returning or raising — executor invariant broken",
        uids=uids, slot=slot_index, level=FALLBACK_LEVELS[last])


def _seq_ladder(slot, U, xw, h0, c0, b_valid, *, u_scales=None, u_rows=None,
                interpret):
    """The three launch strategies for a packed sequence slot, shallowest
    first: the planned fused launch (each cell's chunk walked in the
    slot's VMEM stripes, ``slot.stripe`` frames a grid step, its U
    resident throughout); per-step — the same kernels at
    block_t=1, one launch per timestep; and the pure-jnp reference scan.
    All three consume the identical pre-hoisted ``xw`` (bwd cells arrive
    pre-flipped), so their outputs agree to the kernel's own tolerance and
    the scatter below is rung-agnostic.  Quantized / row-compacted slots
    pass their operands down the kernel rungs unchanged; the reference
    rung reconstructs the dense dequantized matrix (value-identical to
    what the kernel computes with, see kernels.quant), so every rung
    satisfies the same oracle bound."""
    from repro.kernels.gru_cell.ops import gru_seq
    from repro.kernels.gru_cell.ref import gru_seq_ref
    from repro.kernels.lstm_cell.ops import lstm_seq
    from repro.kernels.lstm_cell.ref import lstm_seq_ref

    lstm = slot.family == "lstm"

    def fused():
        if lstm:
            return lstm_seq(U, xw, h0, c0, b_valid=b_valid,
                            u_scales=u_scales, u_rows=u_rows,
                            block_t=slot.stripe, interpret=interpret)
        out, h_n = gru_seq(U, xw, h0, b_valid=b_valid,
                           u_scales=u_scales, u_rows=u_rows,
                           block_t=slot.stripe, interpret=interpret)
        return out, h_n, None

    def per_step():
        outs, h, c = [], h0, c0
        for t in range(slot.chunk_len):
            xw_t = xw[:, :, t:t + 1]
            if lstm:
                o, h, c = lstm_seq(U, xw_t, h, c, b_valid=b_valid,
                                   u_scales=u_scales, u_rows=u_rows,
                                   block_t=1, interpret=interpret)
            else:
                o, h = gru_seq(U, xw_t, h, b_valid=b_valid,
                               u_scales=u_scales, u_rows=u_rows,
                               block_t=1, interpret=interpret)
            outs.append(o)
        return jnp.concatenate(outs, axis=2), h, (c if lstm else None)

    def reference():
        Ud = U
        if u_scales is not None:  # dequantize the int8 payload
            Ud = Ud.astype(jnp.float32) * u_scales[:, None, :, None]
        if u_rows is not None:    # scatter compacted rows back to dense
            Ud = jnp.stack([expand_rows(Ud[g], u_rows[g], slot.H)
                            for g in range(Ud.shape[0])])
        if lstm:
            return lstm_seq_ref(Ud, xw, h0, c0)
        out, h_n = gru_seq_ref(Ud, xw, h0)
        return out, h_n, None

    return [fused, per_step, reference]


def _flag(finite: dict, slot_index: int, uid: int, h_rows,
          c_rows=None) -> None:
    """AND one cell's post-launch state finiteness into the (slot, uid)
    flag — an array, so a program can return it (no ``bool()`` on a
    tracer)."""
    ok = jnp.isfinite(h_rows).all()
    if c_rows is not None:
        ok = ok & jnp.isfinite(c_rows).all()
    key = (slot_index, uid)
    finite[key] = ok if key not in finite else finite[key] & ok


def _raise_nonfinite(plan: DispatchPlan, finite: dict) -> None:
    """Read every flag in one transfer; raise ``NonFiniteStateError`` for
    the first slot with a non-finite item, naming exactly its uids."""
    if not finite:
        return
    keys = sorted(finite)
    ok = np.asarray(jnp.stack([finite[k] for k in keys]))
    bad = [k for k, good in zip(keys, ok) if not good]
    if not bad:
        return
    index = bad[0][0]
    uids = sorted(uid for s, uid in bad if s == index)
    if next(s for s in plan.slots if s.index == index).chained:
        raise NonFiniteStateError(
            f"non-finite recurrent state after chained slot {index} "
            f"(uids {uids})", uids=uids, slot=index, where="decode tick")
    raise NonFiniteStateError(
        f"non-finite recurrent state after slot {index} (uids {uids})",
        uids=uids, slot=index, where="slot state")


def _dir_state(st, item, direction: str) -> dict:
    """Stack one direction's per-layer end-of-walk state into the
    documented {"h": (L,B,H)[, "c"]} shape (gru rows of a mixed stack's
    "c" are zeros)."""
    out = {"h": jnp.stack([st["h"][(l, direction)]
                           for l in range(item.L)])}
    if st["c"] is not None:
        out["c"] = jnp.stack(
            [st["c"][(l, direction)]
             if st["c"][(l, direction)] is not None
             else jnp.zeros((item.B, item.H), jnp.float32)
             for l in range(item.L)])
    return out


def _cell_layer_params(params, st, cell):
    """The parameter dict one cell's launch row binds: the cell's layer,
    and for bidirectional items the cell's direction half."""
    layer = params[cell.uid]["layers"][cell.layer]
    if st["plan"].item.bidirectional:
        layer = layer[cell.direction]
    return layer


def _cell_src(inputs, st, cell, chunk_len: int):
    """One cell's input chunk, in the cell's own walk order.

    Layer 0 reads the item's input slice; deeper layers read the previous
    layer's just-produced chunk — for bidirectional items the fwd‖bwd
    feature concat (both stored in original time order).  "bwd" cells walk
    descending time: the chunk slice is flipped before the hoist
    (pre-launch reversal — exact, the slice IS the chunk, remainders
    included)."""
    ip: ItemPlan = st["plan"]
    it = ip.item
    if cell.layer == 0:
        t0 = cell.chunk * ip.block_t
        src = inputs[cell.uid][:, t0:t0 + chunk_len]
    elif it.bidirectional:
        src = jnp.concatenate(
            [st["outs"][(cell.layer - 1, "fwd")][cell.chunk],
             st["outs"][(cell.layer - 1, "bwd")][cell.chunk]], axis=-1)
    else:
        src = st["outs"][(cell.layer - 1, "fwd")][cell.chunk]
    if cell.direction == "bwd":
        src = jnp.flip(src, axis=1)
    return src


def _cat_pad(rows, B: int):
    """Concatenate row arrays on the batch axis, zero-padding to width B
    (the padded rows are masked to exact no-ops in-kernel)."""
    cat = jnp.concatenate(rows) if len(rows) > 1 else rows[0]
    if cat.shape[0] == B:
        return cat
    pad = [(0, B - cat.shape[0])] + [(0, 0)] * (cat.ndim - 1)
    return jnp.pad(cat, pad)


def stage_weights(params: dict) -> dict:
    """``params`` with each layer's (and direction's) input and recurrent
    matrices W and U cast to bfloat16: the values a one-pass bfloat16 MXU
    dot rounds them to.  U feeds the sequence kernel's bfloat16 dot
    (``kernels.common.mxu_bf16_operands``), W the hoisted input GEMM,
    whose program would otherwise convert it on every call; b stays
    float32.  ``rnn.CompiledStack`` stages a stack once and passes the
    result to its forward and prefill programs, so no call casts a
    weight; at that precision every other reader (an external schedule's
    ``x @ W`` and ``h @ U``) rounds its operands to the same values."""
    def half(p):
        return dict(p, W=p["W"].astype(jnp.bfloat16),
                    U=p["U"].astype(jnp.bfloat16))

    return dict(params, layers=[
        dict(l, fwd=half(l["fwd"]), bwd=half(l["bwd"])) if "fwd" in l
        else half(l) for l in params["layers"]])


def prepare_decode_stack(stack_params: dict, family: str,
                         precision: str = "fp32") -> dict:
    """Stack a parameter stack into the decode kernels' (L, ...) weight
    layout: {"Ws", "bs", "Us"}.  Steady-state callers (the serving engine)
    compute this ONCE per stack and pass it to ``execute(prepared=...)`` —
    the weights don't change between ticks, so restacking them per tick
    would dwarf the launch-overhead saving the chained slot exists for.

    Ws[0] is a zero placeholder when layer 0's input width differs from H;
    the kernel never reads it (layer 0's input half arrives pre-hoisted).

    ``precision`` != "fp32" round-trips each layer's recurrent matrix
    through the precision's fake-quant (``kernels.quant.fake_quant_stack``,
    U only — W/b stay full precision) before stacking: decode ticks run
    the dense dequantized values, so a quantized stack's decode output
    matches its dequantized oracle EXACTLY — the bounded-error contract
    only ever spends its budget in the sequence kernels' scaled dot.
    """
    gates = GATES[family]
    if precision != "fp32":
        from repro.kernels.quant import fake_quant_stack
        stack_params = fake_quant_stack(stack_params, precision)
    stack = stack_params["layers"]
    H = stack[0]["U"].shape[0]
    L = len(stack)
    W0 = (stack[0]["W"].reshape(H, gates, H)
          if stack[0]["W"].shape[0] == H else
          jnp.zeros((H, gates, H), stack[0]["W"].dtype))
    return {
        "Ws": jnp.stack([W0] + [stack[l]["W"].reshape(H, gates, H)
                                for l in range(1, L)]),
        "bs": jnp.stack([stack[l]["b"].reshape(gates, H)
                         for l in range(L)]),
        "Us": jnp.stack([stack[l]["U"].reshape(H, gates, H)
                         for l in range(L)]),
    }


def _run_chained_slot(slot, params, inputs, live, finite, *,
                      interpret=None,
                      prepared=None, on_fault: str = "raise",
                      check_finite: bool = False,
                      inject: Optional[FaultInjector] = None,
                      report: Optional[ExecutionReport] = None,
                      tracer=NULL_TRACER):
    """Execute a chained decode slot: ONE launch for a whole T=1 tick.

    The slot's groups are the L serially dependent layer cells, each the
    B-concatenation of the tick's parameter-sharing items; the decode
    kernel walks layers in grid order, chaining the inter-layer value
    through VMEM scratch (see kernels.*.lstm_decode/gru_decode).  Layer
    0's input GEMM is hoisted here, inside the slot (it exists before
    launch); deeper layers' input GEMMs run in-kernel off the chain.

    Runs behind the same guarded ladder as sequence slots — the per_step
    rung here is per-*layer*: L separate T=1 sequence-kernel launches
    chaining the inter-layer value on the host.
    """
    gates = GATES[slot.family]
    row_cells = slot.groups[0]      # request row order, fixed across layers
    lead_uid = row_cells[0].uid
    stack = params[lead_uid]["layers"]
    L = len(slot.groups)

    with tracer.span("hoist", slot=slot.index):
        xw0_rows = [_hoist(stack[0], inputs[c.uid], gates)[:, 0]
                    for c in row_cells]
    with tracer.span("pack", slot=slot.index):
        xw0 = _cat_pad(xw0_rows, slot.B)                 # (B, gates, H)
        prep = ((prepared or {}).get(lead_uid)
                or prepare_decode_stack(params[lead_uid], slot.family,
                                        precision=slot.precision))
        Ws, bs, Us = prep["Ws"], prep["bs"], prep["Us"]
        h0 = jnp.stack([_cat_pad([live[c.uid]["h"][(l, "fwd")]
                                  for c in row_cells],
                                 slot.B) for l in range(L)])  # (L, B, H)
        if slot.family == "lstm":
            c0 = jnp.stack([_cat_pad([live[c.uid]["c"][(l, "fwd")]
                                      for c in row_cells],
                                     slot.B) for l in range(L)])
        else:
            c0 = None
        uids = sorted({c.uid for c in row_cells})
        sig = slot.signature() if tracer.enabled else ""
    with tracer.span("slot_launch", slot=slot.index, sig=sig, uids=uids):
        h_n, c_n = _guarded_launch(
            slot.index, uids,
            _chained_ladder(slot, xw0, Ws, bs, Us, h0, c0,
                            interpret=interpret),
            on_fault=on_fault, inject=inject, report=report, tracer=tracer)

    with tracer.span("scatter", slot=slot.index):
        off = 0
        for cell in row_cells:
            st = live[cell.uid]
            nb = st["plan"].item.B
            dtype = inputs[cell.uid].dtype
            if check_finite:
                _flag(finite, slot.index, cell.uid, h_n[:, off:off + nb],
                      None if c_n is None else c_n[:, off:off + nb])
            for l in range(L):
                st["h"][(l, "fwd")] = h_n[l, off:off + nb].astype(h0.dtype)
                if c_n is not None:
                    st["c"][(l, "fwd")] = c_n[l, off:off + nb]
                # layer l's new h IS its T=1 output frame
                st["outs"][(l, "fwd")][0] = \
                    h_n[l, off:off + nb, None].astype(dtype)
            off += nb


def _chained_ladder(slot, xw0, Ws, bs, Us, h0, c0, *, interpret):
    """The three launch strategies for a chained T=1 decode slot: the
    planned single decode-kernel launch; per-layer — L separate T=1
    sequence-kernel launches with the inter-layer value (and its input
    GEMM) chained on the host; and the pure-jnp reference cells walked the
    same way.  All return ((L,B,H) h_n, (L,B,H) c_n | None)."""
    from repro.kernels.gru_cell.ops import gru_decode, gru_seq
    from repro.kernels.gru_cell.ref import gru_step_ref
    from repro.kernels.lstm_cell.ops import lstm_decode, lstm_seq
    from repro.kernels.lstm_cell.ref import lstm_cell_ref

    lstm = slot.family == "lstm"
    L = h0.shape[0]

    def fused():
        if lstm:
            return lstm_decode(xw0, Ws, bs, Us, h0, c0, interpret=interpret)
        return gru_decode(xw0, Ws, bs, Us, h0, interpret=interpret), None

    def chain(step):
        # walk the layer chain on the host: layer l>0's input half is the
        # previous layer's fresh h through that layer's input GEMM
        hs, cs = [], []
        xw_t = xw0
        for l in range(L):
            if l:
                xw_t = (jnp.einsum("bh,hgj->bgj", hs[-1], Ws[l])
                        + bs[l]).astype(xw0.dtype)
            h, c = step(l, xw_t)
            hs.append(h)
            cs.append(c)
        return jnp.stack(hs), (jnp.stack(cs) if lstm else None)

    def per_layer(l, xw_t):
        if lstm:
            _, h, c = lstm_seq(Us[l][None], xw_t[None, :, None],
                               h0[l][None], c0[l][None],
                               block_t=1, interpret=interpret)
            return h[0], c[0]
        _, h = gru_seq(Us[l][None], xw_t[None, :, None], h0[l][None],
                       block_t=1, interpret=interpret)
        return h[0], None

    def reference(l, xw_t):
        if lstm:
            return lstm_cell_ref(Us[l], xw_t, h0[l], c0[l])
        return gru_step_ref(Us[l], xw_t, h0[l]), None

    return [fused, lambda: chain(per_layer), lambda: chain(reference)]


def _run_reference(stack, xs, schedule, *, interpret=None,
                   block_t: int = 0):
    """External (unpacked) execution of a stack through the reference
    schedule library — per-layer family aware (families inferred from the
    bound parameters by ``core.schedules.walk_stack``), with the
    bidirectional fwd/bwd split.

    ``fused`` is one internally-striped sequence-kernel launch per layer
    (and per direction); ``per_step`` is the honest per-(layer, step)
    cell-kernel accounting for lstm layers (gru has no per-step pallas
    kernel — pure-jnp unfolded scan, zero launches); the research
    schedules (sequential/batch/intergate/unfolded) run the pure-jnp
    implementations in core.schedules / core.gru.
    """
    from repro.core import gru as gru_mod
    from repro.core import schedules as sch

    if schedule not in ("fused", "per_step"):
        # research schedules ARE the oracle: delegate, one dispatch table
        return sch.reference_stack(stack, xs, schedule)

    def one(family, layer, y):
        if schedule == "fused":
            fn = (sch.run_layer_fused if family == "lstm"
                  else gru_mod.run_layer_fused)
            return fn(layer, y, block_t=block_t, interpret=interpret)
        if family == "lstm":  # per_step: one cell-kernel launch per step
            from repro.kernels.lstm_cell.ops import as_cell_kernel

            return sch.run_layer_unfolded(
                layer, y, cell_kernel=as_cell_kernel(interpret=interpret))
        return gru_mod.run_layer_unfolded(layer, y)

    return sch.walk_stack(stack, xs, one)


def _run_stack_collect(item, stack, xs, *, interpret=None):
    """Unidirectional stack, layer by layer through the fused schedule APIs
    (return_state=True), returning (outputs, exact t=T states) — the
    fallback path when a caller needs state (serving prefill) for an
    unpacked item.  Mixed stacks: gru layers contribute zero rows to "c"
    (present whenever any layer is an LSTM)."""
    from repro.core import gru as gru_mod
    from repro.core import schedules as sch

    y = xs
    any_lstm = "lstm" in item.families
    hs_f, cs_f = [], []
    for fam, layer in zip(item.families, stack["layers"]):
        if fam == "lstm":
            y, (h_n, c_n) = sch.run_layer_fused(layer, y,
                                                interpret=interpret,
                                                return_state=True)
            cs_f.append(c_n)
        else:
            y, h_n = gru_mod.run_layer_fused(layer, y, interpret=interpret,
                                             return_state=True)
            if any_lstm:
                cs_f.append(jnp.zeros((xs.shape[0], item.H), jnp.float32))
        hs_f.append(h_n.astype(xs.dtype))
    state = {"h": jnp.stack(hs_f)}
    if cs_f:
        state["c"] = jnp.stack(cs_f)
    return y, state


def _run_rglru(ip: ItemPlan, xs, *, interpret=None):
    """rglru items execute layer-by-layer through the fused scan kernel.

    The dispatcher's contract for this family is the recurrence core only
    (the surrounding block mixing belongs to the model): inputs arrive as
    a (log_a, gx) pair per the kernel's signature, restricted to L == 1 —
    multi-layer rglru items are plan-only (latency/launch accounting).
    """
    from repro.kernels.rglru.ops import rglru_scan

    log_a, gx = xs
    B, T, W = gx.shape
    h0 = jnp.zeros((B, W), gx.dtype)
    hs, _ = rglru_scan(log_a, gx, h0, interpret=interpret)
    return hs
