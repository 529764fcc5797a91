"""ExecutionPolicy: the validated, frozen replacement for the stringly-typed
``schedule="unfolded"`` / ``**kw`` surface of the pre-facade dispatch
wrappers.

A policy is *how* to run, never *what* to run — it carries no shapes and no
parameters, so one policy object serves every stack and every call, and a
``CompiledStack`` can hash plan-cache keys without inspecting it twice.
Every field is validated at construction with an error that names the
offending field and the allowed values (the old surface let an unknown
schedule string travel all the way into ``core.gru.run_layer``'s function
table and die as a bare KeyError).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from repro.dispatch.planner import DEFAULT_MACS
from repro.dispatch.workitem import PRECISIONS, SPARSITIES

#: "auto" lets the planner score wavefront/fused/per_step per shape;
#: the rest force one execution shape (the research schedules
#: sequential/batch/intergate/unfolded run the pure reference
#: implementations through the planner's external path).
SCHEDULES = ("auto", "wavefront", "fused", "per_step",
             "sequential", "batch", "intergate", "unfolded")

DTYPES = ("float32", "bfloat16", "float16")

#: "raise" = fail fast (pre-ISSUE-6 behaviour): the first launch failure
#: unwinds the caller.  "fallback" = the guarded execution ladder: a failed
#: fused/chained launch re-executes per-step and, failing that, through the
#: non-deprecated pure-jnp reference (oracle-equal by construction), with
#: the degradation recorded in ``CompiledStack.stats``.
ON_FAULT = ("raise", "fallback")

#: "plan" (the default) statically verifies every DispatchPlan the stack
#: builds — coverage, wavefront readiness, packing legality, VMEM budget
#: (``analysis.plancheck``) — raising a structured ``PlanInvariantError``
#: before any launch; runs once per plan-cache build, under an obs
#: ``verify`` span.  "off" skips verification (the benchmark baseline).
VERIFY = ("off", "plan")

# PRECISIONS / SPARSITIES (imported above, shared with the planner's
# WorkItems): "fp32" is bit-exact; "bf16" round-trips U through bfloat16
# (exact vs its dequantized oracle); "int8" quantizes U per-gate (4x
# smaller VMEM residency, fp32 accumulate) with a BOUNDED-error contract
# vs the dequantized oracle — the first policy surface that is not
# bit-equal (see rnn/README.md).  Sparsity: "none" (dense) or "block"
# (skip zero MXU row-tiles of U, value-exact up to dot reduction order).

#: "analytic" scores plans with the perfmodel's cycle formulas (the
#: default, zero-IO).  "measured" loads the replay-calibrated table
#: (``repro.calib``, ``artifacts/measured_costs.json``) for the bound
#: backend and scores merge/schedule/chained decisions in measured µs,
#: falling back to analytic scaling for unmeasured shapes; an empty or
#: missing table degrades to plans bit-identical to "analytic".
COST_MODELS = ("analytic", "measured")


def _bad(field: str, value, allowed) -> ValueError:
    return ValueError(
        f"ExecutionPolicy.{field}={value!r} is invalid; allowed: "
        f"{', '.join(str(a) for a in allowed)}")


@dataclass(frozen=True)
class ExecutionPolicy:
    """How a CompiledStack executes.

    schedule:  "auto" (planner-scored) or a forced schedule — one of
               ``SCHEDULES``.
    block_t:   wavefront chunk-length override, frames per cell, honored
               under "auto" too (the scorer then only weighs the pinned
               chunk against per_step); 0 = scored.  Each launch walks
               its chunk in the VMEM stripe core.tiling picks.
    interpret: force Pallas interpret mode (None = auto: interpret
               everywhere but real TPUs).
    dtype:     cast inputs before execution; None = keep the caller's.
    precision: recurrent-weight precision — "fp32" (bit-exact default),
               "bf16" (U round-tripped through bfloat16; exact vs its
               dequantized oracle), or "int8" (per-gate absmax int8
               payload resident in VMEM, fp32 accumulate; BOUNDED error
               vs the dequantized oracle, not bit-equality — see
               rnn/README.md "Precision & sparsity").  The input GEMM
               (W) always stays full precision.
    sparsity:  "none" (dense) or "block" — skip all-zero MXU row-tiles
               of each layer's recurrent matrix (tile bitmap derived from
               the bound parameters at compile; value-exact up to dot
               reduction order).
    packing:   cross-B packing + stripe alignment on/off (off = every cell
               its own launch row; the benchmark baseline).
    macs:      planner tile-engine budget (the paper's K-width exploration
               space; DEFAULT_MACS = 16K, the paper's reference design).
    on_fault:  "raise" (fail fast) or "fallback" (guarded execution
               ladder: failed launches re-execute per-step, then through
               the pure-jnp reference, recorded in ``.stats`` — see
               ``ON_FAULT``).
    check_finite: verify each launch's recurrent state is finite and raise
               a structured ``NonFiniteStateError`` naming the poisoned
               items (fallback cannot fix a NaN — it re-derives
               deterministically — so this raises under either on_fault).
    verify:    "plan" (default) statically verifies every plan the stack
               builds against the dispatch invariants — exact coverage,
               wavefront readiness, packing legality, stripe/VMEM budgets
               (``analysis.plancheck``) — raising ``PlanInvariantError``
               before anything launches; "off" skips the check.  Runs
               once per plan-cache build (amortizes to zero across cache
               hits) and is counted in ``.stats.plans_verified``.
    cost_model: "analytic" (perfmodel cycle formulas, the default) or
               "measured" (score planner decisions — merge-vs-split,
               schedule choice, chained-vs-loop decode — against the
               replay-calibrated ``repro.calib`` table for this backend;
               unmeasured shapes interpolate from the nearest measured
               neighbor or fall back to analytic, and an empty table
               plans bit-identically to "analytic").
    cost_table: path to the measured-cost JSON; None = the default
               ``artifacts/measured_costs.json``.  Only read when
               ``cost_model="measured"``.
    trace:     record host-time spans + metrics for every call, plan,
               program dispatch and decode tick, and each slot phase
               (hoist/pack/slot_launch/scatter) of the call that builds a
               plan's program, on ``CompiledStack.tracer`` (a
               ``runtime.obs.Tracer``);
               each span is also a ``repro.<name>`` annotation, so a
               ``jax.profiler`` capture around the calls puts the host
               phases on the profiler's clock beside the device time.
               Spans never fence.  Off (the default) binds the shared
               no-op tracer: no events, no annotations, outputs
               bit-identical to the untraced path.
    """

    schedule: str = "auto"
    block_t: int = 0
    interpret: Optional[bool] = None
    dtype: Optional[str] = None
    precision: str = "fp32"
    sparsity: str = "none"
    packing: bool = True
    macs: int = DEFAULT_MACS
    on_fault: str = "raise"
    check_finite: bool = False
    verify: str = "plan"
    cost_model: str = "analytic"
    cost_table: Optional[str] = None
    trace: bool = False

    def __post_init__(self):
        if self.schedule not in SCHEDULES:
            raise _bad("schedule", self.schedule, SCHEDULES)
        if (not isinstance(self.block_t, int) or isinstance(self.block_t, bool)
                or self.block_t < 0):
            raise _bad("block_t", self.block_t,
                       ("a non-negative int (0 = autotuned)",))
        if not (self.interpret is None or isinstance(self.interpret, bool)):
            raise _bad("interpret", self.interpret, (None, True, False))
        if self.dtype is not None and self.dtype not in DTYPES:
            raise _bad("dtype", self.dtype, (None,) + DTYPES)
        if self.precision not in PRECISIONS:
            raise _bad("precision", self.precision, PRECISIONS)
        if self.sparsity not in SPARSITIES:
            raise _bad("sparsity", self.sparsity, SPARSITIES)
        if not isinstance(self.packing, bool):
            raise _bad("packing", self.packing, (True, False))
        if (not isinstance(self.macs, int) or isinstance(self.macs, bool)
                or self.macs < 1):
            raise _bad("macs", self.macs, ("a positive int (MAC budget)",))
        if self.on_fault not in ON_FAULT:
            raise _bad("on_fault", self.on_fault, ON_FAULT)
        if not isinstance(self.check_finite, bool):
            raise _bad("check_finite", self.check_finite, (True, False))
        if self.verify not in VERIFY:
            raise _bad("verify", self.verify, VERIFY)
        if self.cost_model not in COST_MODELS:
            raise _bad("cost_model", self.cost_model, COST_MODELS)
        if not (self.cost_table is None or isinstance(self.cost_table, str)):
            raise _bad("cost_table", self.cost_table,
                       (None, "a path to a measured-cost JSON"))
        if not isinstance(self.trace, bool):
            raise _bad("trace", self.trace, (True, False))

    def describe(self) -> str:
        return (f"ExecutionPolicy(schedule={self.schedule}, "
                f"block_t={self.block_t or 'auto'}, "
                f"interpret={self.interpret}, dtype={self.dtype or 'keep'}, "
                f"precision={self.precision}, sparsity={self.sparsity}, "
                f"packing={self.packing}, macs={self.macs}, "
                f"on_fault={self.on_fault}, "
                f"check_finite={self.check_finite}, "
                f"verify={self.verify}, cost_model={self.cost_model}, "
                f"trace={self.trace})")
