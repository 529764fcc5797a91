"""Replay harness: lower a Candidate to the executor's launch and time it.

Each candidate becomes the SAME kernel call the executor's planned rung
issues for that signature — ``lstm_seq``/``gru_seq`` with (G, B, bt)
batched operands for sequence slots, ``lstm_decode``/``gru_decode`` with
(L, ...) stacked weights for chained decode slots — on synthetic operands
of the candidate's shapes and dtype.  Timing goes through
``runtime.obs.measure_samples`` (warmup-excluded, ``block_until_ready``
fenced — the repo's one clock, repolint RL003), and the per-signature
median + p90 land in a ``MeasuredCostTable`` beside the perfmodel's
analytic estimate for the same shape, so every entry carries its own
``cycles_per_us`` calibration signal.

The input hoist (the X-GEMM) is deliberately NOT replayed: the executor
issues it outside the ``slot_launch`` span (in the slot's ``hoist``
phase; it overlaps the serial tail), so the measured µs here describe the
launch alone.
"""
from __future__ import annotations

import statistics
from typing import Iterable, Optional, Sequence

from repro.calib.candidates import Candidate, dedupe
from repro.calib.table import (MeasuredCostTable, analytic_shape_cycles,
                               current_backend)
from repro.core.perfmodel import Design
from repro.dispatch.workitem import GATES
from repro.runtime.obs import measure_samples


def _operands(cand: Candidate, interpret: Optional[bool]):
    """Synthetic operands matching the executor's call for this shape,
    and the launch thunk over them."""
    import jax.numpy as jnp

    gates = GATES[cand.family]
    H, G, B, bt = cand.H, cand.G, cand.B, cand.block_t
    dt = jnp.dtype(cand.dtype)
    lstm = cand.family == "lstm"

    def filled(shape, dtype=dt):
        # deterministic non-trivial values (no PRNG dependency, nothing
        # that can saturate the gates' nonlinearities to a constant)
        n = 1
        for s in shape:
            n *= s
        return (jnp.arange(n, dtype=jnp.float32).reshape(shape)
                % 7.0 * 0.03 - 0.1).astype(dtype)

    if cand.chained:
        # a decode tick: G is the layer count L (executor's chained rung)
        from repro.kernels.gru_cell.ops import gru_decode
        from repro.kernels.lstm_cell.ops import lstm_decode

        L = G
        xw0 = filled((B, gates, H))
        Ws = filled((L, H, gates, H))
        bs = filled((L, gates, H))
        Us = filled((L, H, gates, H))
        h0 = filled((L, B, H))
        if lstm:
            c0 = filled((L, B, H), jnp.float32)
            return lambda: lstm_decode(xw0, Ws, bs, Us, h0, c0,
                                       interpret=interpret)
        return lambda: gru_decode(xw0, Ws, bs, Us, h0, interpret=interpret)

    from repro.kernels.gru_cell.ops import gru_seq
    from repro.kernels.lstm_cell.ops import lstm_seq

    U = filled((G, H, gates, H))
    xw = filled((G, B, bt, gates, H))
    h0 = filled((G, B, H))
    u_scales = None
    if cand.precision == "int8":
        # the executor's quantized hoist: int8 payload + per-gate scales,
        # so the measured µs is the quantized launch's, not the fp32 one's
        from repro.kernels.quant import quantize_per_gate

        qs = [quantize_per_gate(U[g]) for g in range(G)]
        U = jnp.stack([q for q, _ in qs])
        u_scales = jnp.stack([s for _, s in qs])
    elif cand.precision == "bf16":
        from repro.kernels.quant import bf16_roundtrip

        U = bf16_roundtrip(U)
    if lstm:
        c0 = filled((G, B, H), jnp.float32)
        return lambda: lstm_seq(U, xw, h0, c0, u_scales=u_scales,
                                block_t=bt, interpret=interpret)
    return lambda: gru_seq(U, xw, h0, u_scales=u_scales, block_t=bt,
                           interpret=interpret)


def replay_candidate(cand: Candidate, *, interpret: Optional[bool] = None,
                     repeats: int = 5, warmup: int = 1) -> dict:
    """Replay one candidate: {med_us, p90_us, n} over ``repeats`` fenced
    runs (nearest-rank p90, exact at these sample sizes)."""
    fn = _operands(cand, interpret)
    ts = sorted(measure_samples(fn, repeats=repeats, warmup=warmup))
    rank = max(1, -(-len(ts) * 9 // 10))  # ceil(0.9 * n), nearest-rank
    return {"med_us": statistics.median(ts),
            "p90_us": ts[min(rank, len(ts)) - 1], "n": len(ts)}


def calibrate(cands: Iterable[Candidate], *,
              table: Optional[MeasuredCostTable] = None,
              interpret: Optional[bool] = None,
              repeats: int = 5, warmup: int = 1,
              macs: int = 16384,
              progress=None) -> MeasuredCostTable:
    """Replay every (deduped) candidate into a MeasuredCostTable bound to
    the current backend.  ``progress`` is an optional ``str -> None`` line
    sink (the CLI passes print)."""
    if table is None:
        table = MeasuredCostTable(current_backend(interpret))
    design = Design(macs=macs, schedule="unfolded")
    for cand in dedupe(cands):
        r = replay_candidate(cand, interpret=interpret, repeats=repeats,
                             warmup=warmup)
        est = analytic_shape_cycles(cand.family, cand.H, cand.G, cand.B,
                                    cand.block_t, design,
                                    chained=cand.chained,
                                    precision=cand.precision)
        table.record(cand.signature(), r["med_us"], r["p90_us"], r["n"],
                     est)
        if progress is not None:
            progress(f"  {cand.signature()}: med={r['med_us']:.1f}us "
                     f"p90={r['p90_us']:.1f}us n={r['n']} est={est:.0f}cy")
    return table


def check_table(table: MeasuredCostTable, *,
                interpret: Optional[bool] = None,
                tolerance: float = 25.0, repeats: int = 2,
                progress=None) -> Sequence[str]:
    """Re-replay every signature in the table's bound backend once and
    compare against the stored median; returns the signatures whose fresh
    measurement disagrees by more than ``tolerance``x either way (the
    `make calibrate` gate — generous by default: it exists to catch unit
    and lowering errors, not scheduler jitter)."""
    from repro.calib.table import parse_signature

    bad = []
    for sig in table.signatures():
        f = parse_signature(sig)
        if f is None:
            continue
        cand = Candidate(family=f["family"], H=f["H"], G=f["G"], B=f["B"],
                         block_t=f["chunk_len"], dtype=f["dtype"],
                         dirs=tuple(f["dirs"].split("+")),
                         chained=f["chained"], precision=f["precision"])
        fresh = replay_candidate(cand, interpret=interpret,
                                 repeats=repeats)["med_us"]
        stored = table.lookup(sig)["med_us"]
        ratio = max(fresh, stored) / max(min(fresh, stored), 1e-9)
        line = f"  {sig}: stored={stored:.1f}us fresh={fresh:.1f}us " \
               f"ratio={ratio:.2f}x"
        if progress is not None:
            progress(line)
        if ratio > tolerance:
            bad.append(sig)
    return bad
