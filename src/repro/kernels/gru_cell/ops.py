"""Jitted public wrapper for the sequence-fused GRU kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.autotune import table
from repro.kernels.common import default_interpret, ragged_b_mask
from repro.kernels.gru_cell.kernel import gru_decode_pallas, gru_seq_pallas
from repro.kernels.gru_cell.ref import gru_seq_ref, gru_step_ref


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def gru_seq(U3, xw, h0=None, *, b_valid=None, u_scales=None, u_rows=None,
            block_t: int = 0, interpret: bool | None = None):
    """Sequence-fused GRU recurrence: ONE pallas_call for the whole T walk.

    U3 (H,3,H) or, for a batch of G independent cells, (G,H,3,H); xw
    (B,T,3,H) / (G,B,T,3,H) precomputed input half; h0 optional (…B,H)
    initial state (zeros when omitted).  Returns (hs, h_T); ``hs`` is
    (…B,T,H).  ``block_t`` (the streamed T-stripe) defaults to the autotune
    table's choice within the VMEM capacity (gates=3).

    ``b_valid`` (stacked form only): (G,) int array of valid batch rows per
    cell under ragged-B packing — rows >= b_valid[g] are exact no-ops.

    Time-reversed walks (the bwd half of a bidirectional layer) use
    pre-launch reversal exactly like ``lstm_seq``: flip the xw stripe on
    the time axis and flip ``hs`` back — exact for any T (the T-edge mask
    only pads beyond T), with ``h_T`` then the state after the t=0 step
    (see kernels.lstm_cell.lstm_seq and
    tests/kernels/test_seq_reversed.py).

    ``u_scales`` (…3) f32 marks U3 as int8 per-gate quantized payload;
    ``u_rows`` (…Ha) int32 marks U3 as row-compacted (block-sparse) —
    see kernels.quant for both transforms and their exactness story."""
    stacked = xw.ndim == 5
    if not stacked:
        if b_valid is not None:
            raise ValueError("b_valid requires the stacked (G, ...) form")
        U3, xw = U3[None], xw[None]
        if h0 is not None:
            h0 = h0[None]
        if u_scales is not None:
            u_scales = u_scales[None]
        if u_rows is not None:
            u_rows = u_rows[None]
    G, B, T, _, H = xw.shape
    if h0 is None:
        h0 = jnp.zeros((G, B, H), xw.dtype)
    if T == 0:  # degenerate empty sequence: state passes through
        hs = jnp.zeros((G, B, 0, H), h0.dtype)
        return (hs, h0) if stacked else (hs[0], h0[0])
    if not block_t:
        precision = "int8" if u_scales is not None else "fp32"
        dens = 1.0 if u_rows is None else u_rows.shape[-1] / H
        block_t = table().seq_block(T, B, H, gates=3, precision=precision,
                                    density=dens)
    if interpret is None:
        interpret = default_interpret()
    b_mask = None if b_valid is None else ragged_b_mask(G, B, b_valid)
    hs, h_n = gru_seq_pallas(U3, xw, h0, block_t=block_t, interpret=interpret,
                             b_mask=b_mask, u_scales=u_scales, u_rows=u_rows)
    if not stacked:
        hs, h_n = hs[0], h_n[0]
    return hs, h_n


@functools.partial(jax.jit, static_argnames=("interpret",))
def gru_decode(xw0, Ws, bs, Us, h0, *, interpret: bool | None = None):
    """One T=1 decode tick through a whole L-layer GRU stack in ONE launch
    (the lstm_decode pattern on the 3-gate cell — see kernels.lstm_cell).

    xw0 (B,3,H) hoisted layer-0 input half; Ws (L,H,3,H) (entry 0 unused);
    bs (L,3,H); Us (L,H,3,H); h0 (L,B,H).  Returns h_n (L,B,H); the
    top-layer feedback frame is ``h_n[-1]``.  Bit-identical to L per-layer
    ``gru_seq(..., T=1)`` launches whenever the hoisted input GEMM
    promotes to f32 (see kernels.lstm_cell.lstm_decode for the fully-bf16
    caveat)."""
    if interpret is None:
        interpret = default_interpret()
    return gru_decode_pallas(xw0, Ws, bs, Us, h0, interpret=interpret)


__all__ = ["gru_seq", "gru_seq_ref", "gru_step_ref", "gru_decode"]
