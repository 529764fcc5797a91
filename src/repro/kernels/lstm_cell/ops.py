"""Jitted public wrappers for the fused LSTM kernels (cell + sequence)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.autotune import table
from repro.kernels.common import default_interpret, ragged_b_mask, round_up
from repro.kernels.lstm_cell.kernel import (lstm_cell_pallas,
                                            lstm_decode_pallas,
                                            lstm_seq_pallas)
from repro.kernels.lstm_cell.ref import lstm_cell_ref, lstm_seq_ref


@functools.partial(jax.jit, static_argnames=("block_h", "block_k", "interpret"))
def lstm_cell(U4, xw_t, h_prev, c_prev, *, block_h: int = 0, block_k: int = 0,
              interpret: bool | None = None):
    """Fused recurrent LSTM step.  U4 (H,4,H); xw_t (B,4,H) precomputed
    input half; h (B,H); c (B,H) fp32 -> (h, c)."""
    H = U4.shape[0]
    if not block_h or not block_k:
        bk, bh = table().block(H, H, vmem_budget=2 * 2**20)
        block_h = block_h or min(bh, H)
        block_k = block_k or min(bk, H)
    if interpret is None:
        interpret = default_interpret()
    return lstm_cell_pallas(U4, xw_t, h_prev, c_prev, block_h=block_h,
                            block_k=block_k, interpret=interpret)


def as_cell_kernel(interpret: bool | None = None):
    """Adapter for core.schedules.run_layer_unfolded(cell_kernel=...).

    Schedules store U as (H, 4H) gate-major; the kernel wants (H, 4, H)."""

    def cell(U, xw_t, h, c):
        H = U.shape[0]
        U4 = U.reshape(H, 4, H)
        xw4 = xw_t.reshape(xw_t.shape[0], 4, H)
        return lstm_cell(U4, xw4, h, c, interpret=interpret)

    return cell


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def lstm_seq(U4, xw, h0=None, c0=None, *, b_valid=None, u_scales=None,
             u_rows=None, block_t: int = 0, interpret: bool | None = None):
    """Sequence-fused recurrence: ONE pallas_call for the whole T walk.

    U4 (H,4,H) or, for a batch of G independent cells, (G,H,4,H); xw
    (B,T,4,H) / (G,B,T,4,H) precomputed input half; h0/c0 optional (…B,H)
    initial state (each defaults to zeros when omitted, independently).
    Returns (hs, h_T, c_T); ``hs`` is (…B,T,H).  ``block_t`` (the streamed
    T-stripe) defaults to the autotune table's choice within the VMEM
    capacity (``core.tiling.select_time_block``).

    ``b_valid`` (stacked form only): (G,) int array of valid batch rows per
    cell when ragged-B cells were padded to a common B — rows >= b_valid[g]
    are exact no-ops (state passes through), so valid rows' t=T state is
    bit-exact regardless of padding.

    Time-reversed walks (the bwd half of a bidirectional layer) use
    pre-launch reversal: feed ``jnp.flip(xw, time_axis)`` and flip ``hs``
    back — the kernel walks whatever order the stripe carries, its T-edge
    mask only ever pads *beyond* T, so the reversed walk is exact for any
    T, ragged remainder chunks included, and ``h_T``/``c_T`` are then the
    state after the t=0 step (the end of the reversed walk).  The dispatch
    executor flips per cell, so one G-batched launch can mix fwd and bwd
    cells (tests/kernels/test_seq_reversed.py property-tests the
    contract).

    ``u_scales`` (…4) f32 marks U4 as int8 per-gate quantized payload;
    ``u_rows`` (…Ha) int32 marks U4 as row-compacted (block-sparse) —
    see kernels.quant for both transforms and their exactness story."""
    stacked = xw.ndim == 5
    if not stacked:
        if b_valid is not None:
            raise ValueError("b_valid requires the stacked (G, ...) form")
        U4, xw = U4[None], xw[None]
        if h0 is not None:
            h0 = h0[None]
        if c0 is not None:
            c0 = c0[None]
        if u_scales is not None:
            u_scales = u_scales[None]
        if u_rows is not None:
            u_rows = u_rows[None]
    G, B, T, _, H = xw.shape
    if h0 is None:
        h0 = jnp.zeros((G, B, H), xw.dtype)
    if c0 is None:
        c0 = jnp.zeros((G, B, H), jnp.float32)
    if T == 0:  # degenerate empty sequence: state passes through
        hs = jnp.zeros((G, B, 0, H), h0.dtype)
        return (hs, h0, c0.astype(jnp.float32)) if stacked else \
            (hs[0], h0[0], c0[0].astype(jnp.float32))
    if not block_t:
        precision = "int8" if u_scales is not None else "fp32"
        dens = 1.0 if u_rows is None else u_rows.shape[-1] / H
        block_t = table().seq_block(T, B, H, precision=precision,
                                    density=dens)
    if interpret is None:
        interpret = default_interpret()
    b_mask = None if b_valid is None else ragged_b_mask(G, B, b_valid)
    hs, h_n, c_n = lstm_seq_pallas(U4, xw, h0, c0, block_t=block_t,
                                   interpret=interpret, b_mask=b_mask,
                                   u_scales=u_scales, u_rows=u_rows)
    if not stacked:
        hs, h_n, c_n = hs[0], h_n[0], c_n[0]
    return hs, h_n, c_n


@functools.partial(jax.jit, static_argnames=("interpret",))
def lstm_decode(xw0, Ws, bs, Us, h0, c0, *, interpret: bool | None = None):
    """One T=1 decode tick through a whole L-layer stack in ONE launch.

    The L layer cells of a decode tick are serially dependent, so they
    cannot wavefront — but they CAN share a single kernel launch: the grid
    walks layers in order and the inter-layer value chains through VMEM
    scratch (ROADMAP: "a T=1 wavefront over layers is a single slot").

    xw0 (B,4,H) hoisted layer-0 input half; Ws (L,H,4,H) (entry 0 unused,
    so layer 0's input width may differ from H); bs (L,4,H); Us (L,H,4,H);
    h0/c0 (L,B,H).  Returns (h_n (L,B,H), c_n (L,B,H) fp32); the top-layer
    feedback frame is ``h_n[-1]`` and each layer's new h IS its T=1 output.
    Bit-identical to L per-layer ``lstm_seq(..., T=1)`` launches whenever
    the hoisted input GEMM promotes to f32 (f32 weights with any
    activation dtype, or f32 activations with any weight dtype); fully-
    bf16 stacks agree to one bf16 ulp per deeper layer under interpret
    mode, which emulates in-kernel bf16 dots in f32."""
    if interpret is None:
        interpret = default_interpret()
    return lstm_decode_pallas(xw0, Ws, bs, Us, h0, c0, interpret=interpret)


def as_seq_kernel(interpret: bool | None = None, block_t: int = 0):
    """Adapter for core.schedules.run_layer_fused / core.unfolded.unfold.

    Schedules store U as (H, 4H) gate-major and the hoisted input half as
    (B, T, 4H); the kernel wants the gate axis unpacked to (4, H)."""

    def seq(U, xw, h0=None, c0=None):
        H = U.shape[0]
        B, T = xw.shape[0], xw.shape[1]
        return lstm_seq(U.reshape(H, 4, H), xw.reshape(B, T, 4, H), h0, c0,
                        block_t=block_t, interpret=interpret)

    return seq


__all__ = ["lstm_cell", "lstm_cell_ref", "as_cell_kernel",
           "lstm_seq", "lstm_seq_ref", "as_seq_kernel", "lstm_decode"]
