"""Fused LSTM recurrence as Pallas TPU kernels.

Two granularities live here:

``lstm_cell_pallas`` — ONE recurrent step.  SHARP's three pipeline stages
(Compute Unit -> A-MFU -> Cell Updater) collapse into one VMEM-resident
kernel: the recurrent MVM U·h accumulates in a VMEM scratch tile, and on the
last reduction step the gate activations and the cell/hidden update run as
the epilogue on the same tile — the TPU analogue of SHARP's "output-based
tiling" (no HBM round-trip between the MVM, activation and update stages).
Grid: (j over H output columns, k over H reduction rows); k innermost so the
accumulator tile is revisited.  Block shapes come from the autotune table
(core.tiling.select_block_shape), mirroring the paper's per-model K-width.

``lstm_seq_pallas`` — the WHOLE sequence.  The per-step kernel still pays T
kernel launches and T HBM round-trips of (h, c) when driven by ``lax.scan``;
SHARP's point (§5, Fig. 8.d) is that the recurrent state should stay
resident while timesteps stream through the datapath.  Here the time loop
moves *inside* a single ``pallas_call``: the grid's innermost dimension
walks T-blocks, the precomputed input half ``xw[:, t]`` streams in stripe by
stripe via the BlockSpec index map, and (h, c) live in VMEM scratch that
persists across grid steps — state never touches HBM between timesteps.  A
leading grid dimension ``g`` batches independent recurrences (distinct U per
cell), which is what the wavefront multi-layer schedule packs an
anti-diagonal of (layer, time-chunk) cells into.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (cdiv, mxu_bf16_operands,
                                  seq_vmem_limit_of)


def _kernel(h_ref, u_ref, xw_ref, c_ref, h_out_ref, c_out_ref, acc_ref, *,
            n_k: int, H: int, bk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- Compute Unit: one reduction stripe of U·h ----------------------
    h_blk = h_ref[...]  # (B, bk)
    u_blk = u_ref[...]  # (bk, 4, bh)
    # mask the reduction tail (matrix edge -> SHARP's padding handling);
    # both operands, since out-of-bounds pads are undefined (NaN in interpret)
    base = k * bk
    idx = base + jax.lax.broadcasted_iota(jnp.int32, h_blk.shape, 1)
    h_blk = jnp.where(idx < H, h_blk, 0).astype(h_blk.dtype)
    ridx = base + jax.lax.broadcasted_iota(jnp.int32, u_blk.shape, 0)
    u_blk = jnp.where(ridx < H, u_blk, 0).astype(u_blk.dtype)
    acc_ref[...] += jax.lax.dot_general(
        h_blk, u_blk.reshape(u_blk.shape[0], -1),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(acc_ref.shape)

    # ---- A-MFU + Cell Updater epilogue on the last stripe ---------------
    @pl.when(k == n_k - 1)
    def _epilogue():
        gates = acc_ref[...] + xw_ref[...].astype(jnp.float32)  # (B, 4, bh)
        i = jax.nn.sigmoid(gates[:, 0])
        f = jax.nn.sigmoid(gates[:, 1])
        g = jnp.tanh(gates[:, 2])
        o = jax.nn.sigmoid(gates[:, 3])
        c = f * c_ref[...].astype(jnp.float32) + i * g
        c_out_ref[...] = c
        h_out_ref[...] = (o * jnp.tanh(c)).astype(h_out_ref.dtype)


def lstm_cell_pallas(U4, xw_t, h_prev, c_prev, *, block_h: int, block_k: int,
                     interpret: bool = True):
    """U4 (H,4,H); xw_t (B,4,H); h_prev (B,H); c_prev (B,H) fp32."""
    H = U4.shape[0]
    B = h_prev.shape[0]
    n_j = cdiv(H, block_h)
    n_k = cdiv(H, block_k)

    kernel = functools.partial(_kernel, n_k=n_k, H=H, bk=block_k)
    h_out, c_out = pl.pallas_call(
        kernel,
        grid=(n_j, n_k),
        in_specs=[
            pl.BlockSpec((B, block_k), lambda j, k: (0, k)),          # h_prev
            pl.BlockSpec((block_k, 4, block_h), lambda j, k: (k, 0, j)),  # U4
            pl.BlockSpec((B, 4, block_h), lambda j, k: (0, 0, j)),    # xw_t
            pl.BlockSpec((B, block_h), lambda j, k: (0, j)),          # c_prev
        ],
        out_specs=[
            pl.BlockSpec((B, block_h), lambda j, k: (0, j)),          # h
            pl.BlockSpec((B, block_h), lambda j, k: (0, j)),          # c
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H), h_prev.dtype),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((B, 4, block_h), jnp.float32)],
        interpret=interpret,
    )(h_prev, U4, xw_t, c_prev)
    return h_out, c_out


# ===========================================================================
# sequence-fused kernel: time loop inside ONE pallas_call
# ===========================================================================


def _seq_kernel(*refs, block_t: int, T: int, masked: bool,
                quant: bool = False, sparse: bool = False,
                bf16_dot: bool = False):
    """One grid step = one T-block of one recurrence ``g``.

    Grid is (G, n_t) with t innermost; (h, c) persist in VMEM scratch across
    the t walk and are re-seeded from (h0, c0) at each cell's first block.

    Layout (see ``lstm_seq_pallas``): the gate axis is flattened into the
    lanes — U (Hr, 4H), the xw stripe (bt, B, 4H) time-major — so a step
    reads its (B, 4H) input row by a leading-axis ref index and every gate
    is a static lane slice ``[k·H, (k+1)·H)``; no in-kernel reshape.  The
    step's h goes to the f32 ``ys_scr`` (bt, B, H) stripe, written to
    ``hs_ref`` once per grid step in the output dtype.

    ``masked``: a per-row validity mask (ragged-B packing — cells of
    different batch widths padded to a common B) rides along as an extra
    (B, 1) input; padded rows freeze their state exactly like the T-edge
    mask, so they are exact no-ops and h_T/c_T of valid rows are bit-exact.

    ``quant``: U arrives int8 with a per-lane (1, 4H) scale operand (each
    gate's scale repeated over its H lanes); the int8 payload streams in
    with 4x fewer HBM bytes, but the dot reads its f32 upcast, so it holds
    fp32's VMEM (core.tiling.seq_block_footprint, D8).  The dot
    accumulates in fp32 and the scale is applied to the (B, 4H) accumulate
    after it, so the only error vs the dequantized oracle is the
    distributivity of ``(h @ Uq) * s``.

    ``sparse``: U arrives row-compacted (Ha <= H input rows) with a
    (1, Ha) int32 row-index operand; h is gathered to the surviving rows
    before the dot.  Padding rows are zero U rows at index 0 — exact
    no-ops (see kernels.quant.compact_rows).

    ``bf16_dot``: U arrives bfloat16 and the dot runs as one bfloat16 MXU
    pass (``kernels.common.mxu_bf16_operands``): U feeds the MXU as it
    is and h is cast to bfloat16 before each dot — the values the MXU's
    f32 mode rounds its operands to, latched at half the width.  The
    accumulate, the gates and (h, c) stay float32.
    """
    refs = list(refs)
    xw_ref, u_ref = refs[:2]
    pos = 2
    s_ref = rows_ref = m_ref = None
    if quant:
        s_ref, pos = refs[pos], pos + 1
    if sparse:
        rows_ref, pos = refs[pos], pos + 1
    h0_ref, c0_ref = refs[pos:pos + 2]
    pos += 2
    if masked:
        m_ref, pos = refs[pos], pos + 1
    hs_ref, hn_ref, cn_ref, h_scr, c_scr, ys_scr = refs[pos:]
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _seed():
        h_scr[...] = h0_ref[0].astype(jnp.float32)
        c_scr[...] = c0_ref[0].astype(jnp.float32)

    # (Hr, 4H) — resident across the walk.  A bf16 dot reads its block
    # at each step (a U value held across the t loop costs the compiler a
    # VMEM copy of it, which the VMEM model does not count); every other
    # dot reads the f32 upcast, made ONCE per grid step outside the loop
    # (the int8 per-gate scale rides on the accumulate below)
    U = None if bf16_dot else u_ref[0].astype(jnp.float32)
    H = hs_ref.shape[-1]
    base = t * block_t
    row_ok = None if m_ref is None else m_ref[0] != 0        # (B, 1)

    def step(i, carry):
        h, c = carry
        h_in = h if not sparse else jnp.take(h, rows_ref[0, 0], axis=1)
        u = u_ref[0] if bf16_dot else U
        acc = jax.lax.dot_general(
            h_in.astype(u.dtype), u, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (B, 4H)
        if quant:
            acc = acc * s_ref[0]
        gates = xw_ref[0, i].astype(jnp.float32) + acc
        ig = jax.nn.sigmoid(gates[:, 0 * H:1 * H])
        fg = jax.nn.sigmoid(gates[:, 1 * H:2 * H])
        gg = jnp.tanh(gates[:, 2 * H:3 * H])
        og = jax.nn.sigmoid(gates[:, 3 * H:4 * H])
        # operand order matters under interpret mode: XLA:CPU contracts
        # this into an FMA, and this order keeps the contraction the same
        # for every stripe length, so a chunked walk stays bit-equal to
        # one launch (tests/kernels/test_seq_reversed.py)
        c_new = ig * gg + fg * c
        h_new = og * jnp.tanh(c_new)
        # T-edge mask: the last block's tail reads BlockSpec padding
        # (undefined, NaN under interpret) — freeze the state there
        valid = base + i < T
        if row_ok is not None:
            valid = jnp.logical_and(valid, row_ok)
        h = jnp.where(valid, h_new, h)
        c = jnp.where(valid, c_new, c)
        ys_scr[i] = h
        return h, c

    h, c = jax.lax.fori_loop(0, block_t, step, (h_scr[...], c_scr[...]))
    h_scr[...] = h
    c_scr[...] = c
    hs_ref[0] = ys_scr[...].astype(hs_ref.dtype)
    hn_ref[0] = h.astype(hn_ref.dtype)
    cn_ref[0] = c


def lstm_seq_pallas(U4, xw, h0, c0, *, block_t: int, interpret: bool = True,
                    b_mask=None, u_scales=None, u_rows=None):
    """Sequence-fused LSTM recurrence — ONE kernel launch for all T steps.

    U4 (G,Hr,4,H); xw (G,B,T,4,H) precomputed input half (+bias);
    h0 (G,B,H); c0 (G,B,H).  Returns (hs (G,B,T,H), h_T (G,B,H),
    c_T (G,B,H)).  ``G`` batches independent recurrences (e.g. the cells of
    one wavefront slot); pass G=1 for a single layer.  ``b_mask`` (G,B)
    int32 marks valid batch rows when cells of different B were padded to a
    common width (ragged-B packing): zero rows are exact no-ops.

    ``u_scales`` (G,4) f32: U4 is int8 per-gate quantized; fp32
    accumulate, scale applied post-dot (see kernels.quant).  A bfloat16
    U4 feeds the MXU bfloat16 operands where the dot runs as one
    bfloat16 pass (``kernels.common.mxu_bf16_operands``); elsewhere the
    kernel upcasts it, as it does every other weight dtype.  ``u_rows``
    (G,Ha) int32: U4 is row-compacted to (G,Ha,4,H) — the kernel gathers
    h to the surviving rows (block-sparse row tiles).

    The operands are relaid out here for the TPU's (8, 128) tiling: the
    gate axis flattens into the lanes (free, contiguous), xw/hs go
    time-major so a step indexes a leading axis, and the per-cell side
    operands get a unit second-minor axis ((G,1,·) / (G,B,1) blocks).
    """
    G, B, T, _, H = xw.shape
    Hr = U4.shape[1]
    bt = max(1, min(block_t, T))
    n_t = cdiv(T, bt)

    masked = b_mask is not None
    quant = u_scales is not None
    sparse = u_rows is not None
    bf16_dot = U4.dtype == jnp.bfloat16 and mxu_bf16_operands(interpret)
    kernel = functools.partial(_seq_kernel, block_t=bt, T=T, masked=masked,
                               quant=quant, sparse=sparse, bf16_dot=bf16_dot)
    xw_tm = jnp.swapaxes(xw.reshape(G, B, T, 4 * H), 1, 2)   # (G,T,B,4H)
    in_specs = [
        pl.BlockSpec((1, bt, B, 4 * H), lambda g, t: (g, t, 0, 0)),  # xw
        pl.BlockSpec((1, Hr, 4 * H), lambda g, t: (g, 0, 0)),        # U
    ]
    args = (xw_tm, U4.reshape(G, Hr, 4 * H))
    if quant:
        in_specs.append(pl.BlockSpec((1, 1, 4 * H), lambda g, t: (g, 0, 0)))
        args += (jnp.repeat(u_scales, H, axis=-1)[:, None],)     # scales
    if sparse:
        Ha = u_rows.shape[1]
        in_specs.append(pl.BlockSpec((1, 1, Ha), lambda g, t: (g, 0, 0)))
        args += (u_rows[:, None],)                               # rows
    in_specs += [
        pl.BlockSpec((1, B, H), lambda g, t: (g, 0, 0)),               # h0
        pl.BlockSpec((1, B, H), lambda g, t: (g, 0, 0)),               # c0
    ]
    args += (h0, c0)
    if masked:
        in_specs.append(pl.BlockSpec((1, B, 1), lambda g, t: (g, 0, 0)))
        args += (b_mask[:, :, None],)                            # mask
    hs, h_n, c_n = pl.pallas_call(
        kernel,
        grid=(G, n_t),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bt, B, H), lambda g, t: (g, t, 0, 0)),    # hs
            pl.BlockSpec((1, B, H), lambda g, t: (g, 0, 0)),           # h_T
            pl.BlockSpec((1, B, H), lambda g, t: (g, 0, 0)),           # c_T
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, T, B, H), h0.dtype),
            jax.ShapeDtypeStruct((G, B, H), h0.dtype),
            jax.ShapeDtypeStruct((G, B, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),       # h — resident across t
            pltpu.VMEM((B, H), jnp.float32),       # c — resident across t
            pltpu.VMEM((bt, B, H), jnp.float32),   # this block's h stripe
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=seq_vmem_limit_of(
                U4, xw, h0, bt, quant=quant, sparse=sparse, masked=masked)),
        interpret=interpret,
    )(*args)
    return jnp.swapaxes(hs, 1, 2), h_n, c_n


# ===========================================================================
# chained decode kernel: a whole T=1 stack tick inside ONE pallas_call
# ===========================================================================


def _decode_kernel(xw0_ref, w_ref, b_ref, u_ref, h0_ref, c0_ref,
                   hn_ref, cn_ref, y_scr, xw_scr, *, out_dtype, xw_dtype):
    """One grid step = one layer of a T=1 decode tick.

    Grid is (L,).  The layer cells of a decode tick are serially dependent
    (layer l eats layer l-1's output *at the same timestep*), so no
    wavefront exists — but the TPU grid walks its steps in order, which is
    exactly a dependence-respecting schedule: the inter-layer value flows
    through ``y_scr`` (VMEM scratch), the same persistence trick the
    sequence kernels use for (h, c) across t-blocks.  Layer 0 uses the
    hoisted input half ``xw0`` (its input exists before launch; the in-
    kernel input GEMM is pl.when-guarded so layer 0 pays no dead MXU
    work); deeper layers compute their input GEMM *in-kernel* from
    y_scr — one launch per tick instead of L.  Gates are flattened into
    the lanes ((B, 4H), weights (H, 4H)) exactly as in ``_seq_kernel``.

    The inter-layer value is rounded through ``out_dtype`` and the input
    GEMM through ``xw_dtype`` (the hoist's promotion dtype) before use, so
    a chained tick reproduces the per-layer launches' rounding points —
    bit-identical whenever the hoist promotes to f32 (see lstm_decode).
    """
    l = pl.program_id(0)
    H = hn_ref.shape[-1]

    @pl.when(l == 0)
    def _first():
        xw_scr[...] = xw0_ref[...].astype(jnp.float32)

    @pl.when(l > 0)
    def _deeper():
        # round GEMM + bias through the per-layer hoist's result dtype
        # (``xw_dtype``: einsum promotes activations x weights, then the
        # seq kernel casts to f32) — this keeps a chained tick
        # bit-identical for low-precision weight stacks too, not just f32
        # params
        xw = jax.lax.dot_general(
            y_scr[...], w_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(xw_dtype)
        xw_scr[...] = (xw + b_ref[0].astype(xw_dtype)).astype(jnp.float32)

    gates = xw_scr[...] + jax.lax.dot_general(
        h0_ref[0].astype(jnp.float32), u_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    i = jax.nn.sigmoid(gates[:, 0 * H:1 * H])
    f = jax.nn.sigmoid(gates[:, 1 * H:2 * H])
    g = jnp.tanh(gates[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(gates[:, 3 * H:4 * H])
    # the operand order of _seq_kernel's c update (see there): a chained
    # tick stays bit-equal to L per-layer T=1 launches under interpret
    c = i * g + f * c0_ref[0].astype(jnp.float32)
    h = o * jnp.tanh(c)
    y_scr[...] = h.astype(out_dtype).astype(jnp.float32)
    hn_ref[0] = h.astype(hn_ref.dtype)
    cn_ref[0] = c


def lstm_decode_pallas(xw0, Ws, bs, Us, h0, c0, *, interpret: bool = True):
    """One T=1 decode tick through an L-layer LSTM stack — ONE launch.

    xw0 (B,4,H) hoisted layer-0 input half (+bias); Ws (L,H,4,H) input
    weights per layer, gate axis unpacked (entry 0 is unused — layer 0 is
    pre-hoisted, so X may differ from H); bs (L,4,H); Us (L,H,4,H);
    h0/c0 (L,B,H) the per-layer recurrent state.  Returns (h_n (L,B,H),
    c_n (L,B,H) fp32): layer l's new hidden state IS its T=1 output, so the
    top-layer feedback frame is ``h_n[-1]``.  The gate axis is flattened
    into the lanes here (free, contiguous), as for ``lstm_seq_pallas``.
    """
    L, B, H = h0.shape
    kernel = functools.partial(
        _decode_kernel, out_dtype=h0.dtype,
        xw_dtype=jnp.promote_types(h0.dtype, Ws.dtype))
    h_n, c_n = pl.pallas_call(
        kernel,
        grid=(L,),
        in_specs=[
            pl.BlockSpec((B, 4 * H), lambda l: (0, 0)),          # xw0
            pl.BlockSpec((1, H, 4 * H), lambda l: (l, 0, 0)),    # Ws
            pl.BlockSpec((1, 1, 4 * H), lambda l: (l, 0, 0)),    # bs
            pl.BlockSpec((1, H, 4 * H), lambda l: (l, 0, 0)),    # Us
            pl.BlockSpec((1, B, H), lambda l: (l, 0, 0)),        # h0
            pl.BlockSpec((1, B, H), lambda l: (l, 0, 0)),        # c0
        ],
        out_specs=[
            pl.BlockSpec((1, B, H), lambda l: (l, 0, 0)),        # h_n
            pl.BlockSpec((1, B, H), lambda l: (l, 0, 0)),        # c_n
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, B, H), h0.dtype),
            jax.ShapeDtypeStruct((L, B, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),       # y — the layer chain's wire
            pltpu.VMEM((B, 4 * H), jnp.float32),   # xw — this layer's input half
        ],
        interpret=interpret,
    )(xw0.reshape(B, 4 * H), Ws.reshape(L, H, 4 * H),
      bs.reshape(L, 1, 4 * H), Us.reshape(L, H, 4 * H), h0, c0)
    return h_n, c_n
