"""Sequence-fused GRU recurrence as a Pallas TPU kernel.

The LSTM sequence kernel's T-stripe pattern (kernels.lstm_cell), ported to
the GRU cell: the time loop lives inside ONE ``pallas_call``, the hidden
state is VMEM-resident across the whole T walk, the precomputed input half
streams in T-block stripes via the BlockSpec index map, and a leading grid
dimension ``g`` batches independent recurrences (distinct U per cell) so
the dispatcher can pack GRU cells into shared wavefront slots.

The GRU is the harder Unfolded case (see core/gru.py): the reset gate
couples into the candidate's recurrent term *multiplicatively*, so the
epilogue is  n = tanh(xw_n + r·(U_n h))  rather than a pure pre-activation
sum — but the dependence structure (one recurrent MVM per step, pointwise
tail) is identical, and so is the fusion win: one launch instead of T, no
per-step HBM round-trip of h.

Gate order along the 3-axis: (z, r, n), matching core.gru.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, seq_vmem_limit_of


def _seq_kernel(*refs, block_t: int, T: int, masked: bool,
                quant: bool = False, sparse: bool = False):
    """One grid step = one T-block of one recurrence ``g``.

    Grid is (G, n_t) with t innermost; h persists in VMEM scratch across
    the t walk and is re-seeded from h0 at each cell's first block.  The
    operand layout is the LSTM twin's (kernels.lstm_cell.kernel): gates
    flattened into the lanes (U (Hr, 3H), xw stripe (bt, B, 3H)
    time-major), each gate a static lane slice, the step's h collected in
    the f32 ``ys_scr`` stripe and written to ``hs_ref`` once per block.

    ``masked``: a per-row validity mask (ragged-B packing) rides along as
    an extra (B, 1) input; padded rows freeze their state exactly like the
    T-edge mask, so they are exact no-ops.

    ``quant`` / ``sparse``: the int8 per-gate and row-compacted U paths —
    see the LSTM twin in kernels.lstm_cell.kernel.  The GRU subtlety: the
    per-gate scale must multiply the full (B, 3H) recurrent accumulate
    BEFORE the reset gate couples ``r * hu_n`` into the candidate, so the
    dequantized value the gates see matches the oracle's ``h @ (Uq * s)``
    up to dot/scale distributivity.
    """
    refs = list(refs)
    xw_ref, u_ref = refs[:2]
    pos = 2
    s_ref = rows_ref = m_ref = None
    if quant:
        s_ref, pos = refs[pos], pos + 1
    if sparse:
        rows_ref, pos = refs[pos], pos + 1
    h0_ref = refs[pos]
    pos += 1
    if masked:
        m_ref, pos = refs[pos], pos + 1
    hs_ref, hn_ref, h_scr, ys_scr = refs[pos:]
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _seed():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    # (Hr, 3H) — resident across the walk; upcast ONCE per grid step
    U = u_ref[0].astype(jnp.float32)
    H = hs_ref.shape[-1]
    base = t * block_t
    row_ok = None if m_ref is None else m_ref[0] != 0        # (B, 1)

    def step(i, h):
        h_in = h if not sparse else jnp.take(h, rows_ref[0, 0], axis=1)
        hu = jax.lax.dot_general(
            h_in, U, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (B, 3H)
        if quant:
            hu = hu * s_ref[0]
        xw32 = xw_ref[0, i].astype(jnp.float32)
        z = jax.nn.sigmoid(xw32[:, 0 * H:1 * H] + hu[:, 0 * H:1 * H])
        r = jax.nn.sigmoid(xw32[:, 1 * H:2 * H] + hu[:, 1 * H:2 * H])
        n = jnp.tanh(xw32[:, 2 * H:3 * H] + r * hu[:, 2 * H:3 * H])
        h_new = (1 - z) * n + z * h
        # T-edge mask: the last block's tail reads BlockSpec padding
        # (undefined, NaN under interpret) — freeze the state there
        valid = base + i < T
        if row_ok is not None:
            valid = jnp.logical_and(valid, row_ok)
        h = jnp.where(valid, h_new, h)
        ys_scr[i] = h
        return h

    h = jax.lax.fori_loop(0, block_t, step, h_scr[...])
    h_scr[...] = h
    hs_ref[0] = ys_scr[...].astype(hs_ref.dtype)
    hn_ref[0] = h.astype(hn_ref.dtype)


def gru_seq_pallas(U3, xw, h0, *, block_t: int, interpret: bool = True,
                   b_mask=None, u_scales=None, u_rows=None):
    """Sequence-fused GRU recurrence — ONE kernel launch for all T steps.

    U3 (G,Hr,3,H); xw (G,B,T,3,H) precomputed input half (+bias);
    h0 (G,B,H).  Returns (hs (G,B,T,H), h_T (G,B,H)).  ``G`` batches
    independent recurrences (e.g. the GRU cells of one wavefront slot);
    pass G=1 for a single layer.  ``b_mask`` (G,B) int32 marks valid batch
    rows under ragged-B packing: zero rows are exact no-ops.

    ``u_scales`` (G,3) f32: U3 is int8 per-gate quantized; ``u_rows``
    (G,Ha) int32: U3 is row-compacted to (G,Ha,3,H) (see kernels.quant).
    Operands are relaid out for the TPU tiling as in ``lstm_seq_pallas``.
    """
    G, B, T, _, H = xw.shape
    Hr = U3.shape[1]
    bt = max(1, min(block_t, T))
    n_t = cdiv(T, bt)

    masked = b_mask is not None
    quant = u_scales is not None
    sparse = u_rows is not None
    kernel = functools.partial(_seq_kernel, block_t=bt, T=T, masked=masked,
                               quant=quant, sparse=sparse)
    xw_tm = jnp.swapaxes(xw.reshape(G, B, T, 3 * H), 1, 2)   # (G,T,B,3H)
    in_specs = [
        pl.BlockSpec((1, bt, B, 3 * H), lambda g, t: (g, t, 0, 0)),  # xw
        pl.BlockSpec((1, Hr, 3 * H), lambda g, t: (g, 0, 0)),        # U
    ]
    args = (xw_tm, U3.reshape(G, Hr, 3 * H))
    if quant:
        in_specs.append(pl.BlockSpec((1, 1, 3 * H), lambda g, t: (g, 0, 0)))
        args += (jnp.repeat(u_scales, H, axis=-1)[:, None],)     # scales
    if sparse:
        Ha = u_rows.shape[1]
        in_specs.append(pl.BlockSpec((1, 1, Ha), lambda g, t: (g, 0, 0)))
        args += (u_rows[:, None],)                               # rows
    in_specs.append(pl.BlockSpec((1, B, H), lambda g, t: (g, 0, 0)))   # h0
    args += (h0,)
    if masked:
        in_specs.append(pl.BlockSpec((1, B, 1), lambda g, t: (g, 0, 0)))
        args += (b_mask[:, :, None],)                            # mask
    hs, h_n = pl.pallas_call(
        kernel,
        grid=(G, n_t),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bt, B, H), lambda g, t: (g, t, 0, 0)),    # hs
            pl.BlockSpec((1, B, H), lambda g, t: (g, 0, 0)),           # h_T
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, T, B, H), h0.dtype),
            jax.ShapeDtypeStruct((G, B, H), h0.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),       # h — resident across t
            pltpu.VMEM((bt, B, H), jnp.float32),   # this block's h stripe
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=seq_vmem_limit_of(
                U3, xw, h0, bt, quant=quant, sparse=sparse, masked=masked)),
        interpret=interpret,
    )(*args)
    return jnp.swapaxes(hs, 1, 2), h_n


# ===========================================================================
# chained decode kernel: a whole T=1 stack tick inside ONE pallas_call
# ===========================================================================


def _decode_kernel(xw0_ref, w_ref, b_ref, u_ref, h0_ref, hn_ref, y_scr,
                   xw_scr, *, out_dtype, xw_dtype):
    """One grid step = one layer of a T=1 GRU decode tick (see the LSTM
    twin in kernels.lstm_cell.kernel for the full story): the layer chain
    serializes through ``y_scr``, layer 0 uses the pre-hoisted ``xw0``
    (its in-kernel input GEMM pl.when-guarded away), deeper layers compute
    their input GEMM in-kernel — one launch per tick instead of L.  Gates
    are flattened into the lanes ((B, 3H), weights (H, 3H))."""
    l = pl.program_id(0)
    H = hn_ref.shape[-1]

    @pl.when(l == 0)
    def _first():
        xw_scr[...] = xw0_ref[...].astype(jnp.float32)

    @pl.when(l > 0)
    def _deeper():
        # round GEMM + bias through the per-layer hoist's result dtype
        # (``xw_dtype``) — see the LSTM twin for why this keeps
        # low-precision weight stacks bit-identical too
        xw = jax.lax.dot_general(
            y_scr[...], w_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(xw_dtype)
        xw_scr[...] = (xw + b_ref[0].astype(xw_dtype)).astype(jnp.float32)

    xw = xw_scr[...]
    h0 = h0_ref[0].astype(jnp.float32)
    hu = jax.lax.dot_general(
        h0, u_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    z = jax.nn.sigmoid(xw[:, 0 * H:1 * H] + hu[:, 0 * H:1 * H])
    r = jax.nn.sigmoid(xw[:, 1 * H:2 * H] + hu[:, 1 * H:2 * H])
    n = jnp.tanh(xw[:, 2 * H:3 * H] + r * hu[:, 2 * H:3 * H])
    h = (1 - z) * n + z * h0
    y_scr[...] = h.astype(out_dtype).astype(jnp.float32)
    hn_ref[0] = h.astype(hn_ref.dtype)


def gru_decode_pallas(xw0, Ws, bs, Us, h0, *, interpret: bool = True):
    """One T=1 decode tick through an L-layer GRU stack — ONE launch.

    xw0 (B,3,H) hoisted layer-0 input half (+bias); Ws (L,H,3,H) (entry 0
    unused); bs (L,3,H); Us (L,H,3,H); h0 (L,B,H).  Returns h_n (L,B,H);
    the top-layer feedback frame is ``h_n[-1]``.
    """
    L, B, H = h0.shape
    kernel = functools.partial(
        _decode_kernel, out_dtype=h0.dtype,
        xw_dtype=jnp.promote_types(h0.dtype, Ws.dtype))
    (h_n,) = pl.pallas_call(
        kernel,
        grid=(L,),
        in_specs=[
            pl.BlockSpec((B, 3 * H), lambda l: (0, 0)),          # xw0
            pl.BlockSpec((1, H, 3 * H), lambda l: (l, 0, 0)),    # Ws
            pl.BlockSpec((1, 1, 3 * H), lambda l: (l, 0, 0)),    # bs
            pl.BlockSpec((1, H, 3 * H), lambda l: (l, 0, 0)),    # Us
            pl.BlockSpec((1, B, H), lambda l: (l, 0, 0)),        # h0
        ],
        out_specs=[
            pl.BlockSpec((1, B, H), lambda l: (l, 0, 0)),        # h_n
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, B, H), h0.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),       # y — the layer chain's wire
            pltpu.VMEM((B, 3 * H), jnp.float32),   # xw — this layer's input half
        ],
        interpret=interpret,
    )(xw0.reshape(B, 3 * H), Ws.reshape(L, H, 3 * H),
      bs.reshape(L, 1, 3 * H), Us.reshape(L, H, 3 * H), h0)
    return h_n
