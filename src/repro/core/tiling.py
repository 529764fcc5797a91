"""Reconfigurable MVM tile-engine, abstracted (paper §4.2, §6).

The hardware: N vector-scalar units of width K=32 ganged row-/column-wise
(Config1..4 in Fig. 7), so a fixed MAC budget M yields tile shapes
(K rows x M/K cols) for K in {32, 64, 128, 256}.  A tile is retired per
cycle; an MVM over a (rows x cols) weight matrix costs
ceil(rows/K) * ceil(cols/(M/K)) cycles, and every ceil() is *padding waste*.

Two artifacts live here:

1. The paper-faithful cycle/padding math + per-model tile selection
   (``select_tile``) and edge reconfiguration (``cycles`` with
   ``reconfigure=True`` shrinks K at the last row stripe — §6.2.1, the
   <=1.22x of Fig. 10).

2. The TPU translation (``select_block_shape``): BlockSpec tiles for the
   Pallas kernels, minimizing the same ceil-padding waste subject to MXU
   lane alignment (8, 128) and a VMEM budget — the paper's "offline table"
   becomes a block-shape autotuner; and the sequence kernels' VMEM model
   (``seq_block_footprint``, ``select_time_block``), shared by the
   kernels' scoped limit, the planner's stripes and plancheck.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import List, Sequence, Tuple

K_CHOICES = (32, 64, 128, 256, 512)  # paper Fig. 9 exploration range


@dataclass(frozen=True)
class TileConfig:
    k: int        # VS width = tile rows
    macs: int     # total multiply-adders

    @property
    def cols(self) -> int:  # tile columns
        return max(1, self.macs // self.k)


def mvm_cycles(rows: int, cols: int, tile: TileConfig, reconfigure: bool = False) -> int:
    """Cycles to stream a (rows x cols) MVM through the tile engine.

    ``reconfigure``: at the final row stripe, the controller re-gangs the VS
    units to the largest K' <= K (power-of-two multiple of 32, or 8/16 for
    the smallest remainders) that does not overshoot the remaining rows —
    the padding reconfiguration of §6.2.1.
    """
    full_stripes, rem = divmod(rows, tile.k)
    col_passes = math.ceil(cols / tile.cols)
    cycles = full_stripes * col_passes
    if rem:
        if not reconfigure:
            cycles += col_passes
        else:
            # re-gang: bring K' as close to the remainder as the 32-wide
            # VS units allow (halving K doubles the columns)
            k2 = tile.k
            while k2 > 32 and k2 // 2 >= rem:
                k2 //= 2
            # K' halves free VS units to double the columns
            cols2 = max(1, tile.macs // k2)
            stripes2 = math.ceil(rem / k2)
            cycles += stripes2 * math.ceil(cols / cols2)
    return max(cycles, 1)


def padding_waste(rows: int, cols: int, tile: TileConfig) -> float:
    """Fraction of MAC-cycles burned on padding (fixed configuration)."""
    eff_r = math.ceil(rows / tile.k) * tile.k
    eff_c = math.ceil(cols / tile.cols) * tile.cols
    return 1.0 - (rows * cols) / (eff_r * eff_c)


def select_tile(rows: int, cols: int, macs: int,
                k_choices: Sequence[int] = K_CHOICES,
                reconfigure: bool = True) -> TileConfig:
    """The paper's offline exploration: argmin cycles over the K family."""
    best, best_cycles = None, None
    for k in k_choices:
        if k > macs:
            continue
        t = TileConfig(k=k, macs=macs)
        c = mvm_cycles(rows, cols, t, reconfigure=reconfigure)
        if best_cycles is None or c < best_cycles:
            best, best_cycles = t, c
    assert best is not None
    return best


# ---------------------------------------------------------------------------
# TPU translation: Pallas block shapes
# ---------------------------------------------------------------------------

LANE = 128     # MXU/VPU lane width (last dim)
SUBLANE = 8    # second-to-last dim granule (fp32)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def block_waste(m: int, n: int, bm: int, bn: int) -> float:
    em = math.ceil(m / bm) * bm
    en = math.ceil(n / bn) * bn
    return 1.0 - (m * n) / (em * en)


@functools.lru_cache(maxsize=None)
def select_block_shape(m: int, n: int, *, vmem_budget: int = 4 * 2**20,
                       bytes_per_el: int = 4,
                       bm_choices: Sequence[int] = (8, 16, 32, 64, 128, 256, 512),
                       bn_choices: Sequence[int] = (128, 256, 512, 1024, 2048),
                       ) -> Tuple[int, int]:
    """Choose (bm, bn) minimizing ceil-padding waste, then maximizing tile
    area (fewer grid steps), under a VMEM footprint bound — the TPU analogue
    of the paper's K-width table."""
    best = None
    for bm in bm_choices:
        if bm % SUBLANE and bm < m:
            continue
        for bn in bn_choices:
            if bm * bn * bytes_per_el > vmem_budget:
                continue
            w = block_waste(m, n, bm, bn)
            area = min(bm, _round_up(m, SUBLANE)) * min(bn, _round_up(n, LANE))
            key = (round(w, 6), -area)
            if best is None or key < best[0]:
                best = (key, (bm, bn))
    assert best is not None, (m, n)
    bm, bn = best[1]
    return min(bm, _round_up(m, SUBLANE)), min(bn, _round_up(n, LANE))


# ---------------------------------------------------------------------------
# Sequence kernels: one VMEM model for the kernels, the planner and plancheck
# ---------------------------------------------------------------------------

MiB = 2**20
#: VMEM of one TPU v5e TensorCore (one per chip): 128 MiB
#: (``jax.experimental.pallas.tpu.get_tpu_info()`` for "TPU v5 lite").
VMEM_BYTES = 128 * MiB
#: the scoped VMEM a Mosaic kernel gets on v5e when it asks for none
DEFAULT_SCOPED_VMEM = 16 * MiB
#: what a sequence kernel may request as its scoped VMEM limit: three
#: quarters of the core's VMEM, the rest left to the compiler's own use
#: of VMEM around the kernel.  Derived once; the planner budgets stripes
#: against it and the kernels request at most this much.
SEQ_VMEM_CAPACITY = VMEM_BYTES * 3 // 4
#: Mosaic's internal scratch on top of the counted buffers (AOT compiles
#: for v5e read up to 0.5 MiB above them)
SEQ_VMEM_HEADROOM = 2 * MiB


def _sublanes(itemsize: int) -> int:
    """Second-minor tile of a dtype: 8 rows of 32 bits, packed."""
    return SUBLANE * max(1, 4 // itemsize)


def _tile_bytes(rows: int, lanes: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, lanes) block in (8, 128)-tiled layout."""
    return (_round_up(rows, _sublanes(itemsize)) * _round_up(lanes, LANE)
            * itemsize)


def seq_block_footprint(bt: int, B: int, H: int, *, gates: int = 4,
                        act_bytes: int = 4, weight_bytes: int = 4,
                        precision: str = "fp32", density: float = 1.0,
                        masked: bool = False) -> int:
    """VMEM the sequence kernel (kernels.lstm_cell / kernels.gru_cell
    ``*_seq_pallas``) allocates at T-stripe ``bt``, every block padded to
    its (8, 128) tile (B on the sublanes, gates·H and H on the lanes):

    * U (Hr, gates·H), resident for a cell's whole walk and fetched again
      only when the grid's g moves to the next cell: two buffers, as the
      pipeline allocates every operand; ``weight_bytes`` per weight, and
      4 for an int8 payload, whose f32 upcast is what the kernel holds
      (D8);
    * the xw stripe (bt, B, gates·H) in and the hs stripe (bt, B, H) out,
      two buffers each (the next stripe streams while this one computes);
    * h0 and h_T (and the LSTM's c0 and c_T, float32), two buffers each;
    * scratch: h (and c) (B, H) and the step's h stripe (bt, B, H), f32;
    * the int8 scale row, the block-sparse row index and the ragged-B
      mask, two buffers each, where present.

    ``act_bytes`` is the width of the streamed activations (xw, h),
    ``density`` the share of U's rows a block-sparse launch keeps.  AOT
    compiles for v5e at H1024 B64 float32 read 2.75 MiB per frame of
    stripe, as counted here, over a fixed part that is one U (18.5 MiB)
    for the kernel compiled alone and two U, less 0.7 MiB, inside a
    plan's program; the compiler may read less where it places a whole
    operand in VMEM itself."""
    lstm = gates == 4
    lanes = gates * H
    rows = H if density >= 1.0 else max(1, int(density * H))
    u_bytes = 4 if precision == "int8" else weight_bytes
    total = 2 * _tile_bytes(rows, lanes, u_bytes)
    if precision == "int8":
        total += 2 * _tile_bytes(1, lanes, 4)
    if density < 1.0:
        total += 2 * _tile_bytes(1, rows, 4)
    if masked:
        total += 2 * _tile_bytes(B, 1, 4)
    state = _tile_bytes(B, H, act_bytes) + (_tile_bytes(B, H, 4) if lstm
                                            else 0)
    total += 4 * state                        # (h0, c0) in, (h_T, c_T) out
    total += (2 if lstm else 1) * _tile_bytes(B, H, 4)   # h, c scratch
    per_frame = (2 * _tile_bytes(B, lanes, act_bytes)    # xw stripe
                 + 2 * _tile_bytes(B, H, act_bytes)      # hs stripe
                 + _tile_bytes(B, H, 4))                 # h stripe scratch
    return total + bt * per_frame


def seq_fits(footprint: int) -> bool:
    """Whether a sequence kernel of this footprint fits the capacity."""
    return footprint + SEQ_VMEM_HEADROOM <= SEQ_VMEM_CAPACITY


def seq_vmem_limit(footprint: int) -> int:
    """The scoped VMEM limit a sequence kernel requests: its footprint
    plus Mosaic's headroom, never below the default limit (so a kernel
    that fits it compiles as it always did) nor above the capacity."""
    need = _round_up(footprint + SEQ_VMEM_HEADROOM, MiB)
    return min(SEQ_VMEM_CAPACITY, max(DEFAULT_SCOPED_VMEM, need))


@functools.lru_cache(maxsize=None)
def select_time_block(T: int, B: int, H: int, *, gates: int = 4,
                      act_bytes: int = 4, weight_bytes: int = 4,
                      precision: str = "fp32", density: float = 1.0,
                      masked: bool = False) -> int:
    """T-stripe for one sequence-kernel walk of ``T`` frames
    (kernels.lstm_cell, kernels.gru_cell): the fewest grid steps whose
    stripe fits ``SEQ_VMEM_CAPACITY``, the frames split evenly over them
    (so the T-edge pads fewer frames than there are steps).

    U stays resident for the whole walk however long it is, so any T
    runs in one launch.  Where U and a one-frame stripe do not fit
    together, no stripe does: that raises ``PlanRejected`` naming the
    width, dtype and capacity (there is no smaller stripe to fall back
    to)."""
    if T <= 0:
        return 1
    kw = dict(gates=gates, act_bytes=act_bytes, weight_bytes=weight_bytes,
              precision=precision, density=density, masked=masked)
    fixed = seq_block_footprint(0, B, H, **kw)
    per = seq_block_footprint(1, B, H, **kw) - fixed
    room = SEQ_VMEM_CAPACITY - SEQ_VMEM_HEADROOM - fixed
    if room < per:
        from repro.runtime.errors import PlanRejected
        raise PlanRejected(
            f"no sequence-kernel stripe fits: H{H} ({gates} gates, "
            f"{precision} weights of {weight_bytes} B, B{B}) needs "
            f"{(fixed + per) / MiB:.1f} MiB of VMEM with a one-frame "
            f"stripe, above the {SEQ_VMEM_CAPACITY / MiB:.0f} MiB "
            f"capacity less {SEQ_VMEM_HEADROOM / MiB:.0f} MiB headroom")
    steps = cdiv(T, min(T, room // per))
    return cdiv(T, steps)


def decode_block_footprint(B: int, H: int, *, gates: int = 4,
                           weight_bytes: int = 4) -> int:
    """VMEM of the chained decode kernel (``*_decode_pallas``), whose grid
    walks layers: one layer's W and U blocks (H, gates·H) and bias, two
    buffers each (the next layer's stream in), the hoisted xw0 row, each
    layer's h (and c) in and out, two buffers each, and the (B, H) and
    (B, gates·H) scratch.  The decode kernels request no limit of their
    own, so this has to fit ``DEFAULT_SCOPED_VMEM``."""
    lanes = gates * H
    weights = 2 * (2 * _tile_bytes(H, lanes, weight_bytes)
                   + _tile_bytes(1, lanes, weight_bytes))
    state = (2 if gates == 4 else 1) * _tile_bytes(B, H, 4)
    return (weights + 2 * _tile_bytes(B, lanes, 4) + 4 * state
            + _tile_bytes(B, H, 4) + _tile_bytes(B, lanes, 4))
