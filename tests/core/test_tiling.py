"""Tile-engine math: padding, cycles, selection (paper §4.2/§6)."""
import math

import pytest
from tests._hyp import given, settings, st

from repro.core.tiling import (K_CHOICES, MiB, SEQ_VMEM_CAPACITY,
                               TileConfig, block_waste, cdiv, mvm_cycles,
                               padding_waste, select_block_shape,
                               select_time_block, select_tile,
                               seq_block_footprint, seq_fits, seq_vmem_limit)
from repro.runtime.errors import PlanRejected


@settings(max_examples=50, deadline=None)
@given(rows=st.integers(1, 5000), cols=st.integers(1, 5000),
       k=st.sampled_from(K_CHOICES), macs=st.sampled_from([1024, 4096, 65536]))
def test_cycles_bounds(rows, cols, k, macs):
    if k > macs:
        return
    t = TileConfig(k=k, macs=macs)
    fixed = mvm_cycles(rows, cols, t, reconfigure=False)
    rec = mvm_cycles(rows, cols, t, reconfigure=True)
    ideal = rows * cols / macs
    assert rec <= fixed                       # reconfiguration never hurts
    assert fixed >= max(1, math.floor(ideal))  # can't beat the MAC budget
    # fixed cycles == analytic ceil product
    assert fixed == max(1, math.ceil(rows / t.k) * math.ceil(cols / t.cols))


@settings(max_examples=50, deadline=None)
@given(rows=st.integers(1, 3000), cols=st.integers(1, 3000),
       k=st.sampled_from(K_CHOICES))
def test_padding_waste_range(rows, cols, k):
    t = TileConfig(k=k, macs=4096)
    w = padding_waste(rows, cols, t)
    assert 0.0 <= w < 1.0
    if rows % t.k == 0 and cols % t.cols == 0:
        assert w == 0.0


def test_select_tile_is_argmin():
    for rows, cols, macs in [(1360, 340, 4096), (4096, 1024, 65536),
                             (400, 100, 1024)]:
        best = select_tile(rows, cols, macs)
        best_c = mvm_cycles(rows, cols, best, reconfigure=True)
        for k in K_CHOICES:
            if k > macs:
                continue
            c = mvm_cycles(rows, cols, TileConfig(k=k, macs=macs),
                           reconfigure=True)
            assert best_c <= c


@settings(max_examples=40, deadline=None)
@given(m=st.integers(1, 4096), n=st.integers(1, 8192))
def test_block_shape_constraints(m, n):
    bm, bn = select_block_shape(m, n)
    assert bm >= 1 and bn >= 128 or bn >= n  # lane-aligned
    assert bm * bn * 4 <= 4 * 2**20  # default VMEM budget
    assert 0.0 <= block_waste(m, n, bm, bn) < 1.0


def test_block_shape_prefers_zero_waste():
    bm, bn = select_block_shape(1024, 4096)
    assert 1024 % bm == 0 and 4096 % bn == 0  # divisible dims -> no waste


def test_block_shape_selection_is_cached():
    """The exploration used to re-run on every hot-path layer call."""
    select_block_shape.cache_clear()
    select_block_shape(300, 700)
    hits = select_block_shape.cache_info().hits
    select_block_shape(300, 700)
    assert select_block_shape.cache_info().hits == hits + 1


def test_select_time_block():
    assert select_time_block(1, 1, 96) == 1
    bt = select_time_block(64, 4, 256)
    assert 1 <= bt <= 64 and 64 % bt == 0   # zero T-edge waste is available
    assert select_time_block(7, 2, 96) == 7  # exact fit beats padded stripes
    # huge H: two buffers of the 36 MiB U leave room for a 41-frame
    # stripe, so T=64 runs in two even steps with U resident
    assert select_time_block(64, 8, 1536) == 32
    # U alone above the capacity: refused, never a silent bt=1 fallback
    with pytest.raises(PlanRejected, match="H2048"):
        select_time_block(64, 8, 2048)


@settings(max_examples=30, deadline=None)
@given(T=st.integers(1, 300), B=st.integers(1, 8),
       H=st.sampled_from([32, 96, 256, 1024]))
def test_time_block_constraints(T, B, H):
    bt = select_time_block(T, B, H)
    assert 1 <= bt <= T
    assert seq_fits(seq_block_footprint(bt, B, H))   # within the capacity
    steps = cdiv(T, bt)
    assert steps * bt - T < steps                    # split evenly
    if steps > 1:  # the fewest grid steps: one fewer would not fit
        assert not seq_fits(seq_block_footprint(cdiv(T, steps - 1), B, H))


def test_time_block_int8_doubles_stripe_when_weight_bound():
    """The int8 payload no longer buys a longer stripe: the kernel holds
    U's f32 upcast in VMEM (D8), and AOT compiles for v5e need the same
    VMEM for an int8 and an fp32 U at H1024 B64 (40.5 MiB at bt=8).  So
    at the weight-bound H1536/B8/T64 shape int8 keeps fp32's stripe, as
    does precision="bf16" (its U stays in float32 storage); only U stored
    in bfloat16 halves the weight term and keeps the whole T."""
    bt_fp32 = select_time_block(64, 8, 1536)
    assert bt_fp32 == 32
    assert select_time_block(64, 8, 1536, precision="int8") == bt_fp32
    assert select_time_block(64, 8, 1536, precision="bf16") == bt_fp32
    assert select_time_block(64, 8, 1536, weight_bytes=2) == 64
    # the int8 footprint is fp32's plus the scale row (two f32 buffers)
    assert seq_block_footprint(32, 8, 1536, precision="int8") == \
        seq_block_footprint(32, 8, 1536) + 2 * 8 * 4 * 1536 * 4


def test_time_block_density_discount():
    """Block-sparse residency: a half-dense U (+ its row-index operand)
    shrinks the weight term, so the selector keeps a larger stripe at the
    same weight-bound shape; density=1.0 is byte-identical to dense."""
    assert seq_block_footprint(32, 8, 512, density=1.0) == \
        seq_block_footprint(32, 8, 512)
    dense = select_time_block(64, 8, 1536)
    sparse = select_time_block(64, 8, 1536, density=0.25)
    assert sparse >= 2 * dense
    half = seq_block_footprint(32, 8, 512, density=0.5)
    full = seq_block_footprint(32, 8, 512)
    w = 2 * 4 * 4 * 512 * 512                 # two buffers of U
    # half the rows, plus the (1, 256) int32 row index, two (8, 256) tiles
    assert half == full - w + w // 2 + 2 * 8 * 256 * 4


def test_footprint_matches_the_v5e_compiler_and_counts_padding():
    """AOT compiles of lstm_seq for a described v5e at H1024 B64 float32
    need 2.75 MiB of scoped VMEM a frame of stripe, over 18.5 MiB (one
    16 MiB U) for the kernel compiled alone — 40.5 MiB at an 8-frame
    stripe, 62.5 at 16 — and over two U less 0.7 MiB inside GMAT's plan
    program (86.04 MiB at its 19-frame stripe).  The model counts two
    buffers of U, so it reads 16 MiB above the first and 0.7 MiB above
    the second.  B pads to 8 sublanes and 4H=1360 to 1408 lanes, so
    B7/H340 costs what B8 does and more than its unpadded bytes."""
    U = 16 * MiB
    assert seq_block_footprint(8, 64, 1024) == 40.5 * MiB + U
    assert seq_block_footprint(16, 64, 1024) == 62.5 * MiB + U
    assert 0 <= seq_block_footprint(19, 64, 1024) - 86.04 * MiB < MiB
    assert seq_block_footprint(8, 7, 340) == seq_block_footprint(8, 8, 340)
    unpadded = 4 * (340 * 1360 + 2 * 8 * 7 * (1360 + 340) + 8 * 7 * 340)
    assert seq_block_footprint(8, 7, 340) > unpadded
    assert seq_block_footprint(8, 8, 340, gates=3) < \
        seq_block_footprint(8, 8, 340)


def test_vmem_limit_is_the_default_below_it_and_capped_above():
    """A kernel that fits the default scoped limit asks for exactly it (so
    it compiles as before); a larger one asks for its footprint plus
    headroom, never above the capacity."""
    assert seq_vmem_limit(seq_block_footprint(8, 64, 340)) == 16 * MiB
    assert seq_vmem_limit(seq_block_footprint(16, 64, 1024)) == 81 * MiB
    assert seq_vmem_limit(10 * SEQ_VMEM_CAPACITY) == SEQ_VMEM_CAPACITY
