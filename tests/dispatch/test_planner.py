"""Planner: plan determinism, inspectability, packing and fallbacks."""
import pytest

from repro.configs import get_config
from repro.configs.sharp_lstm import EESEN, lstm_config
from repro.dispatch import WorkItem, plan


def _mix(Ts=(24, 16, 12)):
    cfgs = [lstm_config(64, layers=3), lstm_config(96, layers=2),
            lstm_config(64, layers=4)]
    return [WorkItem.from_config(c, T=t, uid=i)
            for i, (c, t) in enumerate(zip(cfgs, Ts))]


def test_plan_is_deterministic():
    p1, p2 = plan(_mix()), plan(_mix())
    assert p1.describe() == p2.describe()
    assert p1.slots == p2.slots
    assert p1.items == p2.items


def test_plan_is_explicit_and_inspectable():
    p = plan(_mix())
    text = p.describe()
    assert "slot" in text and "wave" in text and "K" in text
    for s in p.slots:
        assert s.cells and s.tile_k > 0 and len(s.mvm_block) == 2
        assert s.chunk_len >= 1
        for c in s.cells:
            # the wavefront invariant: every cell sits on its anti-diagonal
            assert c.layer + c.chunk == s.wave
    for ip in p.items:
        assert ip.schedule in ("wavefront", "fused", "per_step", "per_layer")
        assert ip.tile_k > 0


def test_slot_order_respects_dependencies():
    """A cell's inputs — (l-1, k) and (l, k-1) — must run in earlier
    waves, and slots are emitted in wave order."""
    p = plan(_mix())
    waves = [s.wave for s in p.slots]
    assert waves == sorted(waves)
    seen = set()
    for s in p.slots:
        for c in s.cells:
            if c.layer > 0:
                assert (c.uid, c.layer - 1, c.chunk) in seen
            if c.chunk > 0:
                assert (c.uid, c.layer, c.chunk - 1) in seen
        seen.update((c.uid, c.layer, c.chunk) for c in s.cells)


def test_packing_beats_per_item_launches():
    p = plan(_mix())
    assert p.launches < p.naive_launches
    # every same-signature wave merged: at least one slot is G-batched
    assert any(s.g > 1 for s in p.slots)


def test_all_cells_covered_exactly_once():
    p = plan(_mix())
    for ip in p.items:
        cells = [c for s in p.slots for c in s.cells if c.uid == ip.uid]
        assert len(cells) == len(set(cells)) == ip.item.L * ip.nk


def test_rglru_falls_back_and_bidirectional_packs():
    """rglru stays external (diagonal recurrence, per-layer scan); a
    bidirectional item no longer falls back — its fwd/bwd cells enter the
    packed interleaved timeline (ISSUE-5 retired the per-layer path)."""
    rg = WorkItem.from_config(get_config("recurrentgemma-2b"), T=8, uid=0)
    assert rg.family == "rglru"
    bi = WorkItem.from_config(EESEN, T=8, uid=1)
    assert bi.bidirectional and bi.dirs == 2
    lstm_it = WorkItem.from_config(lstm_config(64, layers=3), T=24, uid=2)
    p = plan([rg, bi, lstm_it])
    assert set(p.external) == {0}
    assert p.item(0).naive_launches == rg.L
    ip = p.item(1)
    assert ip.schedule in ("wavefront", "fused")
    cells = [c for s in p.slots for c in s.cells if c.uid == 1]
    assert len(cells) == 2 * bi.L * ip.nk  # every (layer, chunk, dir) once
    assert {c.direction for c in cells} == {"fwd", "bwd"}


def _bi_item(L=3, T=12, B=1, uid=0, share=None):
    import dataclasses

    cfg = dataclasses.replace(lstm_config(64, layers=L),
                              bidirectional=True)
    return WorkItem.from_config(cfg, T=T, B=B, uid=uid, share=share)


def test_bidirectional_launch_count_matches_interleaved_formula():
    """The acceptance proof: an L-layer bidirectional prefill plans at
    most 2·L·⌈T/bt⌉ launches (the per-direction-per-chunk count) —
    strictly fewer except the nk=2 ragged boundary case, where every wave
    splits — and exactly matches ``bidir_wavefront_launches``: L·nk
    waves, one G-merged launch each, +2 unmerged waves per layer under
    ragged T."""
    from repro.dispatch.planner import bidir_wavefront_launches
    from repro.kernels.common import cdiv

    L = 3
    for T, bt in ((12, 4), (14, 4), (7, 7), (5, 2), (5, 3)):
        p = plan([_bi_item(L=L, T=T)], schedule="wavefront", block_t=bt)
        ip = p.item(0)
        nk = cdiv(T, ip.block_t)
        assert p.launches == bidir_wavefront_launches(L, T, ip.block_t), \
            (T, bt, p.describe())
        assert p.launches <= 2 * L * nk
        if not (nk == 2 and T % ip.block_t):  # the documented equality
            assert p.launches < 2 * L * nk, (T, bt)
        assert p.launches == ip.naive_launches
    # divisible stripes G-merge every wave: exactly L·nk launches
    assert plan([_bi_item(L=L, T=12)], schedule="wavefront",
                block_t=4).launches == L * 3


def test_bidirectional_interleaved_dependencies_respected():
    """Execution order must satisfy the concat dependency: a layer-l cell
    of chunk k runs only after BOTH directions of layer l-1 produced chunk
    k, and after its own walk's previous chunk (fwd: k-1, bwd: k+1)."""
    p = plan([_bi_item(L=3, T=14)], schedule="wavefront", block_t=4)
    nk = p.item(0).nk
    seen = set()
    for s in p.slots:
        for c in s.cells:
            if c.layer > 0:
                assert (c.layer - 1, c.chunk, "fwd") in seen, c
                assert (c.layer - 1, c.chunk, "bwd") in seen, c
            if c.direction == "fwd" and c.chunk > 0:
                assert (c.layer, c.chunk - 1, "fwd") in seen, c
            if c.direction == "bwd" and c.chunk < nk - 1:
                assert (c.layer, c.chunk + 1, "bwd") in seen, c
        seen.update((c.layer, c.chunk, c.direction) for c in s.cells)


def test_bidirectional_cross_b_packs_but_never_merges_directions():
    """share-equal bidirectional requests B-concat per (layer, chunk,
    direction) row — fwd and bwd halves bind different U matrices, so they
    may share a LAUNCH (two g rows) but never a row."""
    items = [_bi_item(L=2, T=8, uid=i, share=0) for i in range(2)]
    p = plan(items, schedule="wavefront", block_t=4)
    solo = plan([items[0]], schedule="wavefront", block_t=4)
    assert p.launches < 2 * solo.launches  # cross-request merge happened
    for s in p.slots:
        for grp in s.groups:
            assert len({(c.layer, c.direction) for c in grp}) == 1
    assert any(len(grp) == 2 for s in p.slots for grp in s.groups)


def test_duplicate_uids_rejected():
    items = _mix()
    with pytest.raises(ValueError):
        plan([items[0], items[0]])


def test_from_config_requires_a_recurrence():
    with pytest.raises(ValueError):
        WorkItem.from_config(get_config("starcoder2-3b"), T=8)


def test_stripe_candidates_respect_vmem_budget():
    """T-wide chunks VMEM cannot hold whole must not sneak in through the
    planner's candidate widening at a memory-bound width (H512): each
    cell stays one stripe that fits the capacity, where the whole T=512
    would not."""
    from repro.core.tiling import seq_block_footprint, seq_fits

    it = WorkItem(uid=0, family="lstm", B=16, T=512, H=512, L=1)
    p = plan([it])
    assert not seq_fits(seq_block_footprint(it.T, it.B, it.H))
    assert seq_fits(seq_block_footprint(p.item(0).block_t, it.B, it.H))
    for s in p.slots:
        assert s.stripe == s.chunk_len and seq_fits(s.footprint())


def test_plan_only_items_are_flagged():
    rg = WorkItem.from_config(get_config("recurrentgemma-2b"), T=8, uid=0)
    p = plan([rg])
    assert not p.item(0).executable
    assert "[plan-only]" in p.item(0).describe()
    one = WorkItem(uid=1, family="rglru", B=1, T=8, H=64, L=1)
    assert plan([one]).item(1).executable


def test_cross_b_merge_is_scored_and_deterministic():
    """Mixed-width same-signature cells merge into padded slots only by
    perfmodel decision; the plan stays deterministic and every row's valid
    width is recorded for the in-kernel mask."""
    items = [WorkItem.from_config(lstm_config(64, layers=2), T=12, B=b,
                                  uid=i) for i, b in enumerate((2, 1))]
    p1, p2 = plan(items), plan(items)
    assert p1.describe() == p2.describe() and p1.slots == p2.slots
    for s in p1.slots:
        assert len(s.group_b) == s.g
        assert all(b <= s.B for b in s.group_b)
        assert max(s.group_b) == s.B  # padding never exceeds the widest row
    # B-widened here (small widths within one MXU row-tile): one slot per
    # wave, strictly fewer launches than the per-B-signature plan
    assert p1.launches < plan(items, cross_b=False).launches


def test_share_groups_require_matching_layers_only():
    """share-keyed items of different T still only concat where wave/layer
    align; all cells remain covered exactly once."""
    cfg = lstm_config(48, layers=3)
    items = [WorkItem.from_config(cfg, T=t, uid=i, share=0)
             for i, t in enumerate((12, 8))]
    p = plan(items)
    for ip in p.items:
        cells = [c for s in p.slots for c in s.cells if c.uid == ip.uid]
        assert len(cells) == len(set(cells)) == ip.item.L * ip.nk
    for s in p.slots:
        for grp in s.groups:
            assert len({c.layer for c in grp}) == 1  # one U per row
        for c in s.cells:
            assert c.layer + c.chunk == s.wave


def test_cross_b_concat_respects_vmem_budget():
    """Concat rows are wider than any item alone — the packer must split a
    share group rather than emit a row at which U and even a one-frame
    stripe blow the sequence kernels' VMEM capacity (H1536: two buffers
    of a 36 MiB U)."""
    from repro.core.tiling import seq_block_footprint, seq_fits

    items = [WorkItem(uid=i, family="lstm", B=64, T=8, H=1536, share=0,
                      L=1) for i in range(6)]
    p = plan(items)
    assert not seq_fits(seq_block_footprint(1, 6 * 64, 1536))
    assert max(len(grp) for s in p.slots for grp in s.groups) < 6
    for s in p.slots:
        assert seq_fits(s.footprint())
        for b in s.group_b:
            assert seq_fits(seq_block_footprint(1, b, s.H))
    # ...while small shapes still concat into single rows
    small = plan([WorkItem(uid=i, family="lstm", B=1, T=8, H=32, L=2,
                           share=0) for i in range(3)])
    assert any(len(grp) == 3 for s in small.slots for grp in s.groups)


def test_stripe_alignment_respects_each_members_vmem_budget():
    """Regression: cross-B chunk alignment must not hand a large-B item a
    stripe that was only valid at a small-B partner's width — each launch
    stripes at its own (widest) row, so every slot's working set stays
    within the kernels' capacity."""
    from repro.core.tiling import seq_fits

    items = [WorkItem(uid=0, family="lstm", B=1, T=512, H=512, L=2),
             WorkItem(uid=1, family="lstm", B=32, T=512, H=512, L=2)]
    p = plan(items)
    for s in p.slots:
        assert seq_fits(s.footprint()), s.describe()


def test_decode_plan_is_one_chained_slot():
    items = [WorkItem(uid=i, family="gru", B=1, T=1, H=48, L=4, share=0)
             for i in range(3)]
    from repro.dispatch import plan_decode
    p = plan_decode(items)
    assert len(p.slots) == 1 and p.slots[0].chained
    assert p.launches == 1 and p.naive_launches == 3 * 4
    s = p.slots[0]
    assert s.g == 4  # one group per layer, in chain order
    assert [grp[0].layer for grp in s.groups] == [0, 1, 2, 3]
    assert s.B == 3 and set(s.group_b) == {3}
    assert "chained" in s.describe()


def test_gru_items_plan_with_three_gates():
    it = WorkItem(uid=0, family="gru", B=1, T=16, H=48, L=2)
    assert it.gates == 3
    p = plan([it, WorkItem(uid=1, family="gru", B=1, T=16, H=48, L=3)])
    assert all(s.family == "gru" for s in p.slots)
    assert p.launches < p.naive_launches
