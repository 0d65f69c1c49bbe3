"""Kernel-reordering weight mapping (paper §III-B) — invariants + oracle."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import patterns as P
from repro.core.indexing import (
    build_index_stream,
    decode_placements,
    index_overhead_bits,
)
from repro.core.mapping import (
    CrossbarConfig,
    map_layer,
    map_layer_naive,
)
from repro.core.ou import naive_ou_schedule, pattern_ou_schedule


def _random_bits(rng, co, ci, n_pat=4, zero_frac=0.3, k=9):
    pats = [0]
    while len(pats) < n_pat + 1:
        b = int(rng.integers(1, 2**k))
        if b not in pats:
            pats.append(b)
    probs = np.full(n_pat + 1, (1 - zero_frac) / n_pat)
    probs[0] = zero_frac
    choice = rng.choice(len(pats), size=(co, ci), p=probs)
    return np.array(pats)[choice]


@pytest.mark.parametrize("order", ["pattern", "channel", "width"])
def test_no_overlap_and_bounds(rng, order):
    """Placements never overlap and never exceed crossbar bounds."""
    bits = _random_bits(rng, co=40, ci=6)
    cfg = CrossbarConfig(rows=64, cols=64, cells_per_weight=2)
    m = map_layer(bits, cfg, block_order=order)
    occupied = {}
    for p in m.placements:
        for r in range(p.row0, p.row0 + p.height):
            for c in range(p.col0, p.col0 + p.width_cells):
                key = (p.crossbar, r, c)
                assert key not in occupied, f"overlap at {key}"
                occupied[key] = p
        assert p.row0 + p.height <= cfg.rows
        assert p.col0 + p.width_cells <= cfg.cols
        assert p.crossbar < m.num_crossbars


def test_all_nonzero_kernels_placed(rng):
    bits = _random_bits(rng, co=30, ci=5)
    m = map_layer(bits)
    placed = {}
    for p in m.placements:
        for kid in p.block.kernel_ids:
            placed.setdefault(p.block.channel, set()).add(kid)
    for c in range(5):
        expect = set(np.nonzero(bits[:, c])[0])
        assert placed.get(c, set()) == expect


def test_zero_kernels_never_stored(rng):
    bits = _random_bits(rng, co=30, ci=5, zero_frac=0.6)
    m = map_layer(bits)
    nz = int((bits != 0).sum())
    assert m.stored_kernels == nz


def test_cells_accounting(rng):
    bits = _random_bits(rng, co=30, ci=5)
    m = map_layer(bits)
    expect = int(P.pattern_sizes(bits).sum()) * m.config.cells_per_weight
    assert m.cells_used == expect


def test_area_never_worse_with_full_sparsity():
    """An all-zero layer maps to zero crossbars."""
    bits = np.zeros((16, 4), np.int64)
    m = map_layer(bits)
    assert m.num_crossbars == 0
    assert m.stored_kernels == 0


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), zero=st.floats(0.0, 0.9))
def test_compression_beats_naive_on_sparse(seed, zero):
    """With <= 4 nonzero patterns, pattern mapping never uses more
    crossbars than naive (the paper's headline claim, as an invariant)."""
    rng = np.random.default_rng(seed)
    bits = _random_bits(rng, co=64, ci=8, n_pat=4, zero_frac=zero)
    ours = map_layer(bits).num_crossbars
    naive = map_layer_naive(64, 8).num_crossbars
    assert ours <= naive


def test_index_stream_roundtrip(rng):
    """§IV-C: placement is reconstructible from the index stream alone."""
    bits = _random_bits(rng, co=50, ci=7)
    m = map_layer(bits)
    stream = build_index_stream(m)
    decoded = decode_placements(stream, m.config)
    assert len(decoded) == len(m.placements)
    for a, b in zip(decoded, m.placements):
        assert (a.crossbar, a.row0, a.col0, a.width_cells) == (
            b.crossbar, b.row0, b.col0, b.width_cells,
        )
        assert a.block.kernel_ids == b.block.kernel_ids


def test_index_overhead_bits(rng):
    bits = _random_bits(rng, co=512, ci=4, zero_frac=0.4)
    m = map_layer(bits)
    stream = build_index_stream(m)
    info = index_overhead_bits(stream)
    # paper §V-D: <= 9 bits per kernel for 512 output channels
    assert info["bits_per_kernel_index"] == 9
    assert info["kernel_index_bits"] == 9 * m.stored_kernels


def test_ou_schedules(rng):
    bits = _random_bits(rng, co=40, ci=6)
    m = map_layer(bits)
    sched = pattern_ou_schedule(m)
    cfg = m.config
    # every OU fits inside a pattern block: wordlines == block height <= 9
    assert (sched.wordlines <= cfg.ou_rows).all()
    assert (sched.bitlines <= cfg.ou_cols).all()
    # total ADC-side cells covered equals stored cells
    assert int(sched.bitlines.sum() * cfg.ou_rows
               >= m.cells_used)  # bands cover all cells

    naive = map_layer_naive(40, 6)
    ns = naive_ou_schedule(naive)
    # naive covers the whole dense matrix
    total_cells = naive.rows_total * naive.cols_total
    covered = int((ns.wordlines * ns.bitlines).sum())
    assert covered == total_cells


def _blocks_by_loop(channel, bits_c, sizes_c):
    """The per-kernel loop ``_blocks_for_channel`` replaced: the reference
    its grouping must equal."""
    from repro.core.mapping import PatternBlock

    groups = {}
    for out_ch, b in enumerate(bits_c):
        if int(b) != P.ALL_ZERO:
            groups.setdefault(int(b), []).append(out_ch)
    out = [PatternBlock(channel, b, int(sizes_c[ks[0]]), tuple(ks))
           for b, ks in groups.items()]
    out.sort(key=lambda blk: (-blk.height, blk.pattern))
    return out


@pytest.mark.parametrize("k", [1, 9, 49])
def test_blocks_for_channel_matches_the_loop(k):
    """Grouping one channel's kernels by pattern with numpy gives exactly
    the blocks, order and kernel ids of the per-kernel loop, for 1x1,
    3x3 and dense 7x7 patterns."""
    from repro.core.mapping import _blocks_for_channel

    rng = np.random.default_rng(k)
    bits = _random_bits(rng, 40, 6, n_pat=min(5, 2**k - 1), k=k)
    sizes = P.pattern_sizes(bits)
    for c in range(bits.shape[1]):
        assert _blocks_for_channel(c, bits[:, c], sizes[:, c]) \
            == _blocks_by_loop(c, bits[:, c], sizes[:, c])
