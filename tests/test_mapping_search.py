"""Mapping design-space optimizer: differential + cost-model harness.

Three contracts under test, matching the guarantees ``check_baseline.py``
gates on the bench side:

  * **zero-drift cost model** — ``core.simulator.mapping_cost`` prices a
    candidate through the simulator's own chain, so its
    area/energy/cycles equal the ``hardware_report`` numbers *exactly*
    (``==`` on floats, no tolerance) for every layer of an optimized
    program, fp32 and int8;
  * **semantics preserved** — ``CompileOptions(optimize='auto')`` only
    changes layout, never math: fp32 logits are bit-identical to the
    fixed scheme on XLA (any forced reorder strategy included), Pallas
    agrees to fp32 noise, the 8-virtual-device sharded path agrees at
    fp32 and int8, and every visited candidate's column reorder is a
    bijective permutation;
  * **never worse, always reproducible** — selection is Pareto-guarded
    (chosen <= fixed on both area-cells and energy, fixed on ties),
    deterministic within a process and byte-identical across processes
    for the same seed, and the chosen mapping round-trips through the
    manifest.

Hypothesis-randomized variants of the bijectivity and zero-drift
properties live in ``tests/test_mapping_search_props.py``; the
exhaustive-sweep oracle check is ``slow``-marked at the bottom.
"""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest
from conftest import run_virtual_devices as _run_sub
from conftest import unfolded

from repro.core.mapping import MappingCandidate
from repro.core.mapsearch import (
    MappingSearchConfig,
    choose_fc_reorder,
    search_layer_mapping,
)
from repro.core.pruning import (
    build_dictionaries,
    magnitude_prune,
    project_params,
)
from repro.core.simulator import mapping_cost
from repro.core.sparse import (
    REORDERS,
    nonzero_block_masks,
    predicted_tile_nnz,
    reorder_columns,
)
from repro.engine import (
    CompileOptions,
    compile_network,
    conv_mapping_search,
    load_program,
    make_forward,
    save_program,
)
from repro.engine.lowering import (
    _pad_axis,
    conv_matrix,
    lower_matrix,
    patch_order,
)
from repro.models.cnn import conv_weight_names, init_cnn, mini_cnn_config


def _pruned(seed=0, sparsity=0.7, num_patterns=4, widths=(8, 16, 16),
            num_classes=4):
    cfg = mini_cnn_config(num_classes=num_classes, input_hw=12,
                          widths=widths)
    params = init_cnn(cfg, jax.random.PRNGKey(seed))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, sparsity)
    dicts = build_dictionaries(params, names, num_patterns)
    params, bits = project_params(params, dicts)
    return cfg, params, bits


@pytest.fixture(scope="module")
def mini():
    return _pruned()


@pytest.fixture(scope="module")
def progs(mini):
    """(fixed, auto) fp32 programs of the same pruned net."""
    cfg, params, bits = mini
    return (
        compile_network(cfg, params, bits),
        compile_network(cfg, params, bits,
                        options=CompileOptions(optimize="auto")),
    )


@pytest.fixture(scope="module")
def x8():
    return jax.random.normal(jax.random.PRNGKey(5), (8, 1, 12, 12))


# ------------------------------------------------------------- cost model


def test_cost_model_zero_drift_fp32(progs):
    """mapping_cost re-prices every optimized layer to the exact
    hardware_report numbers — the differential that makes search
    predictions trustworthy."""
    _, auto = progs
    rep = auto.hardware_report()
    for c, row in zip(auto.convs, rep["layers"]):
        assert c.mapping is not None
        mc = mapping_cost(c.pattern_bits, c.mapping, c.out_hw ** 2,
                          c.kernel ** 2)
        assert mc.crossbars == row["crossbars"]
        assert mc.area_cells == row["area_cells"]
        assert mc.energy_pj == row["energy_pj"]  # exact, not approx
        assert mc.cycles == row["cycles"]


def test_cost_model_zero_drift_int8(mini):
    """Same zero-drift contract when the search prices the quantized
    cell-slice count."""
    cfg, params, bits = mini
    prog = compile_network(
        cfg, params, bits,
        options=CompileOptions(precision="int8", optimize="auto"),
    )
    rep = prog.hardware_report()
    for c, row in zip(prog.convs, rep["layers"]):
        assert c.mapping.cells_per_weight == prog.cells_per_weight
        mc = mapping_cost(c.pattern_bits, c.mapping, c.out_hw ** 2,
                          c.kernel ** 2)
        assert (mc.crossbars, mc.area_cells, mc.energy_pj, mc.cycles) == (
            row["crossbars"], row["area_cells"], row["energy_pj"],
            row["cycles"],
        )


def test_search_cost_equals_report_cost(mini, progs):
    """The standalone search's predicted cost for its chosen candidate is
    the cost the compiled program reports."""
    cfg, params, bits = mini
    _, auto = progs
    rep = auto.hardware_report()
    for i, (c, row) in enumerate(zip(auto.convs, rep["layers"]), start=1):
        res = conv_mapping_search(
            np.asarray(params[f"conv{i}"]["w"]), bits[f"conv{i}"], c.out_hw
        )
        assert res.chosen == c.mapping
        assert res.cost.area_cells == row["area_cells"]
        assert res.cost.energy_pj == row["energy_pj"]


# ------------------------------------------------- search-loop invariants


def test_visited_candidates_all_bijective(mini):
    """Every candidate the search prices induces a bijective column
    permutation on the layer's engine operands — no reorder strategy can
    drop or duplicate an output column."""
    cfg, params, bits = mini
    opts = CompileOptions()
    for i in (1, 2, 3):
        w = np.asarray(params[f"conv{i}"]["w"], np.float32)
        order = patch_order(w.shape[1], w.shape[2], opts.block)
        wp = _pad_axis(_pad_axis(conv_matrix(w, order), 0, opts.block), 1,
                       opts.tile)
        masks = nonzero_block_masks(wp, opts.block)
        res = conv_mapping_search(w, bits[f"conv{i}"], out_hw=10)
        assert res.evaluations == len(res.visited) > 1
        for cand in res.visited:
            order = reorder_columns(masks, cand.reorder)
            np.testing.assert_array_equal(
                np.sort(order), np.arange(masks.shape[0])
            )


def test_predicted_bricks_match_built(mini):
    """predicted_tile_nnz (the search's engine-memory objective) equals
    the brick count the lowering actually stores, per strategy."""
    cfg, params, bits = mini
    opts = CompileOptions()
    w = np.asarray(params["conv2"]["w"], np.float32)
    order = patch_order(w.shape[1], w.shape[2], opts.block)
    wp = _pad_axis(_pad_axis(conv_matrix(w, order), 0, opts.block), 1,
                   opts.tile)
    masks = nonzero_block_masks(wp, opts.block)
    for strategy in REORDERS:
        order = reorder_columns(masks, strategy)
        predicted = int(predicted_tile_nnz(masks, order, opts.tile).sum())
        bp = lower_matrix(wp, opts.block, opts.tile, reorder=strategy)
        assert predicted == int(bp.nnz.sum())


def test_pareto_guard_never_worse(mini):
    cfg, params, bits = mini
    for i in (1, 2, 3):
        res = conv_mapping_search(
            np.asarray(params[f"conv{i}"]["w"]), bits[f"conv{i}"], out_hw=10
        )
        assert res.cost.area_cells <= res.fixed_cost.area_cells
        assert res.cost.energy_pj <= res.fixed_cost.energy_pj
        assert res.fixed == MappingCandidate()
    # the smoke net must show a strict win somewhere (ISSUE acceptance)
    assert any(
        conv_mapping_search(
            np.asarray(params[f"conv{i}"]["w"]), bits[f"conv{i}"], out_hw=10
        ).improved
        for i in (1, 2, 3)
    )


def test_search_rerun_identical(mini):
    """Same inputs + seed -> byte-identical result object, visited order
    included."""
    cfg, params, bits = mini
    w = np.asarray(params["conv1"]["w"])
    a = conv_mapping_search(w, bits["conv1"], out_hw=10)
    b = conv_mapping_search(w, bits["conv1"], out_hw=10)
    assert a == b
    assert a.visited == b.visited


def test_tie_keeps_fixed_scheme():
    """A layer too small for any geometry to win: the Pareto tie-break
    must return the fixed scheme itself, unimproved."""
    bits = np.full((2, 2), 0b111111111, dtype=np.int64)
    res = search_layer_mapping(
        bits,
        search=MappingSearchConfig(crossbar_dims=((512, 512),),
                                   block_orders=("pattern",),
                                   reorders=("pattern",)),
    )
    assert res.chosen == res.fixed
    assert not res.improved


def test_search_config_validation():
    with pytest.raises(ValueError, match="block orders"):
        MappingSearchConfig(block_orders=("bogus",))
    with pytest.raises(ValueError, match="reorder"):
        MappingSearchConfig(reorders=("bogus",))
    with pytest.raises(ValueError, match="crossbar dims"):
        MappingSearchConfig(crossbar_dims=((0, 512),))
    with pytest.raises(ValueError, match="restarts"):
        MappingSearchConfig(restarts=-1)
    # a fixed scheme that cannot realize the layer is an error, not a
    # silent fallback
    bits = np.full((2, 2), 0b111111111, dtype=np.int64)
    with pytest.raises(ValueError, match="cannot realize"):
        search_layer_mapping(bits, fixed=MappingCandidate(ou_rows=2))


def test_optimize_arg_validation(mini):
    cfg, params, bits = mini
    with pytest.raises(ValueError, match="optimize"):
        compile_network(cfg, params, bits,
                        options=CompileOptions(optimize="bogus"))
    with pytest.raises(ValueError, match="optimize"):
        compile_network(cfg, params, bits,
                        options=CompileOptions(optimize=42))


def test_choose_fc_reorder_counts_complete():
    rng = np.random.default_rng(3)
    masks = rng.random((64, 7)) < 0.4
    best, counts = choose_fc_reorder(masks, tile=16)
    assert set(counts) == set(REORDERS)
    assert counts[best] == min(counts.values())
    # ties keep the earliest strategy in the tuple ('pattern' first)
    tied = {s for s in REORDERS if counts[s] == counts[best]}
    assert best == next(s for s in REORDERS if s in tied)


# ------------------------------------------------------------ differential


def _assert_layout_only(fixed, auto, x):
    """The reorders change layout only.  With every weight row reading its
    input in channel order (:func:`conftest.unfolded`, each layer's output
    gathered back into channel order before its consumer reads it) the
    fp32 XLA logits are bit-identical to the fixed compile's; with each
    layer's order folded into the next layer's rows, a reorder also
    reorders the next layer's fp32 sums, so those agree to fp32 rounding."""
    def logits(prog):
        return np.asarray(make_forward(prog, backend="xla")(x))

    np.testing.assert_array_equal(logits(unfolded(auto)),
                                  logits(unfolded(fixed)))
    np.testing.assert_allclose(logits(auto), logits(fixed),
                               rtol=1e-5, atol=1e-5)


def test_auto_logits_bit_identical_xla(progs, x8):
    fixed, auto = progs
    _assert_layout_only(fixed, auto, x8)


@pytest.mark.parametrize("strategy", REORDERS)
def test_forced_reorder_bit_identical_xla(mini, x8, strategy):
    """Any single reorder strategy forced through the search changes
    layout only (:func:`_assert_layout_only`)."""
    cfg, params, bits = mini
    fixed = compile_network(cfg, params, bits)
    auto = compile_network(
        cfg, params, bits,
        options=CompileOptions(
            optimize=MappingSearchConfig(reorders=(strategy,))
        ),
    )
    _assert_layout_only(fixed, auto, x8)


def test_auto_pallas_interpret_matches(progs, x8):
    fixed, auto = progs
    ref = np.asarray(make_forward(fixed, backend="xla")(x8))
    out = np.asarray(
        make_forward(auto, backend="pallas", interpret=True)(x8)
    )
    np.testing.assert_allclose(out, ref, atol=1e-4)


def test_auto_int8_tolerance_equal(mini):
    """int8 logits are only tolerance-equal across layouts: per-brick
    quantization scales depend on column grouping, so a reorder can
    shift individual logits by O(quantization error)."""
    cfg, params, bits = mini
    fixed = compile_network(cfg, params, bits,
                            options=CompileOptions(precision="int8"))
    auto = compile_network(
        cfg, params, bits,
        options=CompileOptions(precision="int8", optimize="auto"),
    )
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 1, 12, 12))
    ref = np.asarray(make_forward(fixed, backend="xla")(x))
    out = np.asarray(make_forward(auto, backend="xla")(x))
    np.testing.assert_allclose(out, ref, atol=5e-3)
    assert (out.argmax(-1) == ref.argmax(-1)).mean() >= 0.95


def test_sharded_auto_matches_subprocess():
    """optimize='auto' programs shard identically to fixed ones: on 8
    virtualized devices the searched fp32 program agrees with its own
    single-device run and with the fixed program (bit for bit with every
    layer's input in channel order, as :func:`_assert_layout_only` says),
    and int8 holds to the quantization bound."""
    res = _run_sub(8, """
    import sys
    sys.path.insert(0, @TESTS@)
    from conftest import unfolded
    from repro.core.pruning import (build_dictionaries, magnitude_prune,
                                    project_params)
    from repro.engine import CompileOptions, compile_network, make_forward
    from repro.launch.mesh import make_mesh
    from repro.models.cnn import (conv_weight_names, init_cnn,
                                  mini_cnn_config)

    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, 0.7)
    dicts = build_dictionaries(params, names, 4)
    params, bits = project_params(params, dicts)
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 1, 12, 12))
    mesh = make_mesh((1, 8), ("data", "model"))

    out = {}
    fixed = compile_network(cfg, params, bits)
    auto = compile_network(cfg, params, bits,
                           options=CompileOptions(optimize="auto"))
    ref = np.asarray(make_forward(fixed, backend="xla")(x))
    single = np.asarray(make_forward(auto, backend="xla")(x))
    sharded = np.asarray(make_forward(auto, backend="xla", mesh=mesh)(x))
    ref_u = np.asarray(make_forward(unfolded(fixed), backend="xla")(x))
    single_u = np.asarray(make_forward(unfolded(auto), backend="xla")(x))
    out["fp32_auto_vs_fixed"] = float(np.abs(single - ref).max())
    out["fp32_unfolded_auto_vs_fixed"] = float(np.abs(single_u
                                                      - ref_u).max())
    out["fp32_sharded_vs_single"] = float(np.abs(sharded - single).max())

    autoq = compile_network(
        cfg, params, bits,
        options=CompileOptions(precision="int8", optimize="auto"))
    sq = np.asarray(make_forward(autoq, backend="xla")(x))
    shq = np.asarray(make_forward(autoq, backend="xla", mesh=mesh)(x))
    out["int8_sharded_vs_single"] = float(np.abs(shq - sq).max())
    print(json.dumps(out))
    """.replace("@TESTS@", repr(os.path.dirname(os.path.abspath(__file__)))))
    # bit-identical, not close
    assert res["fp32_unfolded_auto_vs_fixed"] == 0.0
    assert res["fp32_auto_vs_fixed"] < 1e-5
    assert res["fp32_sharded_vs_single"] < 1e-4
    assert res["int8_sharded_vs_single"] < 5e-3


# -------------------------------------------------------- reproducibility


def test_search_cross_process_determinism():
    """Same seed, two fresh processes: chosen mappings byte-identical."""
    body = """
    from repro.core.pruning import (build_dictionaries, magnitude_prune,
                                    project_params)
    from repro.engine import CompileOptions, compile_network
    from repro.models.cnn import (conv_weight_names, init_cnn,
                                  mini_cnn_config)

    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, 0.7)
    dicts = build_dictionaries(params, names, 4)
    params, bits = project_params(params, dicts)
    prog = compile_network(cfg, params, bits,
                           options=CompileOptions(optimize="auto"))
    print(json.dumps({
        "mappings": [c.mapping.to_manifest() for c in prog.convs],
        "fc": prog.fc.reorder,
    }))
    """
    a = _run_sub(1, body)
    b = _run_sub(1, body)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_in_process_matches_subprocess(progs):
    """The compiled choice is environment-independent: the subprocess
    result equals this process's compile."""
    _, auto = progs
    res = _run_sub(1, """
    from repro.core.pruning import (build_dictionaries, magnitude_prune,
                                    project_params)
    from repro.engine import CompileOptions, compile_network
    from repro.models.cnn import (conv_weight_names, init_cnn,
                                  mini_cnn_config)

    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, 0.7)
    dicts = build_dictionaries(params, names, 4)
    params, bits = project_params(params, dicts)
    prog = compile_network(cfg, params, bits,
                           options=CompileOptions(optimize="auto"))
    print(json.dumps([c.mapping.to_manifest() for c in prog.convs]))
    """)
    assert res == [c.mapping.to_manifest() for c in auto.convs]


# --------------------------------------------------------- serialization


def test_v3_roundtrip_preserves_mapping(tmp_path, progs, x8):
    _, auto = progs
    d = str(tmp_path / "prog")
    save_program(d, auto)
    loaded = load_program(d)  # verify=True: V205/V206 run on the load
    for a, b in zip(auto.convs, loaded.convs):
        assert a.mapping == b.mapping
    assert loaded.fc.reorder == auto.fc.reorder
    ref = np.asarray(make_forward(auto, backend="xla")(x8))
    out = np.asarray(make_forward(loaded, backend="xla")(x8))
    np.testing.assert_array_equal(out, ref)
    assert loaded.hardware_report() == auto.hardware_report()


def test_report_mapping_section(progs):
    fixed, auto = progs
    rf, ra = fixed.hardware_report(), auto.hardware_report()
    assert rf["mapping"]["optimized"] is False
    assert ra["mapping"]["optimized"] is True
    assert ra["mapping"]["per_layer"] == {
        c.name: c.mapping.to_manifest() for c in auto.convs
    }
    # totals are the per-layer sums, and the search won on area
    assert ra["area_cells"] == sum(r["area_cells"] for r in ra["layers"])
    assert ra["area_cells"] < rf["area_cells"]
    assert ra["energy_pj"] <= rf["energy_pj"]


# ----------------------------------------------------------------- oracle


@pytest.mark.slow
def test_greedy_matches_exhaustive_oracle(mini):
    """On the smoke layers the greedy descent must find the exhaustive
    sweep's optimum (same objective value — the argmin candidate may
    differ only on tie-broken axes)."""
    cfg, params, bits = mini
    for i in (1, 2, 3):
        w = np.asarray(params[f"conv{i}"]["w"])
        greedy = conv_mapping_search(w, bits[f"conv{i}"], out_hw=10)
        oracle = conv_mapping_search(
            w, bits[f"conv{i}"], out_hw=10,
            search=MappingSearchConfig(exhaustive=True),
        )
        assert dataclasses.astuple(greedy.cost) == \
            dataclasses.astuple(oracle.cost)
        assert greedy.bricks == oracle.bricks
