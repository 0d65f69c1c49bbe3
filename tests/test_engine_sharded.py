"""Sharded execution of ``CompiledNetwork`` across a device mesh.

Two layers of coverage, mirroring ``tests/test_distributed.py``:

  * in-process tests run whenever the pytest process sees enough devices —
    the CI multi-device job forces
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so every mesh
    size runs there (and the 1-device mesh case always runs, so the
    shard_map code path is exercised even in the plain suite);
  * one subprocess test virtualizes 8 host devices regardless of the
    parent environment and sweeps the whole 1/2/4/8 matrix — including
    tile counts not divisible by the mesh, the data x model mesh, stats
    equality, sharded service traffic, and the Pallas-interpret backend —
    so the multi-device paths are verified by the default tier-1 run too.

Partitioner unit tests (deterministic; hypothesis properties live in
``tests/test_partition.py``) ride along at the bottom.
"""

import jax
import numpy as np
import pytest
from conftest import run_virtual_devices as _run_sub

from repro.core.pruning import (
    build_dictionaries,
    magnitude_prune,
    project_params,
)
from repro.engine import (
    CompileOptions,
    NetworkPartition,
    compile_network,
    make_forward,
    pad_bp_tiles,
    partition_from_mesh,
    partition_network,
    tile_assignment,
)
from repro.launch.mesh import make_mesh
from repro.models.cnn import conv_weight_names, init_cnn, mini_cnn_config

# widths (8, 16, 24) with tile=8 give per-layer spmm tile counts (1, 2, 3)
# — deliberately not divisible by 2/4/8-way meshes, so every sharded run
# exercises the zero-padded grey-area tiles.
UNEVEN_OPTIONS = CompileOptions(block=9, tile=8)


def _pruned_program(options=UNEVEN_OPTIONS, widths=(8, 16, 24), num_classes=5):
    cfg = mini_cnn_config(num_classes=num_classes, input_hw=12, widths=widths)
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, 0.7)
    dicts = build_dictionaries(params, names, 4)
    params, bits = project_params(params, dicts)
    return cfg, compile_network(cfg, params, bits, options=options)


@pytest.fixture(scope="module")
def uneven():
    return _pruned_program()


def _mesh(data: int, model: int):
    n = data * model
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:n])


# ---------------------------------------------------------------- in-process


@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_sharded_forward_matches_single_device(uneven, n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    cfg, prog = uneven
    assert [c.bp.n_tiles for c in prog.convs] == [1, 2, 3]
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 1, 12, 12))
    ref = np.asarray(make_forward(prog, backend="xla")(x))
    out = np.asarray(make_forward(prog, backend="xla", mesh=_mesh(1, n))(x))
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_sharded_data_model_mesh_and_stats(uneven):
    """2x4 mesh, odd batch (fc rows fall back to replication), stats
    counters psum-reduced back to exactly the single-device counts."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    cfg, prog = uneven
    mesh = _mesh(2, 4)
    x = jax.random.normal(jax.random.PRNGKey(7), (7, 1, 12, 12))
    ref, s_ref = make_forward(prog, backend="xla", collect_stats=True)(x)
    out, s_sh = make_forward(
        prog, backend="xla", collect_stats=True, mesh=mesh
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    for name in s_ref.layers:
        np.testing.assert_array_equal(
            s_ref.layers[name].counts, s_sh.layers[name].counts
        )
        assert s_ref.layers[name].windows == s_sh.layers[name].windows


def test_single_device_mesh_runs_everywhere(uneven):
    """The mesh code path itself (shard_map spmm + scatter/psum wiring)
    needs no extra devices: a 1x1 mesh must agree bit-for-bit."""
    cfg, prog = uneven
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 1, 12, 12))
    ref = np.asarray(make_forward(prog, backend="xla")(x))
    out = np.asarray(make_forward(prog, backend="xla", mesh=_mesh(1, 1))(x))
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("n", [1, 2, 4])
def test_sharded_quantized_forward(n):
    """Int8 programs shard the same way: w_scales slabs ride with their
    tiles through shard_map.  Unlike fp32, sharded vs single-device is
    bounded by *quantization* error, not fp32 noise: a one-ulp
    reassociation difference in one layer's psum can flip an int8
    rounding in the next layer's dynamic activation quantization,
    amplifying to O(row_scale/2) — observed ~1e-4 here, asserted at the
    composed quantization bound."""
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    cfg, prog = _pruned_program()
    progq = _pruned_program_quantized()
    # batch 64 so the agreement bars below tolerate a couple of argmax
    # flips on near-tied logits (this net is random-init and 0.7-pruned)
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 1, 12, 12))
    ref = np.asarray(make_forward(progq[1], backend="xla")(x))
    out = np.asarray(
        make_forward(progq[1], backend="xla", mesh=_mesh(1, n))(x)
    )
    np.testing.assert_allclose(out, ref, atol=5e-3)
    assert (out.argmax(-1) == ref.argmax(-1)).mean() >= 0.98
    # and the quantized sharded run agrees with fp32 to quantization error
    ref_fp = np.asarray(make_forward(prog, backend="xla")(x))
    assert (out.argmax(-1) == ref_fp.argmax(-1)).mean() >= 0.95


def _pruned_program_quantized():
    cfg = mini_cnn_config(num_classes=5, input_hw=12, widths=(8, 16, 24))
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, 0.7)
    dicts = build_dictionaries(params, names, 4)
    params, bits = project_params(params, dicts)
    options = CompileOptions(block=9, tile=8, precision="int8")
    return cfg, compile_network(cfg, params, bits, options=options)


# ---------------------------------------------------------------- subprocess


def test_sharded_matrix_subprocess():
    """The full multi-device matrix on 8 virtualized host devices: sharded
    vs single-device forward for 1/2/4/8-way tile parallelism (both spmm
    geometries, uneven tile counts included), the 2x4 data x model mesh,
    exact stats-counter equality, sharded InferenceService traffic, and
    the Pallas-interpret backend."""
    res = _run_sub(8, """
    from repro.core.pruning import (build_dictionaries, magnitude_prune,
                                    project_params)
    from repro.engine import (CompileOptions, InferenceService,
                              compile_network, make_forward)
    from repro.launch.mesh import make_mesh
    from repro.models.cnn import (conv_weight_names, init_cnn,
                                  mini_cnn_config)

    cfg = mini_cnn_config(num_classes=5, input_hw=12, widths=(8, 16, 24))
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, 0.7)
    dicts = build_dictionaries(params, names, 4)
    params, bits = project_params(params, dicts)
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 1, 12, 12))

    out = {"diffs": {}, "n_tiles": {}, "folds": {}}
    for gname, options in [("mxu", CompileOptions()),
                           ("fine", CompileOptions(block=9, tile=8))]:
        prog = compile_network(cfg, params, bits, options=options)
        out["n_tiles"][gname] = [c.bp.n_tiles for c in prog.convs]
        # some layer's rows read a stored order that is not channel order
        out["folds"][gname] = any(
            not np.array_equal(op.in_order, np.arange(len(op.in_order)))
            for op in [*prog.convs, prog.fc])
        ref = np.asarray(make_forward(prog, backend="xla")(x))
        for n in (1, 2, 4, 8):
            mesh = make_mesh((1, n), ("data", "model"),
                             devices=jax.devices()[:n])
            got = np.asarray(
                make_forward(prog, backend="xla", mesh=mesh)(x))
            out["diffs"][f"{gname}_model{n}"] = \\
                float(np.abs(got - ref).max())
        mesh = make_mesh((2, 4), ("data", "model"))
        got = np.asarray(make_forward(prog, backend="xla", mesh=mesh)(x))
        out["diffs"][f"{gname}_data2_model4"] = float(np.abs(got - ref).max())

        _, s_ref = make_forward(prog, backend="xla", collect_stats=True)(x)
        _, s_sh = make_forward(prog, backend="xla", collect_stats=True,
                               mesh=mesh)(x)
        out[f"stats_equal_{gname}"] = all(
            np.array_equal(s_ref.layers[k].counts, s_sh.layers[k].counts)
            and s_ref.layers[k].windows == s_sh.layers[k].windows
            for k in s_ref.layers)

    # sharded service: 10 requests through 8 slots (partial generation)
    prog = compile_network(cfg, params, bits,
                           options=CompileOptions(block=9, tile=8))
    imgs = np.asarray(
        jax.random.normal(jax.random.PRNGKey(1), (10, 1, 12, 12)),
        np.float32)
    mesh = make_mesh((2, 4), ("data", "model"))
    svc = InferenceService(prog, batch_slots=8, backend="xla",
                           collect_stats=True, mesh=mesh)
    ref_svc = InferenceService(prog, batch_slots=8, backend="xla",
                               collect_stats=True)
    out["service_labels_equal"] = bool(
        np.array_equal(svc.classify(imgs), ref_svc.classify(imgs)))
    out["service_stats_equal"] = all(
        np.array_equal(svc.activation_stats.layers[k].counts,
                       ref_svc.activation_stats.layers[k].counts)
        for k in svc.activation_stats.layers)

    # Pallas interpret backend under the same mesh
    mesh2 = make_mesh((1, 2), ("data", "model"), devices=jax.devices()[:2])
    ref = np.asarray(make_forward(prog, backend="xla")(x))
    got = np.asarray(make_forward(prog, backend="pallas", interpret=True,
                                  mesh=mesh2)(x))
    out["diffs"]["pallas_model2"] = float(np.abs(got - ref).max())
    print(json.dumps(out))
    """)
    assert res["n_tiles"]["fine"] == [1, 2, 3]  # uneven vs 2/4/8-way meshes
    assert res["folds"]["fine"]  # the sharded path reads stored orders
    for key, diff in res["diffs"].items():
        assert diff < 1e-4, (key, diff)
    for key, val in res.items():
        if key.startswith(("stats_equal", "service_")):
            assert val, key


# ------------------------------------------------- partitioner (no devices)


@pytest.mark.parametrize("shards", [1, 2, 3, 4, 8])
def test_pad_bp_tiles_invariants(uneven, shards):
    cfg, prog = uneven
    for op in [*prog.convs, prog.fc]:
        bp = op.bp
        padded = pad_bp_tiles(bp, shards)
        assert padded.n_tiles % shards == 0
        assert padded.n_tiles - bp.n_tiles < shards  # minimal padding
        # original tiles bit-identical, padding tiles inert
        np.testing.assert_array_equal(
            np.asarray(padded.w_comp[: bp.n_tiles]), np.asarray(bp.w_comp)
        )
        assert not np.asarray(padded.w_comp[bp.n_tiles:]).any()
        assert not padded.nnz[bp.n_tiles:].any()
        # geometry / permutations untouched -> dense reconstruction equal
        assert (padded.n_out, padded.k_in) == (bp.n_out, bp.k_in)
        np.testing.assert_array_equal(
            np.asarray(padded.dense()), np.asarray(bp.dense())
        )


def test_tile_assignment_partitions_padded_range():
    for n_tiles, shards in [(1, 1), (1, 4), (3, 2), (5, 4), (8, 8), (7, 3)]:
        asg = tile_assignment(n_tiles, shards)
        assert asg.shape[0] == shards
        flat = np.sort(asg.ravel())
        np.testing.assert_array_equal(
            flat, np.arange(len(flat))
        )  # every padded tile exactly once
        assert len(flat) % shards == 0 and len(flat) >= n_tiles


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_partition_from_mesh_defaults_and_validation():
    mesh = _FakeMesh({"data": 2, "model": 4})
    part = partition_from_mesh(mesh)
    assert (part.data, part.model) == (2, 4)
    # explicit partition must match the mesh axis sizes
    ok = NetworkPartition(data=2, model=4)
    assert partition_from_mesh(mesh, ok) is ok
    with pytest.raises(ValueError, match="model=8"):
        partition_from_mesh(mesh, NetworkPartition(data=2, model=8))
    # axis absent from the mesh counts as size 1
    assert partition_from_mesh(_FakeMesh({"x": 3})).n_chips == 1
    with pytest.raises(ValueError):
        NetworkPartition(data=0, model=2)


def test_make_forward_partition_requires_mesh(uneven):
    cfg, prog = uneven
    with pytest.raises(ValueError, match="requires mesh"):
        make_forward(prog, partition=NetworkPartition(model=2))


def test_partition_mesh_size_mismatch_rejected(uneven):
    """A program partitioned for 4 chips must not silently run on a
    smaller mesh."""
    cfg, prog = uneven
    progp = partition_network(prog, model=4)
    with pytest.raises(ValueError, match="mesh has"):
        make_forward(progp, backend="xla", mesh=_mesh(1, 1))


def test_hardware_report_chips_view(uneven):
    cfg, prog = uneven
    progp = partition_network(prog, data=2, model=4)
    rep = progp.hardware_report()
    ch = rep["chips"]
    assert (ch["model_shards"], ch["data_replicas"], ch["n_chips"]) \
        == (4, 2, 8)
    assert len(ch["per_chip"]) == 4
    # proportional split: chips sum back to the program totals
    assert sum(r["crossbars"] for r in ch["per_chip"]) \
        == pytest.approx(rep["crossbars"])
    assert sum(r["energy_pj"] for r in ch["per_chip"]) \
        == pytest.approx(rep["energy_pj"])
    assert ch["total_crossbars_all_chips"] \
        == pytest.approx(rep["crossbars"] * 2)
    # the bottleneck chip is never slower than the serial program
    assert 0 < ch["cycles_parallel"] <= rep["cycles"]
    assert ch["parallel_speedup"] >= 1.0
    # explicit n_chips= view without a recorded partition
    rep4 = prog.hardware_report(n_chips=4)
    assert rep4["chips"]["model_shards"] == 4
    assert rep4["chips"]["data_replicas"] == 1
    assert "chips" not in prog.hardware_report()
