"""Inference engine: lowering/executor parity, serialization, service."""

import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.mapping import map_layer
from repro.core.pruning import (
    build_dictionaries,
    magnitude_prune,
    project_params,
)
from repro.core.sparse import block_density
from repro.engine import (
    ClassifyRequest,
    EngineConfig,
    InferenceService,
    compile_network,
    execute,
    extract_patches,
    load_program,
    make_forward,
    save_program,
)
from repro.models.cnn import (
    cnn_apply,
    conv_weight_names,
    init_cnn,
    mini_cnn_config,
    vgg16_config,
)

BACKENDS = [("xla", None), ("pallas", True)]


def _pruned_net(cfg, seed=0, sparsity=0.7, num_patterns=4):
    params = init_cnn(cfg, jax.random.PRNGKey(seed))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, sparsity)
    dicts = build_dictionaries(params, names, num_patterns)
    return project_params(params, dicts)


@pytest.fixture(scope="module")
def mini():
    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params, bits = _pruned_net(cfg)
    return cfg, params, bits, compile_network(cfg, params, bits)


@pytest.mark.parametrize("c_in", [3, 16])  # K = 27 and 144: both sides
@pytest.mark.parametrize("order", ["channel", "tap"])
def test_extract_patches_matches_conv(rng, order, c_in):
    """im2col patches @ conv_matrix == lax conv (the lowering's premise),
    in either feature order, with the K padding the executor adds."""
    from repro.engine.lowering import conv_matrix

    x = jnp.asarray(rng.normal(size=(2, c_in, 6, 6)).astype(np.float32))
    w = rng.normal(size=(5, c_in, 3, 3)).astype(np.float32)
    k_in = -(-c_in * 9 // 128) * 128
    patches = extract_patches(x, 3, order, k_in)  # [B*H*W, k_in]
    assert patches.shape == (2 * 36, k_in)
    np.testing.assert_array_equal(np.asarray(patches[:, c_in * 9:]), 0.0)
    wm = np.zeros((k_in, 5), np.float32)
    wm[: c_in * 9] = conv_matrix(w, order)
    y = (patches @ jnp.asarray(wm)).reshape(2, 6, 6, 5).transpose(0, 3, 1, 2)
    ref = jax.lax.conv_general_dilated(
        x, jnp.asarray(w), (1, 1), "SAME",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def test_patch_order_rule():
    """Tap-major exactly where K spans more than one block: VGG16's conv1
    (27 rows) stays channel-major, conv2-13 go tap-major."""
    from repro.engine.lowering import patch_order

    assert patch_order(3, 3, 128) == "channel"
    assert patch_order(14, 3, 126) == "channel"  # K == block
    assert patch_order(15, 3, 128) == "tap"
    assert patch_order(64, 3, 128) == "tap"
    assert patch_order(8, 3, 9) == "tap"  # small blocks follow the rule too
    # a few-channel input stays channel-major at any K (a 7x7 RGB stem)
    assert patch_order(2, 3, 9) == "channel"
    assert patch_order(3, 7, 128) == "channel"
    cfg = vgg16_config(num_classes=10, input_hw=32)
    orders = [patch_order(ci, 3, 128) for ci, _ in cfg.conv_channels]
    assert orders == ["channel"] + ["tap"] * 12


def test_lowering_is_lossless(mini):
    """Compressed operands reconstruct the pruned dense weights exactly."""
    from repro.engine.lowering import conv_matrix

    cfg, params, bits, prog = mini
    for i, op in enumerate(prog.convs, start=1):
        wm = conv_matrix(np.asarray(params[f"conv{i}"]["w"]), op.patch_order)
        dense = np.asarray(op.bp.dense())[: wm.shape[0], : wm.shape[1]]
        np.testing.assert_array_equal(dense.astype(np.float32), wm)
        assert 0.0 < block_density(op.bp) <= 1.0


@pytest.mark.parametrize("backend,interpret", BACKENDS)
def test_mini_cnn_both_patch_orders_match_dense(mini, backend, interpret):
    """The mini net's widths put conv1-2 (K = 9, 72) channel-major and
    conv3 (K = 144) tap-major; the compiled forward matches the dense
    reference within the usual tolerance, and the schedule names each
    layer's order and stored bricks."""
    cfg, params, bits, prog = mini
    assert [op.patch_order for op in prog.convs] == ["channel", "channel",
                                                      "tap"]
    ops = dict(prog.op_list())
    for op in prog.convs:
        assert f"{op.patch_order}-major" in ops[op.name]
        assert f"bricks={int(np.sum(op.bp.nnz))} " in ops[op.name]
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 1, 12, 12))
    ref = cnn_apply(cfg, params, x)
    out = make_forward(prog, backend=backend, interpret=interpret)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-3)


def test_tap_major_drops_bricks_of_unused_taps(rng):
    """Kernels that use only taps 4 and 5 of 9: tap-major rows put those
    taps of all 64 channels in one 128-row block, so each tile stores one
    brick where channel-major rows (14 channels x 9 taps a block) store
    all five."""
    from repro.engine.lowering import conv_matrix, lower_matrix

    w = np.zeros((128, 64, 3, 3), np.float32)
    w[:, :, 1, 1:] = rng.normal(size=(128, 64, 2))  # taps 4, 5
    chan = lower_matrix(conv_matrix(w, "channel"), 128, 128)
    tap = lower_matrix(conv_matrix(w, "tap"), 128, 128)
    assert (int(chan.nnz.sum()), chan.k_max) == (5, 5)
    assert (int(tap.nnz.sum()), tap.k_max) == (1, 1)
    np.testing.assert_array_equal(
        np.asarray(tap.block_ids)[0, :1], [2]  # rows 256..383: taps 4, 5
    )


def test_tap_major_never_stores_more_bricks(vgg32):
    """On every tap-major layer of a pattern-pruned VGG16 the stored
    bricks are no more than channel-major rows would store, and fewer in
    total."""
    from repro.engine.lowering import conv_matrix, lower_matrix

    cfg, params, bits, prog = vgg32
    tap_total = chan_total = 0
    for i, op in enumerate(prog.convs, start=1):
        if op.patch_order != "tap":
            continue
        chan = lower_matrix(
            conv_matrix(np.asarray(params[f"conv{i}"]["w"])),
            op.bp.block, op.bp.tile,
        )
        tap_total += int(op.bp.nnz.sum())
        chan_total += int(chan.nnz.sum())
        assert int(op.bp.nnz.sum()) <= int(chan.nnz.sum()), op.name
        assert op.bp.k_max <= chan.k_max, op.name
    assert tap_total < chan_total


def test_zero_selection_counts_same_in_both_orders(rng):
    """The skip counters read (channel, tap) from either feature order and
    count exactly the same selections."""
    from repro.engine.executor import zero_selection_counts
    from repro.engine.stats import skip_patterns_and_masks

    c_in = 16
    x = rng.normal(size=(2, c_in, 6, 6)).astype(np.float32)
    x[rng.random(size=x.shape) < 0.6] = 0.0
    x[:, 3] = 0.0  # a dead channel
    bits = np.array([[0, 0b000011011, 0b111111111, 0b010111010]])
    _, masks = skip_patterns_and_masks(bits, 9)
    valid = jnp.asarray(np.repeat([True, False], 36))
    counts = {
        order: np.asarray(zero_selection_counts(
            extract_patches(jnp.asarray(x), 3, order), c_in, 9, masks,
            valid, order,
        ))
        for order in ("channel", "tap")
    }
    np.testing.assert_array_equal(counts["tap"], counts["channel"])
    assert (counts["tap"][3] == 36).all()


@pytest.mark.parametrize("backend,interpret", BACKENDS)
def test_mini_cnn_parity(mini, backend, interpret):
    cfg, params, bits, prog = mini
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 1, 12, 12))
    ref = cnn_apply(cfg, params, x)
    out = make_forward(prog, backend=backend, interpret=interpret)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-3)


@pytest.fixture(scope="module")
def vgg32():
    cfg = vgg16_config(num_classes=10, input_hw=32)
    params, bits = _pruned_net(cfg, seed=1, sparsity=0.86, num_patterns=8)
    return cfg, params, bits, compile_network(cfg, params, bits)


@pytest.mark.parametrize("backend,interpret", BACKENDS)
def test_vgg16_parity(vgg32, backend, interpret):
    cfg, params, bits, prog = vgg32
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 32, 32))
    ref = cnn_apply(cfg, params, x)
    out = make_forward(prog, backend=backend, interpret=interpret)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-3)


def test_engine_config_small_blocks(mini):
    """Non-default (block, tile) geometry stays exact."""
    cfg, params, bits, _ = mini
    prog = compile_network(cfg, params, bits,
                           ecfg=EngineConfig(block=9, tile=8))
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 1, 12, 12))
    ref = cnn_apply(cfg, params, x)
    out = make_forward(prog, backend="xla")(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-3)


def test_serialize_roundtrip_bit_exact(mini, tmp_path):
    cfg, params, bits, prog = mini
    path = save_program(str(tmp_path / "prog"), prog)
    prog2 = load_program(path)

    assert prog2.config == cfg
    assert (prog2.block, prog2.tile) == (prog.block, prog.tile)
    for a, b in zip(prog.convs, prog2.convs):
        assert (a.name, a.c_in, a.c_out, a.kernel, a.out_hw, a.pool,
                a.patch_order) \
            == (b.name, b.c_in, b.c_out, b.kernel, b.out_hw, b.pool,
                b.patch_order)
        np.testing.assert_array_equal(np.asarray(a.bp.w_comp),
                                      np.asarray(b.bp.w_comp))
        np.testing.assert_array_equal(np.asarray(a.bp.block_ids),
                                      np.asarray(b.bp.block_ids))
        np.testing.assert_array_equal(a.bp.nnz, b.bp.nnz)
        np.testing.assert_array_equal(a.bp.new_order, b.bp.new_order)
        np.testing.assert_array_equal(a.bp.inv_order, b.bp.inv_order)
        np.testing.assert_array_equal(a.bias, b.bias)
        np.testing.assert_array_equal(a.pattern_bits, b.pattern_bits)
    np.testing.assert_array_equal(np.asarray(prog.fc.bp.w_comp),
                                  np.asarray(prog2.fc.bp.w_comp))
    np.testing.assert_array_equal(prog.fc.bias, prog2.fc.bias)

    x = jax.random.normal(jax.random.PRNGKey(9), (3, 1, 12, 12))
    np.testing.assert_array_equal(
        np.asarray(execute(prog, x, backend="xla")),
        np.asarray(execute(prog2, x, backend="xla")),
    )


def test_manifest_v5_carries_patch_order(mini, tmp_path):
    """A save is format v5 and names each conv's patch order."""
    *_, prog = mini
    path = save_program(str(tmp_path / "prog"), prog)
    with open(os.path.join(path, "program.json")) as f:
        manifest = json.load(f)
    assert manifest["format_version"] == 5
    assert [e["patch_order"] for e in manifest["convs"]] \
        == [c.patch_order for c in prog.convs] == ["channel", "channel", "tap"]


@pytest.mark.parametrize("claims_tap", [False, True])
def test_v4_program_loads_channel_major(mini, tmp_path, claims_tap):
    """A format-v4 program (every conv lowered channel-major) loads
    channel-major and serves the logits it served before v5, even where
    its manifest claims a tap-major conv."""
    from conftest import channel_major, downgrade_manifest
    from repro.engine.lowering import conv_matrix, lower_matrix

    cfg, params, bits, prog = mini
    old = channel_major(prog)
    # the rebuilt operands are the ones the v4 compiler stored
    w3 = np.asarray(params["conv3"]["w"])
    np.testing.assert_array_equal(
        np.asarray(old.convs[2].bp.w_comp),
        np.asarray(lower_matrix(conv_matrix(w3), 128, 128).w_comp),
    )
    path = save_program(str(tmp_path / "v4"), old)
    manifest = downgrade_manifest(path, 4)
    if claims_tap:
        manifest["convs"][2]["patch_order"] = "tap"
        with open(os.path.join(path, "program.json"), "w") as f:
            json.dump(manifest, f)
    loaded = load_program(path)
    assert [c.patch_order for c in loaded.convs] == ["channel"] * 3
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 1, 12, 12))
    served = np.asarray(make_forward(loaded, backend="xla")(x))
    np.testing.assert_array_equal(
        served, np.asarray(make_forward(old, backend="xla")(x))
    )
    np.testing.assert_allclose(
        served, np.asarray(make_forward(prog, backend="xla")(x)),
        rtol=1e-5, atol=1e-5,
    )


def test_serialize_roundtrip_partition_metadata(mini, tmp_path):
    """A partitioned program reloads with its partition intact and still
    produces the identical forward output (golden, bit-exact)."""
    from repro.engine import NetworkPartition, partition_network

    cfg, params, bits, prog = mini
    progp = partition_network(prog, data=2, model=4)
    x = jax.random.normal(jax.random.PRNGKey(21), (3, 1, 12, 12))
    golden = np.asarray(execute(prog, x, backend="xla"))

    path = save_program(str(tmp_path / "prog_part"), progp)
    prog2 = load_program(path)
    assert prog2.partition == NetworkPartition(data=2, model=4)
    np.testing.assert_array_equal(
        np.asarray(execute(prog2, x, backend="xla")), golden
    )
    # the chips view survives the round trip via the partition
    rep = prog2.hardware_report()
    assert rep["chips"]["n_chips"] == 8

    # an unpartitioned program round-trips with no partition
    prog3 = load_program(save_program(str(tmp_path / "prog_plain"), prog))
    assert prog3.partition is None
    assert "chips" not in prog3.hardware_report()


def test_serialize_roundtrip_quantized(mini, tmp_path):
    """Quantized programs round-trip bit-exactly: int8 payloads, fp32
    row-group scales, precision/cell_bits and partition metadata all
    survive, and the reloaded program executes identically."""
    from repro.engine import partition_network

    cfg, params, bits, _ = mini
    progq = partition_network(
        compile_network(cfg, params, bits, precision="int8"), data=2, model=2
    )
    path = save_program(str(tmp_path / "progq"), progq)
    prog2 = load_program(path)

    assert prog2.precision == "int8"
    assert prog2.cell_bits == progq.cell_bits
    assert prog2.partition == progq.partition
    for a, b in zip([*progq.convs, progq.fc], [*prog2.convs, prog2.fc]):
        wa, wb = np.asarray(a.bp.w_comp), np.asarray(b.bp.w_comp)
        assert wa.dtype == wb.dtype == np.int8
        np.testing.assert_array_equal(wa, wb)
        sa, sb = np.asarray(a.bp.w_scales), np.asarray(b.bp.w_scales)
        assert sa.dtype == sb.dtype == np.float32
        np.testing.assert_array_equal(sa, sb)

    x = jax.random.normal(jax.random.PRNGKey(17), (3, 1, 12, 12))
    np.testing.assert_array_equal(
        np.asarray(execute(progq, x, backend="xla")),
        np.asarray(execute(prog2, x, backend="xla")),
    )


def test_save_is_atomic(mini, tmp_path):
    """A second save over an existing program replaces it cleanly."""
    *_, prog = mini
    path = save_program(str(tmp_path / "prog"), prog)
    path2 = save_program(str(tmp_path / "prog"), prog)
    assert path == path2
    load_program(path)  # still loadable, no stale .tmp / .old
    import os
    assert not os.path.exists(path + ".tmp")
    assert not os.path.exists(path + ".old")


def test_load_falls_back_to_old_after_interrupted_swap(mini, tmp_path):
    """A save killed between the two swap renames leaves the previous
    program at <dir>.old; load_program must still find it."""
    import os

    *_, prog = mini
    path = save_program(str(tmp_path / "prog"), prog)
    os.replace(path, path + ".old")  # simulate the crash window
    prog2 = load_program(path)
    np.testing.assert_array_equal(prog.fc.bias, prog2.fc.bias)


def test_service_matches_forward(mini):
    cfg, params, bits, prog = mini
    x = np.asarray(
        jax.random.normal(jax.random.PRNGKey(11), (8, 1, 12, 12)),
        np.float32,
    )
    svc = InferenceService(prog, batch_slots=8, backend="xla")
    labels = svc.classify(x)
    ref = np.asarray(make_forward(prog, backend="xla")(jnp.asarray(x)))
    np.testing.assert_array_equal(labels, ref.argmax(-1))
    assert svc.batches_run == 1

    # two generations: 16 requests through 8 slots
    reqs = [ClassifyRequest(image=img) for img in np.concatenate([x, x])]
    svc.serve(reqs)
    assert all(r.done and r.logits is not None for r in reqs)
    np.testing.assert_array_equal(
        [r.label for r in reqs[:8]], [r.label for r in reqs[8:]]
    )
    assert svc.batches_run == 3


def test_service_partial_batch_padded_with_dead_slots(mini):
    """A partial batch runs zero-padded at the fixed batch_slots shape:
    per-sample channel_norm keeps dead slots numerically inert, so the
    live rows are bit-identical to the same images inside the padded
    batch and match the natural-size forward to fp32 tolerance."""
    cfg, params, bits, prog = mini
    x = np.asarray(
        jax.random.normal(jax.random.PRNGKey(13), (3, 1, 12, 12)),
        np.float32,
    )
    svc = InferenceService(prog, batch_slots=8, backend="xla")
    reqs = [ClassifyRequest(image=img) for img in x]
    svc.serve(reqs)
    assert svc.batches_run == 1 and svc.trace_count() == 1
    got = np.stack([r.logits for r in reqs])
    padded = np.zeros((8, 1, 12, 12), np.float32)
    padded[:3] = x
    fixed = np.asarray(make_forward(prog, backend="xla")(jnp.asarray(padded)))
    np.testing.assert_array_equal(got, fixed[:3])
    natural = np.asarray(make_forward(prog, backend="xla")(jnp.asarray(x)))
    np.testing.assert_allclose(got, natural, rtol=1e-5, atol=1e-6)


def test_program_introspection(mini):
    """op_list covers the whole schedule; weight_bytes matches the bricks."""
    cfg, params, bits, prog = mini
    ops = prog.op_list()
    assert len(ops) == prog.num_ops == cfg.num_convs + 2
    assert [name for name, _ in ops[:-2]] \
        == [f"conv{i}" for i in range(1, cfg.num_convs + 1)]
    assert ops[-1][0] == "fc"

    comp, dense = prog.weight_bytes()
    expect_comp = sum(
        int(np.sum(op.bp.nnz)) * op.bp.block * op.bp.tile * 4
        for op in [*prog.convs, prog.fc]
    )
    expect_dense = 4 * (
        sum(c.c_in * 9 * c.c_out for c in prog.convs)
        + prog.fc.d_in * prog.fc.d_out
    )
    assert (comp, dense) == (expect_comp, expect_dense)


def test_hardware_report_consistent_with_mapping(mini):
    """Report crossbar counts == direct map_layer on the same bits."""
    cfg, params, bits, prog = mini
    rep = prog.hardware_report()
    expect = sum(
        map_layer(bits[f"conv{i}"]).num_crossbars
        for i in range(1, cfg.num_convs + 1)
    )
    assert rep["crossbars"] == expect
    assert rep["naive_crossbars"] >= rep["crossbars"]
    assert rep["energy_pj"] > 0 and rep["cycles"] > 0
    assert len(rep["layers"]) == cfg.num_convs
