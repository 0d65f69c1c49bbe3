"""Mutation tests for the static program verifier.

The contract pinned here: a pristine compiled/serialized program passes
with zero errors, and corrupting exactly one field flags exactly the
rule that guards it.  Each catalog entry is (name, mutator, expected
error-rule set); a seeded sweep also corrupts *random* sites of the
payload to show detection does not depend on a lucky index.  (Hypothesis
is not available in this environment, so the catalog + seeded sweep
stand in for its strategies.)
"""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from repro.analysis import ProgramFormatError, VerificationError
from repro.analysis.verify import (
    verify_bp,
    verify_network,
    verify_partition,
    verify_saved,
)
from repro.core.pruning import (
    build_dictionaries,
    magnitude_prune,
    project_params,
)
from repro.core.patterns import ALL_ZERO, pattern_sizes
from repro.engine import CompileOptions, compile_network, partition_network
from repro.engine.partition import NetworkPartition, pad_bp_tiles
from repro.engine import serialize
from repro.models.cnn import conv_weight_names, init_cnn, mini_cnn_config


@pytest.fixture(scope="module")
def pruned():
    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, 0.7)
    dicts = build_dictionaries(params, names, 4)
    params, bits = project_params(params, dicts)
    return cfg, params, bits


@pytest.fixture(scope="module")
def prog_fp32(pruned):
    cfg, params, bits = pruned
    return compile_network(cfg, params, bits,
                           options=CompileOptions(block=16, tile=16))


@pytest.fixture(scope="module")
def prog_int8(pruned):
    cfg, params, bits = pruned
    return compile_network(
        cfg, params, bits,
        options=CompileOptions(block=16, tile=16, precision="int8"),
    )


def _with_bp(prog, bp):
    conv0 = dataclasses.replace(prog.convs[0], bp=bp)
    return dataclasses.replace(prog, convs=[conv0] + prog.convs[1:])


def _np(bp, field):
    return np.array(getattr(bp, field))  # mutable host copy


def _active_slot(bp):
    """(tile, slot) of an active brick with nonzero weights."""
    w = _np(bp, "w_comp")
    nnz = _np(bp, "nnz")
    for t in range(w.shape[0]):
        for k in range(int(nnz[t])):
            if np.any(w[t, k] != 0):
                return t, k
    raise AssertionError("fixture has no active nonzero brick")


def test_pristine_programs_verify_clean(prog_fp32, prog_int8):
    for prog in (prog_fp32, prog_int8):
        report = verify_network(prog)
        assert report.ok, report.format()
        assert prog.verify(strict=True).ok


# ---------------------------------------------------------------------------
# operand-level mutation catalog
# ---------------------------------------------------------------------------


def _mut_perm_duplicate(bp, rng):
    order = _np(bp, "new_order")
    i, j = rng.choice(len(order), size=2, replace=False)
    order[i] = order[j]  # no longer a bijection
    return dataclasses.replace(bp, new_order=order)


def _mut_perm_swap(bp, rng):
    order = _np(bp, "new_order")
    i, j = rng.choice(len(order), size=2, replace=False)
    order[[i, j]] = order[[j, i]]  # still a bijection, inverse now stale
    return dataclasses.replace(bp, new_order=order)


def _mut_geometry(bp, rng):
    return dataclasses.replace(bp, k_in=bp.k_in + 1)


def _mut_brick_shape(bp, rng):
    return dataclasses.replace(bp, w_comp=_np(bp, "w_comp")[:, :, :, :-1])


def _mut_blockid_oob(bp, rng):
    ids = _np(bp, "block_ids")
    t = rng.integers(ids.shape[0])
    ids[t, 0] = bp.k_in // bp.block  # one past the last row group
    return dataclasses.replace(bp, block_ids=ids)


def _mut_nnz_over(bp, rng):
    nnz = _np(bp, "nnz")
    nnz[rng.integers(len(nnz))] = bp.w_comp.shape[1] + 1
    return dataclasses.replace(bp, nnz=nnz)


def _mut_padded_brick(bp, rng):
    bp = pad_bp_tiles(bp, bp.n_tiles + 1)  # appends >=1 inert tile
    w = _np(bp, "w_comp")
    w[-1, 0, 0, 0] = 3.0 if bp.w_scales is None else 3
    return dataclasses.replace(bp, w_comp=w)


def _mut_dict_masks(bp, rng):
    return dataclasses.replace(bp, dict_masks=_np(bp, "dict_masks")[:, :-1])


OPERAND_MUTATIONS = [
    ("perm-not-bijective", _mut_perm_duplicate, {"V101"}),
    ("perm-inverse-stale", _mut_perm_swap, {"V102"}),
    ("geometry-indivisible", _mut_geometry, {"V103"}),
    ("brick-shape", _mut_brick_shape, {"V104"}),
    ("blockid-out-of-bounds", _mut_blockid_oob, {"V105"}),
    ("nnz-over-capacity", _mut_nnz_over, {"V106"}),
    ("padded-brick-nonzero", _mut_padded_brick, {"V107"}),
    ("dict-mask-shape", _mut_dict_masks, {"V109"}),
]


@pytest.mark.parametrize(
    "name,mutate,expected",
    OPERAND_MUTATIONS,
    ids=[m[0] for m in OPERAND_MUTATIONS],
)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_operand_mutation_flags_rule(prog_fp32, name, mutate, expected, seed):
    rng = np.random.default_rng(seed)
    bp = mutate(prog_fp32.convs[0].bp, rng)
    report = verify_bp(bp, layer="conv1")
    assert report.rules("error") == expected, report.format()


@pytest.mark.parametrize(
    "name,mutate,expected",
    OPERAND_MUTATIONS,
    ids=[m[0] for m in OPERAND_MUTATIONS],
)
def test_operand_mutation_caught_at_network_level(
    prog_fp32, name, mutate, expected
):
    rng = np.random.default_rng(0)
    prog = _with_bp(prog_fp32, mutate(prog_fp32.convs[0].bp, rng))
    report = verify_network(prog)
    assert expected <= report.rules("error"), report.format()
    assert all(d.layer == "conv1" for d in report.errors
               if d.rule in expected)
    with pytest.raises(VerificationError) as ei:
        prog.verify(strict=True)
    assert ei.value.report.rules("error") >= expected


def test_blockid_order_is_a_warning_not_error(prog_fp32):
    for conv in prog_fp32.convs:
        bp = conv.bp
        nnz = _np(bp, "nnz")
        tiles = np.flatnonzero(nnz >= 2)
        if tiles.size:
            break
    assert tiles.size, "fixture needs a tile with >= 2 active bricks"
    ids = _np(bp, "block_ids")
    t = int(tiles[0])
    ids[t, [0, 1]] = ids[t, [1, 0]]  # valid set, non-canonical order
    report = verify_bp(dataclasses.replace(bp, block_ids=ids), layer="x")
    assert report.ok
    assert "V108" in report.rules("warning")


# ---------------------------------------------------------------------------
# quantized-path mutations
# ---------------------------------------------------------------------------


def _mut_scale_shape(bp, rng):
    return dataclasses.replace(bp, w_scales=_np(bp, "w_scales")[:, :-1])


def _mut_scale_nan(bp, rng):
    s = _np(bp, "w_scales")
    t, k = _active_slot(bp)
    s[t, k] = np.nan
    return dataclasses.replace(bp, w_scales=s)


def _mut_scale_zero(bp, rng):
    s = _np(bp, "w_scales")
    t, k = _active_slot(bp)
    s[t, k] = 0.0  # silently drops a nonzero brick
    return dataclasses.replace(bp, w_scales=s)


def _mut_dtype(bp, rng):
    return dataclasses.replace(
        bp, w_comp=_np(bp, "w_comp").astype(np.float32)
    )


def _mut_minus_128(bp, rng):
    w = _np(bp, "w_comp")
    t, k = _active_slot(bp)
    w[t, k, 0, 0] = -128  # out of symmetric range AND breaks cell slicing
    return dataclasses.replace(bp, w_comp=w)


QUANT_MUTATIONS = [
    ("scale-shape", _mut_scale_shape, {"V110"}),
    ("scale-nan", _mut_scale_nan, {"V111"}),
    ("scale-zero-drops-brick", _mut_scale_zero, {"V112"}),
    ("quant-dtype", _mut_dtype, {"V113"}),
    ("minus-128-range-and-roundtrip", _mut_minus_128, {"V113", "V114"}),
]


@pytest.mark.parametrize(
    "name,mutate,expected",
    QUANT_MUTATIONS,
    ids=[m[0] for m in QUANT_MUTATIONS],
)
def test_quantized_mutation_flags_rule(prog_int8, name, mutate, expected):
    rng = np.random.default_rng(0)
    bp = mutate(prog_int8.convs[0].bp, rng)
    report = verify_bp(bp, layer="conv1")
    assert report.rules("error") == expected, report.format()


def test_fp32_nonfinite_weight(prog_fp32):
    bp = prog_fp32.convs[0].bp
    w = _np(bp, "w_comp")
    t, k = _active_slot(bp)
    w[t, k, 0, 0] = np.nan
    report = verify_bp(dataclasses.replace(bp, w_comp=w), layer="x")
    assert report.rules("error") == {"V115"}, report.format()


# ---------------------------------------------------------------------------
# layer/network/partition mutations
# ---------------------------------------------------------------------------


def test_pattern_bits_out_of_window(prog_fp32):
    conv0 = prog_fp32.convs[0]
    bits = np.array(conv0.pattern_bits)
    bits[0, 0] = 1 << (conv0.kernel * conv0.kernel)  # one past the window
    prog = dataclasses.replace(
        prog_fp32,
        convs=[dataclasses.replace(conv0, pattern_bits=bits)]
        + prog_fp32.convs[1:],
    )
    assert verify_network(prog).rules("error") == {"V202"}


def test_pattern_bits_shape(prog_fp32):
    conv0 = prog_fp32.convs[0]
    prog = dataclasses.replace(
        prog_fp32,
        convs=[dataclasses.replace(
            conv0, pattern_bits=np.array(conv0.pattern_bits)[:, :0]
        )] + prog_fp32.convs[1:],
    )
    assert verify_network(prog).rules("error") == {"V201"}


@pytest.mark.parametrize(
    "layer,order,expected",
    [
        (0, "tap", {"V207"}),  # K = 9 fits one block of 16
        (1, "zigzag", {"V207"}),
        (1, "channel", set()),  # v1-v4 programs: channel-major everywhere
        (1, "tap", set()),
    ],
    ids=["tap-on-one-block", "unknown-order", "channel-on-many-blocks",
         "tap-on-many-blocks"],
)
def test_patch_order_rule(prog_fp32, layer, order, expected):
    assert [c.patch_order for c in prog_fp32.convs] == ["channel", "tap",
                                                         "tap"]
    convs = list(prog_fp32.convs)
    convs[layer] = dataclasses.replace(convs[layer], patch_order=order)
    report = verify_network(dataclasses.replace(prog_fp32, convs=convs))
    assert report.rules("error") == expected, report.format()
    assert all(d.layer == convs[layer].name for d in report.errors)


def test_bias_shape(prog_fp32):
    conv0 = prog_fp32.convs[0]
    prog = dataclasses.replace(
        prog_fp32,
        convs=[dataclasses.replace(conv0, bias=conv0.bias[:-1])]
        + prog_fp32.convs[1:],
    )
    assert verify_network(prog).rules("error") == {"V204"}


def test_layer_chain_break(prog_fp32):
    fc = dataclasses.replace(
        prog_fp32.fc,
        d_out=prog_fp32.fc.d_out + 1,
        bias=np.zeros(prog_fp32.fc.d_out + 1, np.float32),
    )
    prog = dataclasses.replace(prog_fp32, fc=fc)
    assert verify_network(prog).rules("error") == {"V301"}


def test_precision_contract(prog_fp32):
    prog = dataclasses.replace(prog_fp32, precision="int8")
    assert verify_network(prog).rules("error") == {"V302"}


def test_program_tile_disagreement(prog_fp32):
    prog = dataclasses.replace(prog_fp32, tile=8)
    assert verify_network(prog).rules("error") == {"V303"}


def test_partition_same_axis(prog_fp32):
    part = NetworkPartition(data=2, model=2, data_axis="x", model_axis="x")
    report = verify_partition(prog_fp32, part)
    assert report.rules("error") == {"V403"}
    with pytest.raises(VerificationError):
        partition_network(prog_fp32, data=2, model=2,
                          data_axis="x", model_axis="x")


def test_partition_nonpositive(prog_fp32):
    part = NetworkPartition(data=1, model=1)
    object.__setattr__(part, "model", 0)  # bypass __post_init__
    assert verify_partition(prog_fp32, part).rules("error") == {"V401"}


def test_partition_valid_passes(prog_fp32):
    prog = partition_network(prog_fp32, data=2, model=4)
    assert verify_network(prog).ok


def test_compile_network_verify_modes(pruned):
    cfg, params, bits = pruned
    def opts(verify):
        return CompileOptions(block=16, tile=16, verify=verify)

    prog = compile_network(cfg, params, bits, options=opts("strict"))
    assert verify_network(prog).ok
    compile_network(cfg, params, bits, options=opts("warn"))
    with pytest.raises(ValueError, match="verify must be"):
        compile_network(cfg, params, bits, options=opts("bogus"))


# ---------------------------------------------------------------------------
# searched-mapping mutations (V205/V206)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def prog_auto(pruned):
    cfg, params, bits = pruned
    return compile_network(
        cfg, params, bits,
        options=CompileOptions(block=16, tile=16, optimize="auto"),
    )


def _with_mapping(prog, **kw):
    """First conv's mapping candidate with fields overridden."""
    conv0 = prog.convs[0]
    assert conv0.mapping is not None
    mapped = dataclasses.replace(conv0.mapping, **kw)
    conv0 = dataclasses.replace(conv0, mapping=mapped)
    return dataclasses.replace(prog, convs=[conv0] + prog.convs[1:])


def test_pristine_searched_program_verifies_clean(prog_auto):
    assert all(c.mapping is not None for c in prog_auto.convs)
    report = verify_network(prog_auto)
    assert report.ok, report.format()


MAPPING_MUTATIONS = [
    ("bad-block-order-tag", dict(block_order="bogus"), {"V205"}),
    ("bad-reorder-tag", dict(reorder="zigzag"), {"V205"}),
    ("non-positive-rows", dict(rows=0), {"V205"}),
    ("non-positive-ou-cols", dict(ou_cols=-8), {"V205"}),
    ("ou-taller-than-crossbar", dict(ou_rows=4096, rows=512), {"V206"}),
    ("ou-wider-than-crossbar", dict(ou_cols=4096, cols=512), {"V206"}),
    ("cells-exceed-row", dict(cells_per_weight=10**6), {"V206"}),
]


@pytest.mark.parametrize(
    "name,fields,expected",
    MAPPING_MUTATIONS,
    ids=[m[0] for m in MAPPING_MUTATIONS],
)
def test_mapping_mutation_flags_rule(prog_auto, name, fields, expected):
    prog = _with_mapping(prog_auto, **fields)
    report = verify_network(prog)
    assert report.rules("error") == expected, report.format()
    assert all(d.layer == "conv1" for d in report.errors)


def test_mapping_ou_cannot_hold_tallest_pattern(prog_auto):
    """ou_rows below the layer's tallest pattern block is unrealizable:
    pattern_ou_schedule never splits a block across OU row groups."""
    bits = np.asarray(prog_auto.convs[0].pattern_bits)
    max_h = int(pattern_sizes(bits)[bits != ALL_ZERO].max())
    assert max_h >= 2, "fixture needs a pattern taller than one row"
    prog = _with_mapping(prog_auto, ou_rows=max_h - 1)
    report = verify_network(prog)
    assert report.rules("error") == {"V206"}, report.format()


def test_mapping_int8_cell_slice_mismatch(pruned):
    cfg, params, bits = pruned
    prog = compile_network(
        cfg, params, bits,
        options=CompileOptions(block=16, tile=16, precision="int8",
                               optimize="auto"),
    )
    assert verify_network(prog).ok
    bad = _with_mapping(prog, cells_per_weight=1)
    report = verify_network(bad)
    assert report.rules("error") == {"V206"}, report.format()
    assert any("cell-slice" in d.message for d in report.errors)


def test_fc_reorder_bad_tag(prog_auto):
    fc = dataclasses.replace(prog_auto.fc, reorder="bogus")
    prog = dataclasses.replace(prog_auto, fc=fc)
    report = verify_network(prog)
    assert report.rules("error") == {"V205"}, report.format()
    assert all(d.layer == "fc" for d in report.errors)


def test_searched_program_full_pipeline_clean(prog_auto, tmp_path):
    """compile(optimize) -> partition -> save -> load -> verify, clean at
    every stage."""
    prog = partition_network(prog_auto, data=2, model=2)
    path = os.path.join(tmp_path, "prog_auto")
    serialize.save_program(path, prog)
    assert verify_saved(path).ok
    loaded = serialize.load_program(path)  # verify=True default
    assert verify_network(loaded).ok
    assert [c.mapping for c in loaded.convs] == \
        [c.mapping for c in prog_auto.convs]


# ---------------------------------------------------------------------------
# serialized programs: manifest statics + load-time verification
# ---------------------------------------------------------------------------


@pytest.fixture()
def saved(prog_int8, tmp_path):
    path = os.path.join(tmp_path, "prog")
    serialize.save_program(path, prog_int8)
    return path


def _manifest(path):
    with open(os.path.join(path, "program.json")) as f:
        return json.load(f)


def _rewrite(path, manifest):
    with open(os.path.join(path, "program.json"), "w") as f:
        json.dump(manifest, f)


def test_saved_pristine_roundtrip(saved):
    assert verify_saved(saved).ok
    prog = serialize.load_program(saved)  # verify=True default
    assert verify_network(prog).ok


@pytest.mark.parametrize(
    "corrupt,rule",
    [
        (lambda p: _rewrite(p, {**_manifest(p), "format_version": 99}),
         "M002"),
        (lambda p: _rewrite(
            p, {k: v for k, v in _manifest(p).items() if k != "fc"}
        ), "M003"),
        (lambda p: os.remove(os.path.join(p, "conv1.bias.npy")), "M004"),
        (lambda p: open(
            os.path.join(p, "program.json"), "w"
        ).write("{truncated"), "M001"),
        (lambda p: open(
            os.path.join(p, "fc.w_comp.npy"), "wb"
        ).write(b"not-an-npy"), "M005"),
        (lambda p: _rewrite(p, {**_manifest(p), "convs": [
            {k: v for k, v in e.items() if k != "patch_order"}
            for e in _manifest(p)["convs"]
        ]}), "M003"),
        (lambda p: _rewrite(p, {**_manifest(p), "fc": {
            k: v for k, v in _manifest(p)["fc"].items() if k != "in_order"
        }}), "M003"),
        (lambda p: _rewrite(p, {
            k: v for k, v in _manifest(p).items() if k != "cell_bits"
        }), "M003"),
    ],
    ids=["bad-version", "missing-key", "missing-payload", "truncated-json",
         "corrupt-payload", "missing-patch-order", "missing-fc-in-order",
         "missing-cell-bits"],
)
def test_corrupt_saved_program(saved, corrupt, rule):
    corrupt(saved)
    with pytest.raises(ProgramFormatError) as ei:
        serialize.load_program(saved)
    assert ei.value.rule == rule
    report = verify_saved(saved)
    assert report.rules("error") == {rule}, report.format()


@pytest.mark.parametrize("version", [1, 2, 3, 4, 5])
def test_old_manifest_versions_are_refused(saved, version):
    """A manifest of an older format is refused by its version (M002)
    before any payload is read: even a corrupt payload does not surface."""
    _rewrite(saved, {**_manifest(saved), "format_version": version})
    with open(os.path.join(saved, "fc.w_comp.npy"), "wb") as f:
        f.write(b"not-an-npy")
    with pytest.raises(ProgramFormatError, match="recompile") as ei:
        serialize.load_program(saved)
    assert ei.value.rule == "M002"
    assert verify_saved(saved).rules("error") == {"M002"}


def test_load_rejects_unknown_patch_order(saved):
    manifest = _manifest(saved)
    manifest["convs"][1]["patch_order"] = "diagonal"
    _rewrite(saved, manifest)
    with pytest.raises(VerificationError) as ei:
        serialize.load_program(saved)
    assert ei.value.report.rules("error") == {"V207"}
    assert verify_saved(saved).rules("error") == {"V207"}


def test_load_verifies_semantic_corruption(saved):
    # swap two permutation entries inside the stored payload: the file is
    # structurally valid (every M-rule passes) but semantically wrong
    fname = os.path.join(saved, "conv1.new_order.npy")
    order = np.load(fname)
    order[[0, 1]] = order[[1, 0]]
    np.save(fname, order)
    with pytest.raises(VerificationError) as ei:
        serialize.load_program(saved)
    assert "V102" in ei.value.report.rules("error")
    # opt-out still loads the raw payload
    prog = serialize.load_program(saved, verify=False)
    assert prog.convs
    assert verify_saved(saved).rules("error") == {"V102"}


# ---------------------------------------------------------------------------
# serialized mapping metadata (format v3)
# ---------------------------------------------------------------------------


@pytest.fixture()
def saved_auto(prog_auto, tmp_path):
    path = os.path.join(tmp_path, "prog_auto")
    serialize.save_program(path, prog_auto)
    return path


def _mutate_manifest(path, fn):
    m = _manifest(path)
    fn(m)
    _rewrite(path, m)


@pytest.mark.parametrize(
    "corrupt",
    [
        lambda m: m["convs"][0].__setitem__("mapping", "hybrid"),
        lambda m: m["convs"][0]["mapping"].pop("rows"),
        lambda m: m["convs"][0]["mapping"].__setitem__("block_order", 5),
        lambda m: m["convs"][0]["mapping"].__setitem__("rows", True),
        lambda m: m["fc"].__setitem__("reorder", 7),
    ],
    ids=["mapping-not-a-dict", "mapping-key-missing",
         "block-order-not-a-string", "rows-bool-not-int",
         "fc-reorder-not-a-string"],
)
def test_corrupt_mapping_manifest_is_structural(saved_auto, corrupt):
    _mutate_manifest(saved_auto, corrupt)
    with pytest.raises(ProgramFormatError) as ei:
        serialize.load_program(saved_auto)
    assert ei.value.rule == "M003"
    report = verify_saved(saved_auto)
    assert report.rules("error") == {"M003"}, report.format()


@pytest.mark.parametrize(
    "corrupt,rule",
    [
        (lambda m: m["convs"][0]["mapping"].__setitem__(
            "block_order", "bogus"), "V205"),
        (lambda m: m["convs"][0]["mapping"].__setitem__(
            "reorder", "zigzag"), "V205"),
        (lambda m: m["convs"][0]["mapping"].__setitem__(
            "ou_cols", 4096), "V206"),
    ],
    ids=["stored-bad-block-order", "stored-bad-reorder",
         "stored-ou-wider-than-crossbar"],
)
def test_corrupt_mapping_manifest_is_semantic(saved_auto, corrupt, rule):
    """A type-correct but invalid stored candidate passes the structural
    M-rules and is caught by the semantic verifier at load."""
    _mutate_manifest(saved_auto, corrupt)
    with pytest.raises(VerificationError) as ei:
        serialize.load_program(saved_auto)
    assert rule in ei.value.report.rules("error")
    report = verify_saved(saved_auto)
    assert report.rules("error") == {rule}, report.format()
    # opt-out still loads the raw payload
    assert serialize.load_program(saved_auto, verify=False).convs


# ---------------------------------------------------------------------------
# seeded random-site sweep (hypothesis-style corruption of one field)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(8))
def test_random_single_field_corruption_is_caught(prog_int8, seed):
    rng = np.random.default_rng(seed)
    bp = prog_int8.convs[0].bp
    family = rng.integers(4)
    if family == 0:  # corrupt a random permutation entry
        order = _np(bp, "new_order")
        order[rng.integers(len(order))] += 1
        bp = dataclasses.replace(bp, new_order=order % len(order))
        expect = {"V101", "V102"}
    elif family == 1:  # corrupt a random block id
        ids = _np(bp, "block_ids")
        t = rng.integers(ids.shape[0])
        ids[t, 0] = bp.k_in // bp.block + rng.integers(3)
        bp = dataclasses.replace(bp, block_ids=ids)
        expect = {"V105"}
    elif family == 2:  # shift a random nnz (row-group count)
        nnz = _np(bp, "nnz")
        nnz[rng.integers(len(nnz))] = -1 - rng.integers(3)
        bp = dataclasses.replace(bp, nnz=nnz)
        expect = {"V106"}
    else:  # zero a random active scale over a nonzero brick
        s = _np(bp, "w_scales")
        t, k = _active_slot(bp)
        s[t, k] = 0.0
        bp = dataclasses.replace(bp, w_scales=s)
        expect = {"V112"}
    report = verify_bp(bp, layer="conv1")
    assert report.rules("error") & expect, (
        f"seed {seed} family {family}: {report.format()}"
    )


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_verify(saved, capsys):
    from repro.analysis.__main__ import main

    assert main(["verify", saved]) == 0
    out = capsys.readouterr().out
    assert "clean" in out
    assert main(["verify", saved, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["ok"] is True and doc["errors"] == 0

    fname = os.path.join(saved, "conv1.new_order.npy")
    order = np.load(fname)
    order[[0, 1]] = order[[1, 0]]
    np.save(fname, order)
    assert main(["verify", saved, "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["errors"] >= 1
    assert any(d["rule"] == "V102" for d in doc["diagnostics"])
