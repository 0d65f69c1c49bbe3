"""ResNet v1.5 through the normal path: ``compile_network(verify="strict")``
-> ``make_forward`` -> ``classify_session``, against ``resnet_apply``.

The small ResNet (``resnet_small_config``) has the 7x7/2 stem, the 3x3/2
max pool, a projection shortcut at stride 1 and one at stride 2, and an
identity shortcut; its 3x3 convs are pattern-pruned, the rest dense.  It
runs at 32x32 and at the odd 33x33, where every stride-2 layer meets an
odd side.

Tolerance of the whole forward: ``max|logits - ref| / max|ref| <= 1e-5``.
Both sides compute in float32 from the same weights; they differ in the
order of their sums (im2col matmuls against XLA's convolution) and in
batch norm folded into the weights against applied after the conv, about
1e-7 relative per layer (the readings are 1e-7 to 3e-7).  A wrong padding,
stride, fold or shortcut moves the logits by 1e-2 or more.

The unit cases fail on the usual mistakes: JAX's ``"SAME"`` padding in
place of ``k // 2`` on each side, unfolded weights, a shortcut that
skips its projection, a max pool padded with zeros.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.diagnostics import VerificationError
from repro.analysis.verify import verify_network
from repro.core.mapping import CrossbarConfig, map_layer, map_layer_naive
from repro.core.ou import pattern_ou_schedule
from repro.core.pruning import build_dictionaries, magnitude_prune, project_params
from repro.core.crossbar import EnergyModel
from repro.engine import CompileOptions, compile_network, make_forward
from repro.engine.executor import extract_patches
from repro.engine.lowering import conv_matrix, fold_bn
from repro.engine.serialize import save_program
from repro.models.cnn import conv_out_hw, max_pool_3x3s2, out_sizes, pool_out_hw
from repro.models.resnet import (
    batch_norm,
    init_resnet,
    resnet50_v15_config,
    resnet_apply,
    resnet_small_config,
)
from repro.serve import Request, classify_session

REL_TOL = 1e-5
SMALL_GEOMETRY = {"block": 16, "tile": 8}


def _rel_err(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _pruned(cfg, seed=0):
    """Seeded params with every 3x3 conv pattern-pruned (4 patterns, 70%
    sparse before projection); the stem, 1x1 convs and FC stay dense."""
    params = init_resnet(cfg, jax.random.PRNGKey(seed))
    names = [s.name for s in cfg.layers() if s.kernel == 3]
    params = magnitude_prune(params, names, 0.7)
    params, bits = project_params(params, build_dictionaries(params, names, 4))
    return params, bits


@pytest.fixture(scope="module")
def small():
    cfg = resnet_small_config(input_hw=32)
    params, bits = _pruned(cfg)
    prog = compile_network(cfg, params, bits,
                           options=CompileOptions(verify="strict"))
    return cfg, params, bits, prog


def _images(cfg, n=3, seed=1):
    return jax.random.normal(
        jax.random.PRNGKey(seed), (n, cfg.in_channels, cfg.input_hw,
                                   cfg.input_hw)
    )


# ------------------------------------------------------------------ layers


def test_resnet50_layers_follow_table_1():
    cfg = resnet50_v15_config()
    layers = cfg.layers()
    convs = [s for s in layers if not s.name.endswith("downsample")]
    assert len(convs) == 49 and len(layers) == 53  # + 4 projections
    assert sum(s.kernel == 3 for s in layers) == 16
    assert layers[0].name == "stem" and layers[-1].name == "layer4.2.conv3"
    # v1.5: the stride sits on the 3x3 conv of each stage's first block
    strided = {s.name for s in layers if s.stride == 2}
    assert strided == {"stem"} | {
        f"layer{i}.0.{n}" for i in (2, 3, 4) for n in ("conv2", "downsample")
    }
    sizes = out_sizes(layers, 224)
    assert sizes["stem"] == (112, 56)
    assert [sizes[f"layer{i}.0.conv3"][1] for i in (1, 2, 3, 4)] \
        == [56, 28, 14, 7]
    params = sum(s.c_in * s.c_out * s.kernel**2 for s in layers)
    assert params + 2048 * 1000 + 1000 + 2 * sum(s.c_out for s in layers) \
        == 25_557_032  # torchvision's resnet50 parameter count


def test_the_full_resnet50_compiles_strict():
    """The full-width 224x224 ResNet-50 compiles under ``verify="strict"``
    with no error diagnostics, dense layers with every brick stored; the
    RGB stem stays channel-major (``lowering.patch_order``), the wide 1x1
    convs go tap-major."""
    cfg = resnet50_v15_config()
    params = jax.tree.map(np.asarray, init_resnet(cfg, jax.random.PRNGKey(0)))
    prog = compile_network(cfg, params, options=CompileOptions(verify="strict"))
    assert verify_network(prog).errors == []
    assert [c.name for c in prog.convs] == [s.name for s in cfg.layers()]
    stem = prog.convs[0]
    assert (stem.kernel, stem.stride, stem.out_hw, stem.patch_order) \
        == (7, 2, 112, "channel")
    assert stem.bp.k_in == 256 and int(np.sum(stem.bp.nnz)) == 2
    wide = {c.name: c for c in prog.convs}["layer2.0.conv1"]  # 256 -> 128
    assert (wide.kernel, wide.patch_order) == (1, "tap")
    assert int(np.sum(wide.bp.nnz)) == wide.bp.n_tiles * 2
    assert prog.certificate is not None


# ------------------------------------------------------------- the forward


@pytest.mark.parametrize("geometry", ["default", "small_bricks"])
@pytest.mark.parametrize("hw", [32, 33])
def test_compiled_resnet_matches_reference(hw, geometry):
    cfg = resnet_small_config(input_hw=hw)
    params, bits = _pruned(cfg)
    geo = SMALL_GEOMETRY if geometry == "small_bricks" else {}
    prog = compile_network(cfg, params, bits,
                           options=CompileOptions(verify="strict", **geo))
    if geometry == "small_bricks":
        # the 3x3 convs' K (36, 72) spans blocks: tap-major, with bricks
        # the patterns empty dropped
        conv2 = {c.name: c for c in prog.convs}["layer2.0.conv2"]
        assert conv2.patch_order == "tap"
        assert int(np.sum(conv2.bp.nnz)) < conv2.bp.n_tiles * (
            conv2.bp.k_in // conv2.bp.block)
    x = _images(cfg)
    err = _rel_err(make_forward(prog)(x), resnet_apply(cfg, params, x))
    assert err <= REL_TOL


def test_compiled_resnet_matches_reference_in_the_pallas_kernel(small):
    cfg, params, _, prog = small
    x = _images(cfg, n=2)
    out = make_forward(prog, backend="pallas", interpret=True)(x)
    assert _rel_err(out, resnet_apply(cfg, params, x)) <= REL_TOL


def test_classify_session_serves_the_resnet(small):
    cfg, params, _, prog = small
    x = np.asarray(_images(cfg, n=5))
    sess = classify_session(prog, batch_slots=4)
    sess.warmup()
    done = sess.run([Request(image=img) for img in x])
    got = np.stack([np.asarray(r.logits) for r in done])
    assert _rel_err(got, resnet_apply(cfg, params, x)) <= REL_TOL
    assert sess.trace_count() == 1


# -------------------------------------------------------------- unit cases


@pytest.mark.parametrize("order", ["channel", "tap"])
@pytest.mark.parametrize("k,stride", [(3, 2), (1, 2), (7, 2), (3, 1)])
@pytest.mark.parametrize("hw", [8, 9])
def test_strided_patches_match_conv(order, k, stride, hw, rng):
    """im2col at ``stride`` with ``k // 2`` padding on each side, in both
    patch orders, is ``lax.conv`` with explicit symmetric padding; for a
    stride-2 3x3 or 7x7 conv on an even side JAX's ``"SAME"`` pads
    ``(k // 2 - 1, k // 2)`` and gives another answer."""
    c, co = 5, 6
    x = jnp.asarray(rng.normal(size=(2, c, hw, hw)), jnp.float32)
    w = rng.normal(size=(co, c, k, k)).astype(np.float32)
    pad = k // 2

    def conv(padding):
        return np.asarray(jax.lax.conv_general_dilated(
            x, w, (stride, stride), padding,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=jax.lax.Precision.HIGHEST,
        ))

    want = conv(((pad, pad), (pad, pad)))
    ho = conv_out_hw(hw, k, stride)
    patches = extract_patches(x, k, order, stride=stride)
    assert patches.shape == (2 * ho * ho, c * k * k)
    got = np.asarray(jnp.matmul(patches, conv_matrix(w, order),
                                precision=jax.lax.Precision.HIGHEST))
    got = got.reshape(2, ho, ho, co).transpose(0, 3, 1, 2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    if stride == 2 and k > 1 and hw % 2 == 0:
        assert not np.allclose(got, conv("SAME"), rtol=1e-3, atol=1e-3)


def test_fold_bn_matches_conv_then_bn(rng):
    """conv with the folded weight plus the folded bias is conv then batch
    norm; the unfolded weight is not, and folding keeps pattern zeros."""
    w = rng.normal(size=(8, 4, 3, 3)).astype(np.float32)
    w[:, :, 0, 0] = 0.0
    bn = {
        "gamma": rng.uniform(0.5, 1.5, 8).astype(np.float32),
        "beta": rng.normal(0, 0.1, 8).astype(np.float32),
        "mean": rng.normal(0, 0.1, 8).astype(np.float32),
        "var": rng.uniform(0.5, 1.5, 8).astype(np.float32),
    }
    x = jnp.asarray(rng.normal(size=(2, 4, 6, 6)), jnp.float32)

    def conv(weight):
        return jax.lax.conv_general_dilated(
            x, weight, (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=jax.lax.Precision.HIGHEST,
        )

    want = np.asarray(batch_norm(conv(w), bn, 1e-5))
    wf, bf = fold_bn(w, bn, 1e-5)
    got = np.asarray(conv(wf)) + bf[None, :, None, None]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(conv(w)), want, atol=1e-2)
    assert np.all(wf[:, :, 0, 0] == 0.0)


def test_projection_shortcut_is_computed_and_checked(small):
    """The stride-2 block adds its projection, not its input: zeroing the
    projection's weights moves the logits, and both match the reference;
    a program whose conv3 adds the block's input instead fails V301."""
    cfg, params, bits, prog = small
    x = _images(cfg, n=2)
    name = "layer2.0.downsample"
    zeroed = {**params, name: {**params[name],
                               "w": jnp.zeros_like(params[name]["w"])}}
    prog0 = compile_network(cfg, zeroed, bits)
    out, out0 = make_forward(prog)(x), make_forward(prog0)(x)
    assert _rel_err(out0, resnet_apply(cfg, zeroed, x)) <= REL_TOL
    assert _rel_err(out, out0) > 1e-3
    convs = [dataclasses.replace(c, residual="layer1.0.conv3")
             if c.name == "layer2.0.conv3" else c for c in prog.convs]
    report = verify_network(dataclasses.replace(prog, convs=convs))
    assert report.rules("error") == {"V301"}
    assert any("residual layer1.0.conv3" in d.message for d in report.errors)


@pytest.mark.parametrize("hw", [8, 9])
def test_max_pool_3x3s2_pads_with_minus_inf(hw, rng):
    """At the border the window holds only in-bounds values: a map of
    negative values pools to negative values, where zero padding would
    give 0."""
    x = -1.0 - rng.uniform(size=(1, 2, hw, hw)).astype(np.float32)
    got = np.asarray(max_pool_3x3s2(jnp.asarray(x)))
    ho = pool_out_hw(hw, "max3s2")
    assert got.shape == (1, 2, ho, ho)
    want = np.empty_like(got)
    for i in range(ho):
        for j in range(ho):
            rows = slice(max(2 * i - 1, 0), min(2 * i + 2, hw))
            cols = slice(max(2 * j - 1, 0), min(2 * j + 2, hw))
            want[..., i, j] = x[..., rows, cols].max(axis=(-2, -1))
    np.testing.assert_array_equal(got, want)
    assert np.all(got < 0)


# --------------------------------------------- the rest of the program


def test_range_pass_follows_the_graph(small):
    """Folded batch norm is the spmm's bound; a residual add sums the two
    intervals before the ReLU; the logits of real inputs lie inside."""
    cfg, _, _, prog = small
    cert = prog.certificate
    for c in prog.convs:
        e = cert.layer(c.name)
        lo, hi = e.pre_lo, e.pre_hi
        if c.residual is not None:
            r = cert.layer(c.residual)
            lo, hi = lo + r.act_lo, hi + r.act_hi
        if c.activation == "relu":
            lo, hi = max(lo, 0.0), max(hi, 0.0)
        assert (e.act_lo, e.act_hi) == (lo, hi), c.name
    x = jnp.clip(_images(cfg, n=4), -3.0, 3.0)
    logits = np.asarray(make_forward(prog)(x))
    fc = cert.layer("fc")
    assert fc.pre_lo <= logits.min() and logits.max() <= fc.pre_hi


@pytest.mark.parametrize("layer", ["stem", "layer2.0.downsample"])
def test_hardware_report_prices_each_conv_at_its_own_geometry(small, layer):
    """A conv is priced at its own k*k rows and its own (strided)
    ``out_hw``: the naive crossbars are the ceil-tiling of its
    ``c_in*k*k x c_out*cells`` matrix, ours are ``map_layer``'s, and its
    energy is the per-window OU energy times ``out_hw**2`` windows."""
    _, _, _, prog = small
    conv = {c.name: c for c in prog.convs}[layer]
    want_hw = {"stem": 16, "layer2.0.downsample": 4}[layer]
    assert conv.out_hw == want_hw
    row = {r["name"]: r for r in prog.hardware_report()["layers"]}[layer]
    xb, energy = CrossbarConfig(), EnergyModel()
    kk = conv.kernel * conv.kernel
    assert row["naive_crossbars"] == (
        -(-conv.c_in * kk // xb.rows)
        * -(-conv.c_out * xb.cells_per_weight // xb.cols)
    ) == map_layer_naive(conv.c_out, conv.c_in, kk, xb).num_crossbars
    mapping = map_layer(conv.pattern_bits, xb, kk)
    assert row["crossbars"] == mapping.num_crossbars
    sched = pattern_ou_schedule(mapping)
    if layer == "stem":  # dense 7x7 kernels: 49-row blocks in 9-row bands
        assert sched.wordlines.max() == xb.ou_rows
    per_window = float(energy.ou_energy(sched.wordlines,
                                        sched.bitlines).sum())
    assert row["energy_pj"] == pytest.approx(per_window * want_hw**2)


def test_compile_spans_every_layer(small):
    """One ``lower:<name>`` compile span per conv and the FC, in order."""
    from repro.obs import Tracer

    cfg, params, bits, prog = small
    tr = Tracer()
    compile_network(cfg, params, bits, options=CompileOptions(tracer=tr))
    lowers = [s.name for s in tr.spans("compile")
              if s.name.startswith("lower:")]
    assert sorted(lowers) == sorted(
        [f"lower:{c.name}" for c in prog.convs] + ["lower:fc"]
    )


def test_saving_a_graph_program_names_the_gap(small, tmp_path):
    _, _, _, prog = small
    with pytest.raises(NotImplementedError, match="ResNetConfig"):
        save_program(str(tmp_path / "prog"), prog)


def test_resnet_runs_on_a_mesh(small):
    from repro.launch.mesh import make_mesh

    cfg, _, _, prog = small
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    x = _images(cfg, n=2)
    np.testing.assert_allclose(
        np.asarray(make_forward(prog, mesh=mesh)(x)),
        np.asarray(make_forward(prog)(x)), rtol=1e-5, atol=1e-5,
    )


def test_resnet_runs_in_int8(small):
    """The int8 program compiles strict (range pass over int8 operands)
    and agrees with fp32 to quantization error."""
    cfg, params, bits, prog = small
    q = compile_network(cfg, params, bits, options=CompileOptions(
        precision="int8", verify="strict"))
    x = _images(cfg, n=4)
    assert _rel_err(make_forward(q)(x), make_forward(prog)(x)) < 0.05


def test_resnet_collects_skip_statistics(small):
    """Every conv reports its counters over its own (strided) output
    positions; ReLU outputs feed zeros to the next layer's selections."""
    cfg, _, _, prog = small
    x = _images(cfg, n=2)
    logits, stats = make_forward(prog, collect_stats=True)(x)
    assert set(stats.layers) == {c.name for c in prog.convs}
    for c in prog.convs:
        assert stats.layers[c.name].windows == 2 * c.out_hw**2, c.name
    assert stats.layers["layer1.0.conv2"].mean_skip() > 0.0
    with pytest.raises(VerificationError):
        verify_network(dataclasses.replace(
            prog, convs=[dataclasses.replace(prog.convs[0], out_hw=17),
                         *prog.convs[1:]],
        )).raise_if_errors("test")
