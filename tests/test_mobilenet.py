"""MobileNetV2 through the normal path: ``compile_network(verify="strict")``
-> ``make_forward`` -> ``classify_session``, against ``mobilenet_apply``.

The small MobileNetV2 (``mobilenet_small_config``) has the 3x3/2 stem, a
``t = 1`` block, a stride-2 block, a block with the residual add and the
1x1 head; its depthwise convs are pattern-pruned (8 patterns of 4 taps,
the centre among them), the rest dense.  It runs at 32x32 and at the odd
33x33, where every stride-2 layer meets an odd side.

Tolerance of the whole forward: ``max|logits - ref| / max|ref| <= 1e-5``,
as for the ResNet.  Both sides compute in float32 from the same weights;
they differ in the order of their sums (im2col matmuls of block-diagonal
matrices against XLA's grouped convolution) and in batch norm folded
into the weights against applied after the conv, about 1e-7 relative per
layer (the readings are 1e-7 to 3e-7).  A wrong group, padding, clip or
activation after the add moves the logits by 1e-3 or more.

The unit cases each fail on a named mistake: a block-diagonal matrix
built in the wrong patch or channel order, ReLU in place of ReLU6, a
ReLU after the linear bottleneck's add, batch norm folded over the wrong
axis, and the producer of a depthwise conv left in channel order.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.verify import verify_network
from repro.core.mapping import CrossbarConfig, map_layer, map_layer_naive
from repro.engine import (
    CompileOptions,
    InferenceService,
    compile_network,
    make_forward,
)
from repro.engine import lowering
from repro.engine.executor import STAGES, extract_patches
from repro.engine.lowering import conv_matrix, fold_bn, group_dense
from repro.engine.serialize import save_program
from repro.models.cnn import out_sizes
from repro.models.mobilenet import (
    MobileNetV2Config,
    init_mobilenet,
    mobilenet_apply,
    mobilenet_small_config,
    mobilenet_v2_config,
)
from repro.models.resnet import batch_norm
from repro.obs import Tracer
from repro.serve import Request, classify_session

REL_TOL = 1e-5
CENTRE = 4


def _rel_err(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _pruned(cfg, seed=0, n_patterns=8, taps=4):
    """Seeded params (numpy) with every depthwise conv pattern-pruned:
    ``n_patterns`` distinct patterns of ``taps`` taps, each holding the
    centre tap, one per kernel drawn uniformly.  Returns ``(params,
    pattern_bits)``."""
    params = jax.tree.map(np.asarray, init_mobilenet(cfg, jax.random.PRNGKey(seed)))
    rng = np.random.default_rng(seed)
    others = [t for t in range(9) if t != CENTRE]
    bits = {}
    for spec in cfg.layers():
        if not spec.depthwise:
            continue
        pats: list[int] = []
        while len(pats) < n_patterns:
            pick = rng.choice(others, taps - 1, replace=False)
            b = (1 << CENTRE) | int(np.sum(1 << pick))
            if b not in pats:
                pats.append(b)
        b = np.asarray(pats, np.int64)[rng.integers(0, n_patterns, spec.c_out)]
        masks = (b[:, None] >> np.arange(9)) & 1
        p = params[spec.name]
        p["w"] = (p["w"] * masks.reshape(spec.c_out, 1, 3, 3)).astype(np.float32)
        bits[spec.name] = b[:, None]
    return params, bits


@pytest.fixture(scope="module")
def small():
    cfg = mobilenet_small_config(input_hw=32)
    params, bits = _pruned(cfg)
    prog = compile_network(cfg, params, bits,
                           options=CompileOptions(verify="strict"))
    return cfg, params, bits, prog


def _images(cfg, n=3, seed=1):
    return jax.random.normal(
        jax.random.PRNGKey(seed), (n, cfg.in_channels, cfg.input_hw,
                                   cfg.input_hw)
    )


def _replace_conv(prog, name, **fields):
    return dataclasses.replace(prog, convs=[
        dataclasses.replace(c, **fields) if c.name == name else c
        for c in prog.convs
    ])


# ------------------------------------------------------------------ layers


def test_mobilenet_v2_layers_follow_table_2():
    cfg = mobilenet_v2_config()
    layers = cfg.layers()
    assert len(layers) == 52
    dw = [s for s in layers if s.depthwise]
    assert len(dw) == 17 and all(s.kernel == 3 for s in dw)
    assert sum(s.residual is not None for s in layers) == 10
    names = [s.name for s in layers]
    assert names[:3] == ["features.0", "features.1.conv.0",
                         "features.1.conv.1"]
    assert names[3:6] == ["features.2.conv.0", "features.2.conv.1",
                          "features.2.conv.2"]
    assert names[-1] == "features.18"
    # the linear bottleneck: a projection has no activation, every other
    # conv clips at 6
    for s in layers:
        linear = s.name.endswith((".conv.1", ".conv.2")) and not s.depthwise
        assert s.activation == (None if linear else "relu6"), s.name
    sizes = out_sizes(layers, 224)
    assert sizes["features.0"][0] == 112
    assert sizes["features.17.conv.2"][0] == sizes["features.18"][0] == 7
    params = sum(s.c_in // s.groups * s.c_out * s.kernel**2 for s in layers)
    assert params + 1280 * 1000 + 1000 + 2 * sum(s.c_out for s in layers) \
        == 3_504_872  # torchvision's mobilenet_v2 parameter count
    macs = sum(sizes[s.name][0] ** 2 * s.c_in // s.groups * s.c_out
               * s.kernel**2 for s in layers) + 1280 * 1000
    assert 299e6 < macs < 302e6  # Table 3: 300M multiply-adds


def test_the_full_mobilenet_v2_compiles_strict():
    """The full-width 224x224 MobileNetV2 compiles under ``verify="strict"``
    with no error diagnostics and no channel gather; its spans report
    every depthwise conv's bricks, fewer in pattern order than the
    channel order would store."""
    cfg = mobilenet_v2_config()
    params, bits = _pruned(cfg)
    tr = Tracer()
    prog = compile_network(cfg, params, bits,
                           options=CompileOptions(verify="strict", tracer=tr))
    assert verify_network(prog).errors == []
    assert prog.gathers() == 0 and prog.certificate is not None
    spans = {s.name: s.args for s in tr.spans("compile")}
    top = spans["compile_network"]
    assert top["depthwise"] == 17 and top["gathers"] == 0
    lowers = [spans[f"lower:{c.name}"] for c in prog.convs]
    assert [a["groups"] for a in lowers] == [c.groups for c in prog.convs]
    assert [a["bricks"] for a in lowers] == [
        int(np.sum(c.bp.nnz)) for c in prog.convs]
    dw = [a for a, c in zip(lowers, prog.convs) if c.depthwise]
    assert top["dw_bricks"] == sum(a["bricks"] for a in dw)
    assert top["dw_bricks_channel_order"] == sum(
        a["bricks_channel_order"] for a in dw)
    assert top["dw_bricks"] < top["dw_bricks_channel_order"]
    widest = spans["lower:features.15.conv.1"]
    assert widest["bricks"] < 0.7 * widest["bricks_channel_order"]


# ------------------------------------------------------------- the forward


@pytest.mark.parametrize("hw,backend", [(32, "xla"), (33, "xla"),
                                        (32, "pallas")])
def test_compiled_mobilenet_matches_reference(hw, backend):
    cfg = mobilenet_small_config(input_hw=hw)
    params, bits = _pruned(cfg)
    prog = compile_network(cfg, params, bits,
                           options=CompileOptions(verify="strict"))
    assert prog.gathers() == 0
    kw = {"backend": "pallas", "interpret": True} if backend == "pallas" \
        else {"backend": "xla"}
    x = _images(cfg)
    got = make_forward(prog, **kw)(x)
    assert _rel_err(got, mobilenet_apply(cfg, params, x)) <= REL_TOL


def test_classify_session_serves_the_mobilenet(small):
    cfg, params, _, prog = small
    x = np.asarray(_images(cfg, n=5))
    sess = classify_session(prog, batch_slots=4)
    sess.warmup()
    done = sess.run([Request(image=img) for img in x])
    got = np.stack([np.asarray(r.logits) for r in done])
    assert _rel_err(got, mobilenet_apply(cfg, params, x)) <= REL_TOL
    assert sess.trace_count() == 1


def test_every_mobilenet_forward_op_carries_a_layer_and_stage(small):
    """Every op of the served forward sits under ``<layer>/<stage>``; each
    ReLU6 clip runs inside its layer's ``epilogue`` and the linear
    bottleneck's add inside ``residual``."""
    _, _, _, prog = small
    layers = [op.name for op in prog.convs] + ["gap", "fc"]
    scope = re.compile(
        rf"^jit\(forward\)/({'|'.join(map(re.escape, layers))})/"
        rf"({'|'.join(STAGES)})(/|$)"
    )
    hlo = InferenceService(prog, batch_slots=4).lower().compile().as_text()
    traced = [n for n in re.findall(r'op_name="([^"]*)"', hlo)
              if n.startswith("jit(forward)/")]
    assert traced and [n for n in traced if not scope.match(n)] == []
    seen = {scope.match(n).group(1, 2) for n in traced}
    assert {n for n, s in seen if s == "residual"} == {"features.3.conv.2"}
    clips = set(re.findall(
        r'clamp\(.*op_name="jit\(forward\)/([^/"]*)/epilogue/jit\(clip\)',
        hlo))
    assert clips == {c.name for c in prog.convs if c.activation == "relu6"}


# -------------------------------------------------------------- unit cases


@pytest.mark.parametrize("order", ["channel", "tap"])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("c,groups", [(12, 12), (12, 3)])
def test_block_diagonal_conv_matrix_matches_grouped_conv(order, stride, c,
                                                         groups, rng):
    """Patches of an input stored in a permuted channel order times the
    block-diagonal matrix built in that order are the grouped conv, for a
    depthwise conv and one of 1 < groups < C, in both patch orders."""
    c_out = 2 * c
    x = rng.normal(size=(2, c, 9, 9)).astype(np.float32)
    w = rng.normal(size=(c_out, c // groups, 3, 3)).astype(np.float32)
    in_order = rng.permutation(c)
    want = jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((1, 1), (1, 1)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups, precision="highest",
    )
    patches = extract_patches(jnp.asarray(x[:, in_order]), 3, order,
                              stride=stride)
    got = jnp.matmul(patches, conv_matrix(w, order, in_order, groups),
                     precision="highest")
    ho = want.shape[2]
    got = np.asarray(got).reshape(2, ho, ho, c_out).transpose(0, 3, 1, 2)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-5)
    dense = group_dense(w, groups)
    assert np.count_nonzero(dense) == np.count_nonzero(w)


def test_relu6_clips_where_activations_pass_6(small):
    """Weights scaled so activations pass 6: the program matches the
    reference, and the same program with ReLU in place of ReLU6 does
    not."""
    cfg, params, bits, _ = small
    hot = jax.tree.map(np.copy, params)
    for name, p in hot.items():
        if name != "fc":
            p["bn"]["gamma"] = p["bn"]["gamma"] * 8.0
    prog = compile_network(cfg, hot, bits,
                           options=CompileOptions(verify="strict"))
    x = _images(cfg)
    want = mobilenet_apply(cfg, hot, x)
    assert _rel_err(make_forward(prog)(x), want) <= REL_TOL
    relu = prog
    for c in prog.convs:
        if c.activation == "relu6":
            relu = _replace_conv(relu, c.name, activation="relu")
    assert _rel_err(make_forward(relu)(x), want) > 1e-2
    cert = prog.certificate
    for c in prog.convs:
        e = cert.layer(c.name)
        if c.activation == "relu6":
            assert 0.0 <= e.act_lo <= e.act_hi <= 6.0, c.name
            assert e.pre_hi > 6.0, c.name


def test_linear_bottleneck_adds_without_activation(small):
    """The block with a residual add keeps it linear: a ReLU after the add
    moves the logits, and the range pass keeps the add's negative half."""
    cfg, params, _, prog = small
    add = [c for c in prog.convs if c.residual is not None]
    assert [c.name for c in add] == ["features.3.conv.2"]
    assert add[0].activation is None
    x = _images(cfg)
    want = mobilenet_apply(cfg, params, x)
    assert _rel_err(make_forward(prog)(x), want) <= REL_TOL
    relu = _replace_conv(prog, add[0].name, activation="relu")
    assert _rel_err(make_forward(relu)(x), want) > 1e-3
    e = prog.certificate.layer(add[0].name)
    r = prog.certificate.layer(add[0].residual)
    assert (e.act_lo, e.act_hi) == (e.pre_lo + r.act_lo, e.pre_hi + r.act_hi)
    assert e.act_lo < 0.0


def test_fold_bn_into_a_depthwise_conv(rng):
    """Batch norm scales output channels: folded into a depthwise weight
    [C, 1, 3, 3] and bias, the conv equals conv then batch norm."""
    c = 6
    w = rng.normal(size=(c, 1, 3, 3)).astype(np.float32)
    bn = {"gamma": rng.uniform(0.5, 1.5, c), "beta": rng.normal(0, 0.1, c),
          "mean": rng.normal(0, 0.1, c), "var": rng.uniform(0.5, 1.5, c)}
    x = rng.normal(size=(2, c, 7, 7)).astype(np.float32)

    def conv(w):
        return jax.lax.conv_general_dilated(
            x, w, (2, 2), ((1, 1), (1, 1)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=c, precision="highest",
        )

    wf, bf = fold_bn(w, bn, 1e-5)
    assert wf.shape == w.shape
    np.testing.assert_allclose(
        np.asarray(conv(wf)) + bf[None, :, None, None],
        np.asarray(batch_norm(conv(w), bn, 1e-5)), rtol=1e-5, atol=1e-5,
    )


def test_pattern_sorted_producer_stores_fewer_bricks(monkeypatch):
    """A 576-channel depthwise conv stores fewer bricks when its producer
    writes its channels in the depthwise's pattern order than in channel
    order, and the logits are the same."""
    cfg = MobileNetV2Config(blocks=((6, 96, 1, 1),), stem_width=96,
                            head_width=32, num_classes=10, input_hw=16)
    params, bits = _pruned(cfg, seed=3)
    x = _images(cfg, n=2)
    sorted_prog = compile_network(cfg, params, bits)
    monkeypatch.setattr(lowering, "depthwise_producer_orders",
                        lambda *a: {})
    channel_prog = compile_network(cfg, params, bits)
    name = "features.1.conv.1"
    dw_sorted = {c.name: c for c in sorted_prog.convs}[name]
    dw_channel = {c.name: c for c in channel_prog.convs}[name]
    assert dw_sorted.c_in == 576 and dw_sorted.depthwise
    assert np.array_equal(np.sort(np.asarray(bits[name])[
        dw_sorted.in_order, 0]), np.asarray(bits[name])[dw_sorted.in_order, 0])
    assert int(np.sum(dw_sorted.bp.nnz)) < int(np.sum(dw_channel.bp.nnz))
    assert sorted_prog.gathers() == channel_prog.gathers() == 0
    np.testing.assert_allclose(
        np.asarray(make_forward(sorted_prog)(x)),
        np.asarray(make_forward(channel_prog)(x)), rtol=1e-6, atol=1e-6,
    )


# ---------------------------------------------- the passes and the refusals


def test_hardware_report_prices_a_depthwise_conv_per_kernel(small):
    """A depthwise conv is C kernels of one input channel each: ``map_layer``
    of its ``[C, 1]`` pattern bits, never the dense C x C layer."""
    _, _, _, prog = small
    conv = {c.name: c for c in prog.convs}["features.2.conv.1"]
    assert conv.depthwise and conv.pattern_bits.shape == (conv.c_out, 1)
    row = {r["name"]: r for r in prog.hardware_report()["layers"]}[conv.name]
    xb = CrossbarConfig()
    assert row["crossbars"] == map_layer(conv.pattern_bits, xb, 9).num_crossbars
    assert row["naive_crossbars"] == map_layer_naive(conv.c_out, 1, 9,
                                                     xb).num_crossbars
    assert row["total_kernels"] == row["stored_kernels"] == conv.c_out


def test_verify_names_wrong_depthwise_bits(small):
    """Pattern bits of a grouped conv are [c_out, c_in // groups]: a full
    [c_out, c_in] table is V201, a groups the config does not have is
    V301."""
    _, _, _, prog = small
    conv = {c.name: c for c in prog.convs}["features.2.conv.1"]
    wide = np.repeat(conv.pattern_bits, conv.c_in, axis=1)
    rules = [d.rule for d in verify_network(
        _replace_conv(prog, conv.name, pattern_bits=wide)).errors]
    assert rules == ["V201"]
    rules = [d.rule for d in verify_network(
        _replace_conv(prog, conv.name, groups=1)).errors]
    assert "V301" in rules and "V201" in rules


def test_mobilenet_runs_on_a_mesh(small):
    from repro.launch.mesh import make_mesh

    cfg, _, _, prog = small
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    x = _images(cfg, n=2)
    np.testing.assert_allclose(
        np.asarray(make_forward(prog, mesh=mesh)(x)),
        np.asarray(make_forward(prog)(x)), rtol=1e-5, atol=1e-5,
    )


def test_mobilenet_runs_in_int8(small):
    """The int8 program compiles strict (range pass over its int8
    block-diagonal bricks) and agrees with fp32 to quantization error."""
    cfg, params, bits, prog = small
    q = compile_network(cfg, params, bits, options=CompileOptions(
        precision="int8", verify="strict"))
    x = _images(cfg, n=4)
    assert _rel_err(make_forward(q)(x), make_forward(prog)(x)) < 0.05


@pytest.mark.parametrize("what", ["optimize", "collect_stats", "save"])
def test_grouped_programs_refuse_what_they_lack(small, what, tmp_path):
    cfg, params, bits, prog = small
    with pytest.raises(NotImplementedError):
        if what == "optimize":
            compile_network(cfg, params, bits,
                            options=CompileOptions(optimize="auto"))
        elif what == "collect_stats":
            make_forward(prog, collect_stats=True)
        else:
            save_program(str(tmp_path / "prog"), prog)
