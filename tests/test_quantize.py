"""Int-quantized 4-bit-cell execution path (core/quantize + engine wiring).

Covers the quantization math (deterministic error-bound and cell-slice
round-trip checks; the hypothesis fuzzing of the same invariants lives in
``tests/test_quantize_props.py``), the int8 kernel variants on both
backends, the end-to-end accuracy of a quantized compiled CNN against its
fp32 twin, and the cell-slice-derived hardware pricing.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core.pruning import (
    build_dictionaries,
    magnitude_prune,
    project_params,
)
from repro.core.quantize import (
    QMAX,
    cell_slices,
    compose_cell_slices,
    dequantize_groups,
    group_scales,
    n_cell_slices,
    quantize_bp,
    quantize_groups,
    quantize_rows,
)
from repro.core.sparse import build_block_pattern, nonzero_block_masks
from repro.engine import CompileOptions, compile_network, make_forward
from repro.kernels.ops import pattern_spmm
from repro.models.cnn import (
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
def mini_pair():
    """(cfg, fp32 program, int8 program) for the same pruned mini CNN."""
    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params, bits = _pruned_net(cfg)
    prog = compile_network(cfg, params, bits)
    progq = compile_network(cfg, params, bits,
                            options=CompileOptions(precision="int8"))
    return cfg, prog, progq


# ------------------------------------------------- deterministic bounds


@pytest.mark.parametrize("scale_pow", [-4, 0, 4])
def test_quantize_dequantize_error_bounded_by_group_scale(rng, scale_pow):
    """|w - s*q| <= s/2 elementwise, per group (round-to-nearest bound)."""
    w = rng.normal(size=(3, 4, 8, 8)).astype(np.float32) * 10.0**scale_pow
    w[0, 0] = 0.0  # an all-zero group must survive (scale 0, exact)
    scales = group_scales(w, group_ndim=2)
    q = quantize_groups(w, scales, group_ndim=2)
    back = dequantize_groups(q, scales, group_ndim=2)
    bound = scales[:, :, None, None] / 2 * (1 + 1e-5) + 1e-30
    assert (np.abs(back - w) <= bound).all()
    assert np.abs(q).max() <= QMAX


@pytest.mark.parametrize("cell_bits", [2, 3, 4, 5, 7, 8])
def test_cell_slices_roundtrip(rng, cell_bits):
    """Sign-magnitude cell decomposition is lossless and fits the cells."""
    q = rng.integers(-QMAX, QMAX + 1, size=(5, 7), dtype=np.int8)
    s = cell_slices(q, cell_bits)
    assert s.shape == q.shape + (n_cell_slices(cell_bits),)
    assert s.max() < 2**cell_bits
    np.testing.assert_array_equal(compose_cell_slices(s, cell_bits), q)


@pytest.mark.parametrize("cell_bits", [3, 5, 7])
def test_cell_slices_roundtrip_exhaustive_nondividing(cell_bits):
    """Non-dividing cell widths: bit-exact over the entire int8 domain.

    When ``cell_bits`` does not divide ``WEIGHT_BITS`` the top slice is
    narrower than the rest and carries the sign bit at an offset — the
    exact configuration the random round-trip can miss at the domain
    edges, so every representable value is checked.
    """
    q = np.arange(-QMAX, QMAX + 1, dtype=np.int8)
    s = cell_slices(q, cell_bits)
    assert s.max() < 2**cell_bits
    np.testing.assert_array_equal(compose_cell_slices(s, cell_bits), q)


@pytest.mark.parametrize("cell_bits", [3, 5, 7])
def test_verifier_cell_slice_agreement_nondividing(rng, cell_bits):
    """verify_bp's V114 round-trip check agrees with the quantizer at
    non-dividing cell widths: a healthy operand is silent, and an
    unrepresentable stored value (-128) trips both V113 and V114."""
    from repro.analysis.verify import verify_bp

    w = rng.normal(size=(64, 48)).astype(np.float32)
    w[rng.random(w.shape) < 0.5] = 0.0
    bp = build_block_pattern(w, block=16, tile=8, masks=nonzero_block_masks(w, 16))
    qbp = quantize_bp(bp)
    report = verify_bp(qbp, layer="conv", cell_bits=cell_bits)
    assert report.ok, report.format()
    assert not {"V113", "V114"} & report.rules()

    w_comp = np.asarray(qbp.w_comp).copy()
    w_comp[0, 0, 0, 0] = -128  # |q| > QMAX never survives the slice trip
    broken = dataclasses.replace(qbp, w_comp=w_comp)
    report = verify_bp(broken, layer="conv", cell_bits=cell_bits)
    assert {"V113", "V114"} <= report.rules(), report.format()


def test_quantized_bp_dense_within_bound(rng):
    """dense() of a quantized weight errs at most scale/2 per element."""
    w = rng.normal(size=(64, 48)).astype(np.float32)
    w[rng.random(w.shape) < 0.5] = 0.0
    bp = build_block_pattern(w, block=16, tile=8, masks=nonzero_block_masks(w, 16))
    qbp = quantize_bp(bp)
    assert qbp.precision == "int8"
    assert np.asarray(qbp.w_comp).dtype == np.int8
    err = np.abs(np.asarray(qbp.dense()) - np.asarray(bp.dense()))
    max_scale = float(np.asarray(qbp.w_scales).max())
    assert err.max() <= max_scale / 2 * (1 + 1e-5)


def test_quantize_rows_bounds(rng):
    x = rng.normal(size=(6, 32)).astype(np.float32)
    x[2] = 0.0
    q, s = quantize_rows(x)
    back = np.asarray(q, np.float32) * np.asarray(s)[:, None]
    bound = np.asarray(s)[:, None] / 2 + 1e-30
    assert (np.abs(back - x) <= bound).all()
    assert np.asarray(q)[2].tolist() == [0] * 32


# ---------------------------------------------------------------- kernels


def test_quant_spmm_backends_agree_bitwise(rng):
    """XLA scan and Pallas (interpret) int8 variants produce identical
    fp32 outputs for the same quantized operands."""
    import jax.numpy as jnp

    w = rng.normal(size=(64, 48)).astype(np.float32)
    w[rng.random(w.shape) < 0.6] = 0.0
    bp = build_block_pattern(w, block=16, tile=8, masks=nonzero_block_masks(w, 16))
    qbp = quantize_bp(bp)
    x = jnp.asarray(rng.normal(size=(5, 64)).astype(np.float32))
    y_xla = np.asarray(pattern_spmm(x, qbp, backend="xla"))
    y_pal = np.asarray(pattern_spmm(x, qbp, backend="pallas", interpret=True))
    np.testing.assert_array_equal(y_xla, y_pal)
    # and both stay within the composed quantization bound of the exact y
    y_ref = np.asarray(x) @ w
    denom = np.abs(y_ref).max()
    assert np.abs(y_xla - y_ref).max() / denom < 0.05


# ------------------------------------------------------------- end-to-end


@pytest.mark.parametrize("backend,interpret", BACKENDS)
def test_quantized_forward_agrees_with_fp32(mini_pair, backend, interpret):
    """Quantized forward: >= 99% top-1 agreement with the fp32 engine on a
    synthetic eval batch, logits within a small relative bound."""
    cfg, prog, progq = mini_pair
    x = jax.random.normal(jax.random.PRNGKey(5), (256, 1, 12, 12))
    ref = np.asarray(make_forward(prog, backend="xla")(x))
    out = np.asarray(make_forward(progq, backend=backend, interpret=interpret)(x))
    agree = (out.argmax(-1) == ref.argmax(-1)).mean()
    assert agree >= 0.99
    rel = np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-9)
    assert rel < 0.05


def test_quantized_program_metadata(mini_pair):
    cfg, prog, progq = mini_pair
    assert prog.precision == "fp32" and prog.cells_per_weight is None
    assert progq.precision == "int8" and progq.cells_per_weight == 2
    for op in [*progq.convs, progq.fc]:
        assert np.asarray(op.bp.w_comp).dtype == np.int8
        assert op.bp.w_scales is not None
        assert op.bp.precision == "int8"
    # int8 storage is ~4x smaller than the fp32 payload (plus scales)
    comp_fp, dense = prog.weight_bytes()
    comp_q, dense_q = progq.weight_bytes()
    assert dense_q == dense
    assert comp_q < comp_fp / 2


def test_hardware_report_prices_stored_cell_slices(mini_pair):
    """int8 programs price area from the actual 2-slice storage; fp32
    programs keep the crossbar model's assumed width."""
    cfg, prog, progq = mini_pair
    rep, repq = prog.hardware_report(), progq.hardware_report()
    assert rep["precision"] == {
        "weights": "fp32",
        "weight_bits": 32,
        "cell_bits": 4,
        "cells_per_weight": 4,
        "derived_from_storage": False,
    }
    assert repq["precision"] == {
        "weights": "int8",
        "weight_bits": 8,
        "cell_bits": 4,
        "cells_per_weight": 2,
        "derived_from_storage": True,
    }
    assert repq["crossbars"] <= rep["crossbars"]
    assert repq["energy_pj"] < rep["energy_pj"]


def test_vgg16_quantized_area_win():
    """On VGG16-sized layers the halved cell count buys real crossbars."""
    cfg = vgg16_config(num_classes=10, input_hw=32)
    params, bits = _pruned_net(cfg, seed=1, sparsity=0.86, num_patterns=8)
    prog = compile_network(cfg, params, bits)
    progq = compile_network(cfg, params, bits,
                            options=CompileOptions(precision="int8"))
    rep, repq = prog.hardware_report(), progq.hardware_report()
    assert repq["crossbars"] < rep["crossbars"]
    assert repq["naive_crossbars"] < rep["naive_crossbars"]


def test_engine_config_validates_precision():
    with pytest.raises(ValueError):
        CompileOptions(precision="int4")
    with pytest.raises(ValueError):
        CompileOptions(cell_bits=0)
    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params, bits = _pruned_net(cfg)
    with pytest.raises(ValueError):
        compile_network(cfg, params, bits,
                        options=CompileOptions(precision="fp16"))


def test_quantized_nondefault_geometry(mini_pair):
    """Non-MXU (block, tile) geometry quantizes and executes too."""
    cfg, prog, _ = mini_pair
    params, bits = _pruned_net(cfg)
    progq = compile_network(
        cfg, params, bits,
        options=CompileOptions(block=9, tile=8, precision="int8"),
    )
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 1, 12, 12))
    ref = np.asarray(make_forward(prog, backend="xla")(x))
    out = np.asarray(make_forward(progq, backend="xla")(x))
    assert (out.argmax(-1) == ref.argmax(-1)).mean() >= 0.95
