"""The work count reads the pruned model, not its lowering; peaks are keyed
by device kind; the harness's weights are the program's synthetic VGG16.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
import model  # noqa: E402
import work  # noqa: E402

from repro.engine import CompileOptions, compile_network  # noqa: E402
from repro.models.cnn import CNNConfig, synthetic_vgg16  # noqa: E402


def _cfg(name):
    return model.NetConfig.load(HERE / "configs" / f"{name}.json")


def _small():
    """VGG16's first three convs, at their published widths, on 16x16."""
    raw = dict(_cfg("vgg16_imagenet").raw)
    raw.update(conv_channels=raw["conv_channels"][:3], pool_after=[2],
               input_hw=16)
    raw["pruning"] = dict(raw["pruning"],
                          patterns_per_layer=raw["pruning"]["patterns_per_layer"][:3])
    return model.NetConfig.from_dict(raw)


def _lower(cfg, params, bits, block, tile):
    net = CNNConfig(conv_channels=cfg.conv_channels, pool_after=cfg.pool_after,
                    num_classes=cfg.num_classes, input_hw=cfg.input_hw)
    return compile_network(net, params, bits,
                           options=CompileOptions(block=block, tile=tile))


def _params_of(prog):
    """The pruned weights as the compiled program holds them."""
    out = {}
    for op in prog.convs:
        dense = np.asarray(op.bp.dense())[: op.c_in * op.kernel**2, : op.c_out]
        w = dense.reshape(op.c_in, op.kernel, op.kernel, op.c_out)
        out[op.name] = {"w": w.transpose(3, 0, 1, 2)}
    out["fc"] = {"w": np.asarray(prog.fc.bp.dense())[: prog.fc.d_in, : prog.fc.d_out]}
    return out


def test_two_lowerings_give_the_same_work():
    cfg = _small()
    params, bits = model.make_weights(cfg)
    a = _lower(cfg, params, bits, block=128, tile=128)
    b = _lower(cfg, params, bits, block=32, tile=64)
    stored = [sum(op.bp.w_comp.size for op in p.convs) for p in (a, b)]
    assert stored[0] != stored[1]  # the bricks differ ...
    want = work.network_work(cfg, params)
    for prog in (a, b):
        # ... and the count does not
        assert work.network_work(cfg, _params_of(prog)) == want


def test_counts_are_useful_work():
    cfg = _small()
    params, _ = model.make_weights(cfg)
    layers = work.network_work(cfg, params)
    conv1 = params["conv1"]["w"]
    nnz = np.count_nonzero(conv1)
    assert 0 < nnz < conv1.size
    assert layers[0].flops_per_image == 2 * 16 * 16 * nnz
    assert layers[0].weight_bytes == 4 * nnz
    assert layers[0].act_bytes_per_image == (3 + 64) * 16 * 16 * 4
    assert layers[-1].flops_per_image == 2 * 128 * 1000


def test_least_time_under_each_bound():
    peak = work.peak_for("TPU v5 lite")
    layer = work.LayerWork("x", flops_per_image=197e12, act_bytes_per_image=0.0,
                           weight_bytes=819e9 * 3)
    assert layer.seconds(1, peak, "fp32") == pytest.approx(
        {"compute": 1.0, "memory": 3.0})
    assert layer.seconds(4, peak, "fp32") == pytest.approx(
        {"compute": 4.0, "memory": 3.0})
    assert layer.seconds(4, peak, "int8")["compute"] == pytest.approx(4 * 197 / 393)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9"):
        work.peak_for("TPU v9")


@pytest.mark.parametrize("config,dataset", [("vgg16_imagenet", "imagenet")])
def test_weights_match_the_programs_synthetic_vgg16(config, dataset):
    cfg = _cfg(config)
    params, bits = model.make_weights(cfg)
    _, theirs, their_bits = synthetic_vgg16(dataset, seed=cfg.weight_seed,
                                            num_classes=cfg.num_classes)
    for name in theirs:
        np.testing.assert_array_equal(params[name]["w"], np.asarray(theirs[name]["w"]))
        if name in bits:
            np.testing.assert_array_equal(bits[name], their_bits[name])
