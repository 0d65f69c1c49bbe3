"""The control fails each configuration's limit.

The control is the reference put in the program's place and computed one
precision lower: every matmul as three bf16 passes (``Precision.HIGH`` on a
TPU) instead of float32 at ``HIGHEST``. Its ``logit_rel_err`` against the
reference must lie above the configuration's limit, or the comparison
could not tell a program that computes at that precision from a sound one.
Here the configurations run at their own widths and input sizes on the
CPU, on a few images; on the chip ``control.py`` reads the same number at
each cell's size.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
import control  # noqa: E402
import model  # noqa: E402
import traffic  # noqa: E402


@pytest.mark.parametrize("config,n", [("vgg16_imagenet", 2)])
def test_control_reads_above_the_limit(config, n):
    cfg = model.NetConfig.load(HERE / "configs" / f"{config}.json")
    params, _ = model.make_weights(cfg)
    images = traffic.make_images(2**31 + 3, n, cfg.conv_channels[0][0], cfg.input_hw)
    err = control.control_reading(cfg, params, images, block=n)
    assert err > cfg.logit_rel_err_limit
