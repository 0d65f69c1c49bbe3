"""The inverted-residual (MobileNetV2) family: it draws, counts and
computes what it was pinned to, runs through ``run.run_cell`` like any
family, and a planted fault in its answers reads as not ``correct``.

``pins/mobilenetv2_imagenet.json`` holds the sha256 of every weight array
(batch-norm statistics included) and pattern-bit array ``make_weights``
draws for ``configs/mobilenetv2_imagenet.json``, the ``network_work``
tuples, and the sha256 of the images, the reference logits and the
control logits of four seeded images on the ``mobilenetv2_small`` cut
(``cuts/mobilenetv2_small.json``). The running statistics come from a
forward on the seeded calibration images, and XLA's CPU convolution
splits its sums by the number of cores it may use, so all of it is
computed in a child process held to one core.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
TESTS = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
import cut  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402

from repro.engine import service  # noqa: E402

PINS = json.loads((TESTS / "pins" / "mobilenetv2_imagenet.json").read_text())
MIX = {"mode": "offline", "batch_slots": 4, "pool": 8}

_PINNED = """
import dataclasses, hashlib, json, os, sys
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import numpy as np
import cut, model, spec, traffic
def sha(a):
    a = np.ascontiguousarray(np.asarray(a))
    return f"{a.dtype}{list(a.shape)}:" + hashlib.sha256(a.tobytes()).hexdigest()
net, cfg = spec.load_config(f"{sys.argv[1]}/configs/{sys.argv[3]}.json")
params, bits = net.make_weights(cfg)
weights = {}
for layer, d in params.items():
    for k, v in d.items():
        for kk, vv in (v.items() if isinstance(v, dict) else [(None, v)]):
            weights[f"{layer}/{k}" + (f"/{kk}" if kk else "")] = sha(vv)
net, small = cut.load(sys.argv[3], sys.argv[4])
sp, _ = net.make_weights(small)
images = traffic.make_images(int(sys.argv[5]), 4, small.in_channels,
                             small.input_hw)
print(json.dumps({
    "weights": weights,
    "pattern_bits": {k: sha(v) for k, v in bits.items()},
    "network_work": [list(dataclasses.astuple(w))
                     for w in net.network_work(cfg, params, cfg.precision)],
    "cut_images": sha(images),
    "cut_reference": sha(model.logits_in_blocks(net, small, sp, images, 4)),
    "cut_control": sha(
        model.logits_in_blocks(net, small, sp, images, 4, "three_pass")),
}))
"""


def pinned_digests(config: str, cut_name: str, image_seed: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _PINNED, str(HERE), str(TESTS), config,
         cut_name, str(image_seed)],
        env=env, capture_output=True, text=True, timeout=600, check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def pinned():
    return pinned_digests(PINS["config"], PINS["cut"], PINS["cut_image_seed"])


@pytest.mark.parametrize("what", ["weights", "pattern_bits", "network_work",
                                  "cut_images", "cut_reference", "cut_control"])
def test_inverted_residual_gives_the_pinned(pinned, what):
    assert pinned[what] == PINS[what]


def test_every_depthwise_conv_and_only_those_is_pruned():
    """Each depthwise kernel keeps 4 taps, the centre among them, from its
    layer's 8 patterns; every other conv is dense."""
    net, cfg = cut.load("mobilenetv2_imagenet", "mobilenetv2_small")
    params, bits = net.make_weights(cfg)
    layers = cfg.layers()
    assert set(bits) == {l.name for l in layers if l.groups > 1}
    for layer in layers:
        w = params[layer.name]["w"]
        if layer.groups == 1:
            assert bool((w != 0).all()), layer.name
            continue
        b = bits[layer.name][:, 0]
        assert w.shape == (layer.c_out, 1, 3, 3)
        assert len(np.unique(b)) <= 8 and np.all(b & (1 << 4))
        taps = (w.reshape(layer.c_out, 9) != 0)
        assert np.array_equal(taps.sum(1), np.full(layer.c_out, 4))
        assert np.array_equal(
            taps, (b[:, None] >> np.arange(9)) & 1 == 1), layer.name
    full = spec.load_config(HERE / "configs" / "mobilenetv2_imagenet.json")[1]
    assert len(full.layers()) == 52
    assert sum(l.groups > 1 for l in full.layers()) == 17


def _cell():
    net, cfg = cut.load("mobilenetv2_imagenet", "mobilenetv2_small")
    return spec.Cell(name="mobilenetv2_small.offline", chips=1, network=net,
                     config=cfg, mix=MIX, end_to_end=[], per_layer=[])


@pytest.fixture(autouse=True)
def _cache_off_the_tree(monkeypatch, tmp_path):
    monkeypatch.setattr(run, "CACHE_DIR", tmp_path / "jax_cache")


def test_the_family_runs_through_run_cell():
    res = run.run_cell(_cell(), seed=2**31 + 7, seconds=1.0, trace=False,
                       require_chip=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["answers_min"]["value"] > 0


def test_an_altered_logit_is_not_correct(monkeypatch):
    make = service.make_forward

    def altered_make_forward(*a, **k):
        fwd = make(*a, **k)

        def fn(x, valid=None):
            return jnp.asarray(fwd(x, valid)).at[0, 0].add(0.5)

        fn.trace_count = fwd.trace_count
        fn.lower = fwd.lower
        return fn

    monkeypatch.setattr(service, "make_forward", altered_make_forward)
    res = run.run_cell(_cell(), seed=2**31 + 7, seconds=1.0, trace=False,
                       require_chip=False)
    assert not res["correct"], res["checks"]
    c = res["checks"]["logit_rel_err"]
    assert float(c["value"]) > c["limit"]
