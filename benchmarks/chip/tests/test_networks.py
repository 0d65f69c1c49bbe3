"""Network families: the VGG chain draws, counts and computes what it was
pinned to; a family enters as files alone; a configuration must name one.

``pins/vgg16_imagenet.json`` holds what the harness gave before its VGG
code moved into ``networks/vgg_chain.py``: the sha256 of every weight
array and pattern-bit array ``make_weights`` draws for
``configs/vgg16_imagenet.json``, the ``network_work`` tuples, and the
sha256 of the images, the reference logits and the control logits of
four seeded images on the ``first3`` cut (``cuts/first3.json``). XLA's CPU
convolution splits its sums by the number of cores it may use, so the
logits are computed in a child process held to one core.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
TESTS = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
import run  # noqa: E402
import spec  # noqa: E402

PINS = json.loads((TESTS / "pins" / "vgg16_imagenet.json").read_text())


def _sha(a) -> str:
    a = np.ascontiguousarray(np.asarray(a))
    return f"{a.dtype}{list(a.shape)}:" + hashlib.sha256(a.tobytes()).hexdigest()


@pytest.fixture(scope="module")
def vgg16():
    net, cfg = spec.load_config(HERE / "configs" / f"{PINS['config']}.json")
    return net, cfg, net.make_weights(cfg)


def test_vgg_chain_draws_the_pinned_weights(vgg16):
    _, _, (params, bits) = vgg16
    got = {f"{layer}/{k}": _sha(v) for layer, d in params.items()
           for k, v in d.items()}
    assert got == PINS["weights"]
    assert {k: _sha(v) for k, v in bits.items()} == PINS["pattern_bits"]


def test_vgg_chain_counts_the_pinned_work(vgg16):
    net, cfg, (params, _) = vgg16
    got = [list(dataclasses.astuple(layer))
           for layer in net.network_work(cfg, params, cfg.precision)]
    assert got == PINS["network_work"]


_CUT_LOGITS = """
import hashlib, json, os, sys
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import cut, model, traffic
def sha(a):
    return f"{a.dtype}{list(a.shape)}:" + hashlib.sha256(a.tobytes()).hexdigest()
net, cfg = cut.load(sys.argv[3], sys.argv[4])
params, _ = net.make_weights(cfg)
images = traffic.make_images(int(sys.argv[5]), 4, cfg.in_channels, cfg.input_hw)
print(json.dumps({
    "cut_images": sha(images),
    "cut_reference": sha(model.logits_in_blocks(net, cfg, params, images, 4)),
    "cut_control": sha(
        model.logits_in_blocks(net, cfg, params, images, 4, "three_pass")),
}))
"""


def test_vgg_chain_reference_gives_the_pinned_logits():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _CUT_LOGITS, str(HERE), str(TESTS),
         PINS["config"], PINS["cut"], str(PINS["cut_image_seed"])],
        env=env, capture_output=True, text=True, timeout=300, check=True,
    )
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {k: PINS[k] for k in got}


def _toy_checkout(root: Path) -> None:
    """A family, its configuration and a cell of it, in files under
    ``root`` alone; the cell's traffic mix is the harness's own."""
    (root / "networks").mkdir()
    shutil.copy(TESTS / "networks" / "toy_chain.py", root / "networks")
    (root / "toy.json").write_text(json.dumps({
        "name": "toy", "network": "toy_chain", "in_channels": 3, "width": 8,
        "input_hw": 8, "num_classes": 10, "weight_seed": 5,
        "precision": "fp32", "compile": {},
        "correct": {"logit_rel_err_limit": 3e-6},
    }))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "toy.json"}],
        "workloads": [{"name": "toy.offline_b32", "config": "toy",
                       "traffic": "offline_b32", "chips": 1}],
        "end_to_end": [
            {"name": "images_per_s", "unit": "images/s"},
            {"name": "setup_s", "unit": "s"},
        ],
        "per_layer": [],
    }))


def test_a_family_enters_as_files_alone(monkeypatch, tmp_path):
    _toy_checkout(tmp_path)
    with pytest.raises(spec.ConfigError, match="toy_chain.py"):
        spec.network("toy_chain")  # the harness has no such family
    monkeypatch.setattr(spec, "NETWORKS", tmp_path / "networks")
    monkeypatch.setattr(run, "CACHE_DIR", tmp_path / "jax_cache")
    cell = spec.load_cell(tmp_path, "toy.offline_b32")
    res = run.run_cell(cell, seed=2**31 + 13, seconds=1.0, trace=False,
                       require_chip=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["answers_min"]["value"] > 0
    assert set(res["metrics"]) == {"images_per_s", "setup_s"}
    assert res["metrics"]["images_per_s"]["value"] > 0


@pytest.mark.parametrize("network,error", [
    (None, 'has no "network" key'),
    ("no_such_family", "no_such_family.py does not exist"),
    ("../configs/x", "does not exist"),
])
def test_a_configuration_must_name_a_family_with_a_module(tmp_path, network,
                                                          error):
    raw = json.loads((HERE / "configs" / "vgg16_imagenet.json").read_text())
    if network is None:
        del raw["network"]
    else:
        raw["network"] = network
    path = tmp_path / "config.json"
    path.write_text(json.dumps(raw))
    with pytest.raises(spec.ConfigError, match=error) as e:
        spec.load_config(path)
    assert str(path) in str(e.value)


def test_a_family_module_must_define_the_contract(monkeypatch, tmp_path):
    (tmp_path / "half.py").write_text("class Config:\n    pass\n")
    monkeypatch.setattr(spec, "NETWORKS", tmp_path)
    with pytest.raises(spec.ConfigError, match="make_weights, build_program, "
                                               "forward, network_work"):
        spec.network("half")
