"""The small ResNet's forward is pinned: the program it traces and the
logits it computes.

The small ResNet (``resnet_small_config`` at 32x32, its 3x3 convs pruned
to 4 patterns a layer, compiled ``verify="strict"``) is lowered at a batch
of 2. The pins are the sha256 of the lowered HLO with its metadata
stripped (the instructions the graph traces, before any compiler pass)
and the sha256 of its logits on two seeded images, each on the XLA path
and in the Pallas interpreter. They were recorded before convs took
``groups`` and an activation field in place of the ReLU flag, which left
every full conv's program as it was. The logits are computed in a child
process held to one core, since XLA's CPU kernels may split their sums by
the number of cores.
"""

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")

PINS = {
    "xla": {
        "hlo": "aba50dfda2544c83975bf394710ce7427468e96dd35f0a9ae2917d417e07250a",
        "logits": "c59e14276cabe33bab960afc76c5be26d3abbc7a418696ca7e352773b72e2472",
    },
    "pallas": {
        "hlo": "5ebe57fab8ed8f19fcc82c27ae2f9f29700c823ec76c1ced5f26a74d833c6006",
        "logits": "ca37a648e6f38180cf516542694d91346e986d8fbd711f5204cde0fe0c5d83b1",
    },
}

_CHILD = r"""
import hashlib, json, os, re, sys
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
import jax
import numpy as np
from repro.core.pruning import build_dictionaries, magnitude_prune, project_params
from repro.engine import CompileOptions, compile_network, make_forward
from repro.models.resnet import init_resnet, resnet_small_config

def strip(hlo):
    keep = ("%", "ROOT ", "ENTRY ", "HloModule ", "}")
    lines = [ln for ln in hlo.splitlines() if ln.lstrip().startswith(keep)]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))

def sha(b):
    return hashlib.sha256(b).hexdigest()

cfg = resnet_small_config(input_hw=32)
params = init_resnet(cfg, jax.random.PRNGKey(0))
names = [s.name for s in cfg.layers() if s.kernel == 3]
params = magnitude_prune(params, names, 0.7)
params, bits = project_params(params, build_dictionaries(params, names, 4))
prog = compile_network(cfg, params, bits,
                       options=CompileOptions(verify="strict"))
x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (2, 3, 32, 32)),
               np.float32)
valid = np.ones(2, bool)
out = {}
for backend in ("xla", "pallas"):
    kw = {"backend": "pallas", "interpret": True} if backend == "pallas" \
        else {"backend": "xla"}
    fn = make_forward(prog, **kw)
    hlo = fn.lower(x, valid).as_text(dialect="hlo")
    logits = np.asarray(fn(x, valid), np.float32)
    out[backend] = {"hlo": sha(strip(hlo).encode()),
                    "logits": sha(np.ascontiguousarray(logits).tobytes())}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def digests():
    path = os.pathsep.join(p for p in (SRC, os.environ.get("PYTHONPATH")) if p)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=path)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, capture_output=True,
        text=True, timeout=300, check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("what", ["hlo", "logits"])
def test_small_resnet_forward_is_pinned(digests, backend, what):
    assert digests[backend][what] == PINS[backend][what]
