"""The VGG chain's forward is pinned: the program it traces and the logits
it computes.

A mini VGG chain (``mini_cnn_config``, pruned to 4 patterns a layer) is
compiled and its jitted forward lowered at a batch of 4. The pins are the
sha256 of the lowered HLO with its metadata stripped (the instructions
the chain traces, before any compiler pass) and the sha256 of its logits
on four seeded images, each on the XLA path and in the Pallas
interpreter. They were recorded before the executor learned strides,
residual adds and folded batch norm, so a chain whose every layer keeps
the defaults traces the same program as before and computes the same
bits. The logits are computed in a child process held to one core, since
XLA's CPU kernels may split their sums by the number of cores.
"""

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")

PINS = {
    "xla": {
        "hlo": "928e5af0724d7282a75a449f19cbe017286c80c3152540bad584b2c6600d9cc6",
        "logits": "f21e166a6b467e332b6169de3cdc91a5e767a2bb5619e7846e595f072399650f",
    },
    "pallas": {
        "hlo": "dd93c7af0aba2a27f70cd9ea17b550ee235d7863ae9018c307c5270053544e0c",
        "logits": "8c2b67e93775809c9e53f5e14cda7f2373e5287261235472fd89feaa37cff8cd",
    },
}

_CHILD = r"""
import hashlib, json, os, re, sys
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
import jax
import numpy as np
from repro.core.pruning import build_dictionaries, magnitude_prune, project_params
from repro.engine import compile_network, make_forward
from repro.models.cnn import conv_weight_names, init_cnn, mini_cnn_config

def strip(hlo):
    keep = ("%", "ROOT ", "ENTRY ", "HloModule ", "}")
    lines = [ln for ln in hlo.splitlines() if ln.lstrip().startswith(keep)]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))

def sha(b):
    return hashlib.sha256(b).hexdigest()

cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
params = init_cnn(cfg, jax.random.PRNGKey(0))
names = conv_weight_names(cfg)
params = magnitude_prune(params, names, 0.7)
params, bits = project_params(params, build_dictionaries(params, names, 4))
prog = compile_network(cfg, params, bits)
x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (4, 1, 12, 12)),
               np.float32)
valid = np.ones(4, bool)
out = {}
for backend in ("xla", "pallas"):
    kw = {"backend": "pallas", "interpret": True} if backend == "pallas" else {}
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
def test_vgg_chain_forward_is_pinned(digests, backend, what):
    assert digests[backend][what] == PINS[backend][what]
