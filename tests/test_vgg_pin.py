"""The VGG chain's forward is pinned: the program it traces and the logits
it computes.

A mini VGG chain (``mini_cnn_config``, pruned to 4 patterns a layer) is
compiled and its jitted forward lowered at a batch of 4. The pins are the
sha256 of the lowered HLO with its metadata stripped (the instructions
the chain traces, before any compiler pass) and the sha256 of its logits
on four seeded images, each on the XLA path and in the Pallas
interpreter. They were recorded when each layer's stored output order
was first folded into the next layer's weight rows (manifest format 6),
so the forward gathers no channels; the same chain lowered as the format-5
compiler did, which gathers after every layer, gives the same logits to
fp32 rounding. The Pallas HLO pin was recorded again when the kernel's row
tile began to follow each call's row count (one tile of 576 rows a layer,
where five of 128 ran after a row pad); its logits pin did not move. The
logits are computed in a child process held to one core,
since XLA's CPU kernels may split their sums by the number of cores.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from conftest import pre_v6_columns, unfolded

from repro.core.pruning import build_dictionaries, magnitude_prune, project_params
from repro.engine import compile_network, make_forward
from repro.models.cnn import conv_weight_names, init_cnn, mini_cnn_config

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")

PINS = {
    "xla": {
        "hlo": "0f9c9b824d6f05dadae22866a6c0879f4b27d39237d1ee4923de7809773934ee",
        "logits": "8fe775ac449524c133912614c472c5aef3abc907eaf1db9b87052e1d1400df21",
    },
    "pallas": {
        "hlo": "731acdf29cbb69dcc1f80f206d6e90f74fede844a62e33dc695079e4a5c25145",
        "logits": "2794bc09ec8b3b12b4b2747952db2db180344e76b47027b37e0f32a2ea946ad1",
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


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_folded_chain_matches_its_format5_copy(backend):
    """The pinned chain gathers nothing; the same program lowered as the
    format-5 compiler did (rows in channel order, padding first) gathers
    after every layer and at the logits, and gives the same logits to
    fp32 rounding."""
    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, 0.7)
    params, bits = project_params(params, build_dictionaries(params, names, 4))
    prog = compile_network(cfg, params, bits)
    with pre_v6_columns():
        old = unfolded(prog)
    assert prog.gathers() == 0
    assert old.gathers() == len(old.convs) + 1
    kw = {"backend": "pallas", "interpret": True} if backend == "pallas" \
        else {"backend": "xla"}
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (4, 1, 12, 12)),
                   np.float32)
    np.testing.assert_allclose(
        np.asarray(make_forward(prog, **kw)(x)),
        np.asarray(make_forward(old, **kw)(x)), rtol=1e-5, atol=1e-5,
    )
