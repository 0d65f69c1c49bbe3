"""Nothing hides which device ran the program.

* The Pallas kernels run compiled on a TPU, or in the interpreter only
  when the caller asks for it: off the TPU, ``backend='pallas'`` without
  ``interpret=True`` raises instead of interpreting in silence.
* The persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
  says, else at the fixed ``<repo>/.jax_cache``.
* ``chip_smoke.py`` refuses to run anywhere but on a TPU, and outside a
  checkout of the repository.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantize import quantize_bp
from repro.core.sparse import build_block_pattern
from repro.engine import compile_network
from repro.engine.executor import make_forward
from repro.kernels.ops import flash_attention, pattern_spmm
from repro.models.cnn import init_cnn, mini_cnn_config

REPO = Path(__file__).resolve().parents[1]


def _bp(quantized: bool):
    w = np.random.default_rng(0).normal(size=(256, 128)).astype(np.float32)
    bp = build_block_pattern(w, num_patterns=4, density=0.5)
    return quantize_bp(bp) if quantized else bp


def _spmm(quantized: bool, interpret):
    x = jnp.ones((8, 256), jnp.float32)
    return pattern_spmm(x, _bp(quantized), backend="pallas",
                        interpret=interpret)


def _attention(interpret):
    q = jnp.ones((1, 2, 128, 64), jnp.float32)
    return flash_attention(q, q, q, backend="pallas", interpret=interpret)


def _forward(interpret):
    cfg = mini_cnn_config()
    prog = compile_network(cfg, init_cnn(cfg, jax.random.PRNGKey(0)))
    fwd = make_forward(prog, backend="pallas", interpret=interpret)
    return fwd(jnp.ones((2, 1, cfg.input_hw, cfg.input_hw), jnp.float32))


CALLS = {
    "pattern_spmm": lambda interpret: _spmm(False, interpret),
    "pattern_spmm_int8": lambda interpret: _spmm(True, interpret),
    "flash_attention": _attention,
    "make_forward": _forward,
}


@pytest.mark.parametrize("name", sorted(CALLS))
@pytest.mark.parametrize("interpret", [None, False])
def test_pallas_off_tpu_without_interpret_raises(name, interpret):
    if jax.default_backend() == "tpu":
        pytest.skip("the compiled kernel is the right call on a TPU")
    with pytest.raises(ValueError, match="interpret=True"):
        CALLS[name](interpret)


def _python(argv: list[str], env_updates: dict, cwd=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_updates, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO / "src")]
        + ([os.environ["PYTHONPATH"]] if os.environ.get("PYTHONPATH") else [])
    )
    return subprocess.run(
        [sys.executable, *argv], capture_output=True, text=True, env=env,
        cwd=cwd, timeout=300,
    )


_CACHE_PROBE = (
    "import json, jax\n"
    "import repro.engine, repro.serve\n"
    "from repro.compile_cache import enable_compile_cache\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "dirs = [enable_compile_cache(), enable_compile_cache()]\n"
    "print(json.dumps([before, dirs, jax.config.jax_compilation_cache_dir]))\n"
)


@pytest.mark.parametrize("env_dir", [None, "custom"])
def test_compile_cache_dir(tmp_path, env_dir):
    env = {}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    out = _python(["-c", _CACHE_PROBE], env)
    assert out.returncode == 0, out.stderr[-2000:]
    before, dirs, after = json.loads(out.stdout.strip().splitlines()[-1])
    want = env.get("JAX_COMPILATION_CACHE_DIR", str(REPO / ".jax_cache"))
    # importing repro sets nothing; the helper names one fixed directory,
    # the same on every call, and leaves a set variable in charge
    assert before == env.get("JAX_COMPILATION_CACHE_DIR")
    assert dirs == [want, want]
    assert after == want


def test_chip_smoke_refuses_cpu():
    out = _python([str(REPO / "chip_smoke.py")], {}, cwd=REPO)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "not a TPU" in out.stderr


def test_chip_smoke_refuses_outside_checkout(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    out = _python([str(tmp_path / "chip_smoke.py")], {}, cwd=tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
