import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# Tests that need >1 device run in a subprocess with
# XLA_FLAGS=--xla_force_host_platform_device_count (the main pytest
# process stays at 1 device unless CI forces more, so every other test
# sees the normal environment).  Shared by tests/test_distributed.py and
# tests/test_engine_sharded.py.
_SUBPROCESS_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
import jax, json
import numpy as np
"""


def run_virtual_devices(n_devices: int, body: str) -> dict:
    """Run ``body`` under ``n_devices`` virtualized host devices; the body
    must end by printing one JSON line, which is returned parsed."""
    code = _SUBPROCESS_PRELUDE.format(n=n_devices) + textwrap.dedent(body)
    env = dict(os.environ)
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert out.returncode == 0, f"subprocess failed:\n{out.stderr[-3000:]}"
    line = out.stdout.strip().splitlines()[-1]
    return json.loads(line)


def channel_major(program):
    """``program`` as format v1-v4 compilers lowered it: every conv's
    weight rows channel-major, re-lowered from its stored weights with
    its own reorder strategy.  The result carries no range certificate
    (the one ``program`` had describes other bricks)."""
    import dataclasses

    from repro.engine.lowering import conv_matrix, lower_matrix

    convs = []
    for c in program.convs:
        if c.patch_order == "tap":
            kk = c.kernel * c.kernel
            wm = np.asarray(c.bp.dense())[: c.k_unpadded, : c.c_out]
            w = wm.reshape(kk, c.c_in, c.c_out).transpose(2, 1, 0)
            w = w.reshape(c.c_out, c.c_in, c.kernel, c.kernel)
            reorder = "pattern" if c.mapping is None else c.mapping.reorder
            bp = lower_matrix(conv_matrix(w), c.bp.block, c.bp.tile,
                              program.precision, reorder=reorder)
            c = dataclasses.replace(c, bp=bp, patch_order="channel")
        convs.append(c)
    return dataclasses.replace(program, convs=convs, certificate=None)


def downgrade_manifest(directory: str, version: int) -> dict:
    """Rewrite a saved program's manifest as format ``version`` (< 5): no
    per-conv ``patch_order``.  Returns the rewritten manifest."""
    path = os.path.join(directory, "program.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["format_version"] = version
    for e in manifest["convs"]:
        del e["patch_order"]
    with open(path, "w") as f:
        json.dump(manifest, f)
    return manifest
