import contextlib
import json
import os
import subprocess
import sys
import textwrap
import unittest.mock

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


def unfolded(program):
    """``program`` as compilers before format 6 lowered it: every weight
    row (the FC's too) reads its input in channel order (``in_order``
    None), re-lowered from the stored weights with the layer's own reorder
    strategy, so the forward gathers each layer's output back into
    channel order.  The result carries no range certificate (the one
    ``program`` had describes other bricks)."""
    import dataclasses

    from repro.engine.lowering import conv_matrix, lower_matrix

    def relower(wm, reorder):
        return lower_matrix(wm, program.block, program.tile,
                            program.precision, reorder=reorder)

    convs = []
    for c in program.convs:
        if c.in_order is not None:
            kk = c.kernel * c.kernel
            wm = np.asarray(c.bp.dense())[: c.k_unpadded, : c.c_out]
            if c.patch_order == "tap":
                w = wm.reshape(kk, c.c_in, c.c_out).transpose(2, 1, 0)
            else:
                w = wm.reshape(c.c_in, kk, c.c_out).transpose(2, 0, 1)
            w = w[:, np.argsort(c.in_order)]  # rows by position -> by channel
            w = w.reshape(c.c_out, c.c_in, c.kernel, c.kernel)
            reorder = "pattern" if c.mapping is None else c.mapping.reorder
            c = dataclasses.replace(
                c, bp=relower(conv_matrix(w, c.patch_order), reorder),
                in_order=None,
            )
        convs.append(c)
    fc = program.fc
    if fc.in_order is not None:
        wm = np.asarray(fc.bp.dense())[: fc.d_in, : fc.d_out]
        fc = dataclasses.replace(
            fc, bp=relower(wm[np.argsort(fc.in_order)], fc.reorder),
            in_order=None,
        )
    return dataclasses.replace(program, convs=convs, fc=fc,
                               certificate=None)


@contextlib.contextmanager
def pre_v6_columns():
    """Lower as compilers before format 6 did: under the ``'pattern'``
    reorder the all-zero columns (a narrow layer's padding) sort first, so
    a layer's real outputs are not its leading stored columns."""
    from repro.core import sparse

    real = sparse.reorder_columns

    def legacy(masks, strategy="pattern"):
        if strategy != "pattern":
            return real(masks, strategy)
        keys = np.array([m.tobytes() for m in np.asarray(masks, bool)])
        return np.argsort(keys, kind="stable").astype(np.int32)

    with unittest.mock.patch.object(sparse, "reorder_columns", legacy):
        yield
