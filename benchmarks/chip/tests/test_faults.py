"""A run whose timed path is broken underneath comes out not ``correct``.

Each test drives the whole of a run (set-up, window, reference,
comparison) on the CPU at a small size, skipping only the harness's look
for a chip, once on the program as it is and once for each fault an
inference cell can have:

* ``altered``: one logit of one answer changed where it is produced;
* ``half_batch``: the second half of every batch left out, its rows
  answered with the first half's;
* ``stale``: a step that returns the previous step's answers unchanged;
* ``nan``: one logit of one answer made NaN where it is produced;
* ``control``: every answer computed by the control, the reference at
  three bf16 passes (the family's ``forward(..., "three_pass")``), in
  the program's place.

(The fault of an exchange between chips left out has no place here: every
cell runs on one chip.) Run from the repository root:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
import cut  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402

from repro.engine import service  # noqa: E402

MIXES = {
    "offline": {"mode": "offline", "batch_slots": 8, "pool": 16},
    # two slots and a rate that keeps both busy, so the second half of a
    # batch holds answers
    "http": {"mode": "http", "batch_slots": 2, "pool": 16, "connections": 8,
             "warm": 16, "arrivals": {"process": "poisson", "rate_rps": 400.0}},
}


def _cell(mode):
    """VGG16's layout at small widths and size, with the configuration's
    limit."""
    net, cfg = cut.load("vgg16_imagenet", "small")
    return spec.Cell(name=f"small.{mode}", chips=1, network=net, config=cfg,
                     mix=MIXES[mode], end_to_end=[], per_layer=[])


def _altered(out, last, x, cell):
    return out.at[0, 0].add(0.5)


def _half_batch(out, last, x, cell):
    half = out.shape[0] // 2
    return jnp.concatenate([out[:half], out[:half]])


def _stale(out, last, x, cell):
    return out if last is None else last


def _nan(out, last, x, cell):
    return out.at[0, 0].set(jnp.nan)


def _control(out, last, x, cell):
    params, _ = cell.network.make_weights(cell.config)
    return cell.network.forward(cell.config, params, x, "three_pass")


FAULTS = {"altered": _altered, "half_batch": _half_batch, "stale": _stale,
          "nan": _nan, "control": _control}


def _break(monkeypatch, fault, cell):
    make = service.make_forward

    def broken_make_forward(*a, **k):
        fwd = make(*a, **k)
        last = [None]

        def fn(x, valid=None):
            out = fwd(x, valid)
            got = FAULTS[fault](out, last[0], x, cell)
            last[0] = out
            return got

        fn.trace_count = fwd.trace_count
        fn.lower = fwd.lower
        return fn

    monkeypatch.setattr(service, "make_forward", broken_make_forward)


@pytest.fixture(autouse=True)
def _cache_off_the_tree(monkeypatch, tmp_path):
    monkeypatch.setattr(run, "CACHE_DIR", tmp_path / "jax_cache")


@pytest.mark.parametrize("mode", sorted(MIXES))
def test_sound_program_is_correct(mode):
    res = run.run_cell(_cell(mode), seed=2**31 + 7, seconds=1.0, trace=False,
                       require_chip=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["answers_min"]["value"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("mode", sorted(MIXES))
def test_broken_program_is_not_correct(monkeypatch, mode, fault):
    cell = _cell(mode)
    _break(monkeypatch, fault, cell)
    res = run.run_cell(cell, seed=2**31 + 7, seconds=1.0, trace=False,
                       require_chip=False)
    assert not res["correct"], res["checks"]
    c = res["checks"]["logit_rel_err"]
    assert float(c["value"]) > c["limit"]
