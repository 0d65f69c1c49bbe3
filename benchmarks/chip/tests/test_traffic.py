"""The traffic generator: seeds reorder the same work.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import traffic  # noqa: E402

POISSON = {"process": "poisson", "rate_rps": 640.0}


@pytest.mark.parametrize("arrivals", [POISSON])
def test_every_seed_sends_the_same_requests_in_the_window(arrivals):
    a = traffic.schedule(arrivals, 10.0, 1)
    b = traffic.schedule(arrivals, 10.0, 2**33 + 5)
    assert len(a) == len(b) == round(arrivals["rate_rps"] * 10.0)
    for t in (a, b):
        assert t[0] == 0.0 and t[-1] < 10.0 and np.all(np.diff(t) >= 0)
    assert not np.array_equal(a, b)


def test_poisson_gaps_are_one_set_in_another_order():
    a = np.diff(traffic.schedule(POISSON, 10.0, 3))
    b = np.diff(traffic.schedule(POISSON, 10.0, 4))
    # the same gaps but the first one each seed leaves out
    assert np.isclose(np.sort(a)[1:-1], np.sort(b)[1:-1], rtol=0, atol=1e-3 / 640).mean() > 0.99
    assert np.mean(a) == pytest.approx(1 / 640, rel=0.01)


def test_order_uses_the_pool_evenly_and_images_are_seeded():
    order = traffic.request_order(5, 100, 16)
    counts = np.bincount(order, minlength=16)
    assert counts.max() - counts.min() <= 1
    a = traffic.make_images(9, 4, 3, 8)
    assert np.array_equal(a, traffic.make_images(9, 4, 3, 8))
    assert not np.array_equal(a, traffic.make_images(10, 4, 3, 8))
    assert a.dtype == np.float32 and a.min() >= -2 and a.max() < 2
