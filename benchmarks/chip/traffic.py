"""The one traffic generator: every mix is a data file that this reads.

A mix (``traffic/<name>.json``) holds only parameters:

    {"mode": "offline", "batch_slots": 32, "pool": 64}
    {"mode": "http", "batch_slots": 8, "pool": 256,
     "arrivals": {"process": "poisson", "rate_rps": 600.0}}

``mode`` "offline" is a closed loop in the serving process: the queue
always holds at least two batches. "http" is an open loop of single-image
``POST /v1/run`` requests on a schedule. ``pool`` is the number of distinct
images a run draws its requests from.

The seed draws the images, the order in which requests take them, and the
order of the gaps between arrivals. The set of gaps is the same for every
seed (stratified quantiles of the exponential), so seeds reorder the same
work instead of changing its amount. This module imports numpy alone: the
load generator, which never imports JAX, uses it too.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

MODES = ("offline", "http")


def load(path: str | Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("mode") not in MODES:
        raise ValueError(f"{path}: mode must be one of {MODES}")
    return mix


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & (2**64 - 1), stream])


def make_images(seed: int, n: int, channels: int, hw: int) -> np.ndarray:
    """``n`` images ``[n, C, H, W]``: 8-bit pixels scaled to [-2, 2), exact
    in float32 and short in JSON."""
    px = _rng(seed, 0).integers(0, 256, (n, channels, hw, hw))
    return ((px - 128) / 64.0).astype(np.float32)


def request_order(seed: int, n: int, pool: int) -> np.ndarray:
    """Pool index of each of ``n`` requests: whole shuffled passes over the
    pool, so every image is used as evenly as ``n`` allows."""
    rng = _rng(seed, 2)
    passes = [rng.permutation(pool) for _ in range(-(-n // pool))]
    return np.concatenate(passes)[:n]


def schedule(arrivals: dict, seconds: float, seed: int) -> np.ndarray:
    """Send times (seconds from the window's start) of the requests due in
    ``[0, seconds)``.

    The arrivals are Poisson: the gaps are exponential quantiles at
    ``(i + 0.5) / n`` in an order drawn from the seed, in units of the mean
    gap.
    """
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    n = max(1, int(round(float(arrivals["rate_rps"]) * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps = gaps[_rng(seed, 1).permutation(n)]
    # the first request goes at 0; the gaps fill the window to its end
    # less one gap, whatever their order
    return (np.cumsum(gaps) - gaps[0]) / gaps.sum() * seconds
