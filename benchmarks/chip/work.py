"""Useful work, minimal bytes and the chip's peaks.

Each network family counts its layers (``network_work`` in
``networks/<family>.py``) as the work any implementation of the pruned
network must do, taken from the model and never from its lowering:

* useful FLOPs of a conv: 2 x output positions x nonzero weights; of the
  FC: 2 x inputs x outputs (it is dense);
* minimal bytes of a layer: its unpadded input activation read once, its
  output written once, and its nonzero weight values at their stored
  width. Activations are float32; weights are read once per batch.

Zero bricks, im2col in the kernel or another block size change the time
and not this count. Peaks come from ``peaks.json``, keyed by
``device_kind``; a device missing there is an error.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")
ACT_BYTES = 4  # float32 activations
WEIGHT_BYTES = {"fp32": 4, "int8": 1}


@dataclasses.dataclass(frozen=True)
class LayerWork:
    name: str
    flops_per_image: float
    act_bytes_per_image: float  # input read + output written
    weight_bytes: float  # nonzero weights at their stored width

    def seconds(self, batch: int, peak: dict, precision: str) -> dict:
        """The chip's least time for this layer at ``batch`` images under
        each bound: ``{"compute": s, "memory": s}``."""
        return {
            "compute": self.flops_per_image * batch / peak_flops(peak, precision),
            "memory": (self.act_bytes_per_image * batch + self.weight_bytes)
            / peak["hbm_bytes_per_s"],
        }


def peak_for(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}"
        )
    return table[device_kind]


def peak_flops(peak: dict, precision: str) -> float:
    # fp32 programs use the bf16 MXU peak: the chip publishes no fp32 one
    return peak["int8_ops_per_s" if precision == "int8" else "bf16_flops_per_s"]
