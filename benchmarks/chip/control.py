"""The control of a cell's comparison: the reference one precision lower.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3

For each seed this draws the cell's pool of images, computes the plain
reference logits (float32 at ``HIGHEST``) and the control's: the same
forward with every matmul as three bf16 passes, what ``Precision.HIGH``
computes on a TPU. It prints the control's ``logit_rel_err`` against the
reference, the number the cell's ``correct`` compares, in blocks of the
cell's batch. The control has to read above the configuration's limit:
a program computing at that precision would fail the check. On the chip
at the cell's size this gives the limit's upper reading; the benchmark's
own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
import spec as cellspec  # noqa: E402
import traffic  # noqa: E402
from model import logits_in_blocks, make_weights  # noqa: E402


def control_reading(cfg, params, images, block: int) -> float:
    """``run.compare`` of the control's answers for ``images`` with the
    reference: the widest ``max|control - ref| / max|ref|``."""
    ref = logits_in_blocks(cfg, params, images, block, "highest")
    low = logits_in_blocks(cfg, params, images, block, "three_pass")
    return run.compare(list(enumerate(low)), ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = cellspec.load_cell(Path.cwd(), args.workload)
    cfg, mix = cell.config, cell.mix
    params, _ = make_weights(cfg)
    block = min(int(mix["batch_slots"]), int(mix["pool"]))
    for seed in args.seeds:
        images = traffic.make_images(
            seed, mix["pool"], cfg.conv_channels[0][0], cfg.input_hw
        )
        err = control_reading(cfg, params, images, block)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "control_logit_rel_err": err,
            "limit": cfg.logit_rel_err_limit,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
