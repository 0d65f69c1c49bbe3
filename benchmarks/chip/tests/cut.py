"""Test-size cuts of a configuration, kept as data in ``cuts/<name>.json``.

A cut lays its keys over ``configs/<config>.json``: a key replaces the
configuration's, a group of keys (a JSON object) is merged into the
configuration's group of that name. Everything the cut does not name, the
family and the limit among it, stays the configuration's.
"""

import json
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
import spec  # noqa: E402


def load(config: str, name: str):
    """``(family module, Config)`` of ``config`` cut by ``cuts/<name>.json``."""
    raw = json.loads((CHIP / "configs" / f"{config}.json").read_text())
    path = Path(__file__).with_name("cuts") / f"{name}.json"
    for key, value in json.loads(path.read_text()).items():
        raw[key] = {**raw[key], **value} if isinstance(value, dict) else value
    return spec.config_of(raw, path)
