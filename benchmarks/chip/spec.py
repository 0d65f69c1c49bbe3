"""Find a cell's pieces by the names in ``BENCHMARK.json``.

* a configuration: the file its ``configs`` entry names;
* a traffic mix: ``traffic/<traffic>.json`` beside this file;
* a metric, end-to-end or per-layer: ``metrics/<name>.py`` beside this
  file, whose ``read(run)`` returns the number or ``None`` when it finds
  nothing to read.

A cell reports the end-to-end metrics that list it under ``workloads`` (or
that list no cells), and the per-layer metrics that list it, or that list
no cells and move an end-to-end metric the cell reports.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

import traffic
from model import NetConfig

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: NetConfig
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _reported(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = [m for m in bench["end_to_end"] if _reported(m, workload)]
    names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in names)
    ]
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=NetConfig.load(root / configs[w["config"]]["file"]),
        mix=traffic.load(HERE / "traffic" / f"{w['traffic']}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path
    )
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
