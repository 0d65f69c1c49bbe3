"""Find a cell's pieces by the names in ``BENCHMARK.json``.

* a configuration: the file its ``configs`` entry names. The file's
  ``"network"`` key names its family, whose code is
  ``networks/<family>.py`` beside this file;
* a traffic mix: ``traffic/<traffic>.json`` beside this file;
* a metric, end-to-end or per-layer: ``metrics/<name>.py`` beside this
  file, whose ``read(run)`` returns the number or ``None`` when it finds
  nothing to read.

A family module holds everything that knows the network's shape, and
defines exactly these names:

* ``Config.from_dict(raw)``: the parsed configuration file, with at least
  ``name``, ``in_channels``, ``input_hw`` (images are ``[in_channels,
  input_hw, input_hw]``), ``num_classes``, ``weight_seed``, ``precision``
  (the program's, ``"fp32"`` or ``"int8"``), ``logit_rel_err_limit`` and
  ``raw`` (the dict itself, whose ``"compile"`` holds the keyword
  arguments of the program's ``CompileOptions``);
* ``make_weights(cfg) -> (params, program_inputs)``: the benchmark's own
  draw from ``cfg.weight_seed``, nothing imported from the program;
* ``build_program(cfg, params, program_inputs, options)``: the program
  under test, a ``CompiledNetwork`` built through the program's public
  entry points under ``options`` (a ``CompileOptions``);
* ``forward(cfg, params, x, precision)``: the plain float32 reference
  logits ``[B, classes]`` of images ``x [B, C, H, W]`` at ``"highest"``,
  or the control's at ``"three_pass"`` (``model.at_precision``);
* ``network_work(cfg, params, precision)``: a ``work.LayerWork`` per
  layer, the useful FLOPs and minimal bytes the roofline readers read.

A new architecture enters as files alone: its family module, its
configuration, and where needed a traffic mix and metric readers.

A cell reports the end-to-end metrics that list it under ``workloads`` (or
that list no cells), and the per-layer metrics that list it, or that list
no cells and move an end-to-end metric the cell reports.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType

import traffic

HERE = Path(__file__).resolve().parent
NETWORKS = HERE / "networks"
FAMILY_NAMES = ("Config", "make_weights", "build_program", "forward",
                "network_work")


class ConfigError(ValueError):
    """A configuration file that names no family, or one with no module."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    network: ModuleType  # the configuration's family module
    config: object  # network.Config
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _reported(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _load_module(path: Path, name: str) -> ModuleType:
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    # a dataclass looks its module up while the module runs
    sys.modules[name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def network(family: str) -> ModuleType:
    """The family module ``networks/<family>.py``."""
    path = NETWORKS / f"{family}.py"
    if not (isinstance(family, str) and family.isidentifier() and path.is_file()):
        raise ConfigError(f"no network family {family!r}: {path} does not exist")
    mod = _load_module(path, "network_" + family)
    missing = [n for n in FAMILY_NAMES if not hasattr(mod, n)]
    if missing:
        raise ConfigError(f"{path} does not define {', '.join(missing)}")
    return mod


def load_config(path: Path) -> tuple[ModuleType, object]:
    """``(family module, its Config)`` of the configuration file ``path``."""
    return config_of(json.loads(Path(path).read_text()), path)


def config_of(raw: dict, origin) -> tuple[ModuleType, object]:
    """``(family module, its Config)`` of the parsed configuration ``raw``;
    errors name ``origin``, the file it came from."""
    if "network" not in raw:
        raise ConfigError(
            f"{origin} has no \"network\" key: name the family, whose module "
            f"is {NETWORKS}/<family>.py"
        )
    try:
        net = network(raw["network"])
    except ConfigError as e:
        raise ConfigError(f"{origin}: {e}") from None
    return net, net.Config.from_dict(raw)


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
    net, cfg = load_config(root / configs[w["config"]]["file"])
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        network=net,
        config=cfg,
        mix=traffic.load(HERE / "traffic" / f"{w['traffic']}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    return _load_module(path, "metric_" + name.replace(".", "_")).read
