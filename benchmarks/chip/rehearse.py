"""Compile each cell's served forward for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/chip/rehearse.py [cell ...]

For each cell of ``BENCHMARK.json`` (or those named), this builds the
cell's program exactly as ``run.py`` does, compiles the jitted forward at
the cell's batch for one chip of a described ``v5e:2x2`` topology, and
prints ``memory_analysis()`` and the number of kernel calls. The
reference forward is compiled at its block size too. Nothing runs, so it
says nothing of times or results; it shows what the chip's compiler
would refuse, and how much memory each program asks for.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def main(names: list[str]) -> int:
    import json

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import model
    import spec as cellspec
    from repro.engine import CompileOptions, compile_network, make_forward
    from repro.kernels import ops
    from repro.models.cnn import CNNConfig

    # a compile for a described chip cannot be read back from the
    # persistent cache: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    # the program picks its kernel path from the attached backend (the
    # CPU here); steer it to the compiled TPU kernel
    ops._interpret_flag = lambda interpret: False

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name in names or [w["name"] for w in bench["workloads"]]:
        cell = cellspec.load_cell(ROOT, name)
        cfg, b = cell.config, int(cell.mix["batch_slots"])
        params, bits = model.make_weights(cfg)
        net = CNNConfig(
            conv_channels=cfg.conv_channels, pool_after=cfg.pool_after,
            num_classes=cfg.num_classes, input_hw=cfg.input_hw,
            kernel=cfg.kernel,
        )
        prog = compile_network(net, params, bits, options=CompileOptions(
            precision=cfg.precision, **cfg.raw["compile"]))
        fwd = make_forward(prog, backend="pallas")
        shape = (b, cfg.conv_channels[0][0], cfg.input_hw, cfg.input_hw)
        x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)
        valid = jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=chip)
        t = time.perf_counter()
        compiled = fwd.lower(x, valid).compile()
        n_kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
        print(f"{name}: forward at batch {b} compiled in "
              f"{time.perf_counter() - t:.1f} s; {n_kernels} kernel calls; "
              f"{compiled.memory_analysis()}", flush=True)

        rb = min(64, int(cell.mix["pool"]))
        xr = jax.ShapeDtypeStruct((rb, *shape[1:]), jnp.float32, sharding=chip)
        pr = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), params
        )
        ref = jax.jit(lambda p, a: model.forward(cfg, p, a)).lower(pr, xr).compile()
        print(f"{name}: reference at block {rb}: {ref.memory_analysis()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
