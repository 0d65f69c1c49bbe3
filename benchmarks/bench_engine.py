"""Dense ``cnn_apply`` vs compiled-engine execution, across sparsity levels.

Runs mini-CNN and VGG16 shapes through the default kernel dispatch (the
Pallas kernel on a TPU, the XLA path on CPU), and emits a JSON report with:

  * dense-vs-engine wall-clock per (network, sparsity),
  * each compiled program's ``hardware_report()`` totals, priced three
    ways for the same compiled network: no-skip upper bound, an *assumed*
    uniform skip probability (ASSUMED_SKIP), and the skip probabilities
    *measured* on the bench activations by the stats-collecting forward —
    plus the measured-vs-assumed energy delta,
  * a ``quantized`` sub-entry per level: the same pruned network compiled
    at ``precision='int8'`` (4-bit-cell bit-sliced storage) and executed
    through the int8-input/int32-accumulate kernel — accuracy delta
    (max-abs logit difference and top-1 agreement vs the fp32 engine)
    next to the crossbar-area/energy win the narrower cells buy,
  * a ``service`` throughput entry: ``InferenceService`` draining a
    bursty 100-request trace at fixed ``batch_slots`` through the
    continuous-batching scheduler — requests/s, mean occupancy/latency,
    the single-trace guarantee (``trace_count``) and the exactness of the
    accumulated skip statistics vs a one-shot stats forward,
  * an ``http_service`` entry: the same bursty trace through the
    ``repro.serve`` asyncio HTTP front end over a real socket — req/s,
    first-result p50/p99, mean slot occupancy (gated at >= 90%), the
    single-trace invariant under socket-driven concurrency, and a
    load-shedding phase whose served/shed split must conserve requests,
  * a 1-vs-N-device sharded-execution entry: the same compiled program
    run unsharded and tile/batch-sharded over a mesh, recording both
    wall-clocks, the speedup, and the max output difference.  On an
    accelerator it runs in-process on every device the process sees; on
    the CPU backend it runs over N virtualized host devices (a child
    process, ``--xla_force_host_platform_device_count``), where the
    "speedup" mostly measures collective overhead,
  * a consistency check: compiling the Table-II-matched synthetic cifar10
    network must reproduce ``core/simulator.simulate_dataset``'s per-layer
    crossbar counts exactly (same pattern bits -> same ``map_layer``).

Usage:
  PYTHONPATH=src python -m benchmarks.bench_engine \\
      [--out FILE] [--quick] [--smoke] [--trace-out FILE]

``--trace-out`` additionally records the service entry on a span tracer
(``repro.obs``) and writes a Chrome trace-event JSON — load it in
Perfetto or chrome://tracing to see compile phases, per-layer forward
spans, and all 100 request lifecycles on one timeline; the service
entry then also carries the predicted-vs-measured ``drift`` section.

``--smoke`` is the CI bench-regression configuration: mini-CNN only, one
sparsity level, a 2-device sharded entry — small enough for every PR, but
still covering the engine-vs-dense ratio, the quantized accuracy/area
numbers, and the simulator-consistency check that
``benchmarks/check_baseline.py`` gates against
``benchmarks/baselines/bench_smoke.json``.

As part of ``benchmarks.run`` it contributes the usual CSV rows.
"""

from __future__ import annotations

import argparse
import dataclasses
import http.client
import json
import os
import subprocess
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import timed
from repro.compile_cache import enable_compile_cache
from repro.obs.trace import Tracer
from repro.core.pruning import (
    build_dictionaries,
    magnitude_prune,
    project_params,
)
from repro.core.simulator import simulate_dataset
from repro.engine import (
    CompileOptions,
    InferenceService,
    compile_network,
    make_forward,
    partition_network,
)
from repro.launch.mesh import make_mesh
from repro.serve import Request, ServingServer, classify_session
from repro.models.cnn import (
    CNNConfig,
    cnn_apply,
    conv_weight_names,
    init_cnn,
    mini_cnn_config,
    synthetic_vgg16,
    vgg16_config,
)

SPARSITIES = (0.5, 0.75, 0.9)
# Fallback skip probability when no activations have been observed: ReLU
# on roughly centred pre-activations zeroes ~half the inputs, so a
# selection of one pattern's taps being all-zero is modelled coarsely as
# 0.5 — precisely the kind of assumption the measured path replaces.
ASSUMED_SKIP = 0.5


def _pruned(cfg: CNNConfig, sparsity: float, num_patterns: int, seed: int):
    params = init_cnn(cfg, jax.random.PRNGKey(seed))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, sparsity)
    dicts = build_dictionaries(params, names, num_patterns)
    return project_params(params, dicts)


EVAL_BATCH = 128  # agreement sample size: granularity 1/128 < gate slack


def _quantized_entry(cfg, params, bits, x, fp32_fn, fp32_us, rep_fp32):
    """Int8/4-bit-cell execution of the same pruned network: accuracy
    delta vs the fp32 engine next to the area/energy the cells buy.

    Timing uses the bench batch ``x``; the accuracy numbers use a larger
    synthetic eval batch so top-1 agreement has finer granularity than
    the baseline gate's slack (one argmax flip must not fail CI).

    Deep *random-init* networks (the vgg16 entry) report noticeably
    lower agreement than trained ones: per-sample ``channel_norm``
    divides by a std computed from the (quantization-noisy) activations
    of each sample, so int8 scale noise compounds layer over layer and
    random-init logits are near-tied to begin with.  The trained mini
    example and the smoke gate sit at 100% agreement."""
    progq = compile_network(
        cfg, params, bits, options=CompileOptions(precision="int8")
    )
    q_fn = make_forward(progq)
    _, q_us = timed(lambda: jax.block_until_ready(q_fn(x)), repeats=3)
    repq = progq.hardware_report()
    comp_bytes, _ = progq.weight_bytes()
    x_eval = jax.random.normal(
        jax.random.PRNGKey(7), (EVAL_BATCH,) + x.shape[1:]
    )
    out_fp32, out_q = fp32_fn(x_eval), q_fn(x_eval)
    top1 = float(
        (jnp.argmax(out_q, -1) == jnp.argmax(out_fp32, -1)).mean()
    )
    return {
        "precision": progq.precision,
        "cell_bits": progq.cell_bits,
        "cells_per_weight": repq["precision"]["cells_per_weight"],
        "eval_batch": EVAL_BATCH,
        "engine_us": q_us,
        "vs_fp32_engine": q_us / max(fp32_us, 1e-9),
        "max_abs_diff_vs_fp32": float(jnp.abs(out_q - out_fp32).max()),
        "top1_agreement_vs_fp32": top1,
        "weight_bytes": comp_bytes,
        "crossbars": repq["crossbars"],
        "area_efficiency": repq["area_efficiency"],
        "energy_pj_noskip": repq["energy_pj"],
        "area_win_vs_fp32": rep_fp32["crossbars"]
        / max(repq["crossbars"], 1),
        "energy_win_vs_fp32": rep_fp32["energy_pj"]
        / max(repq["energy_pj"], 1e-9),
        # same stored int8 numbers, repriced at other cell widths: the
        # accuracy column is constant, the area/energy columns move
        "cell_sweep": [
            {
                "cell_bits": cb,
                "cells_per_weight": rep_cb["precision"]["cells_per_weight"],
                "crossbars": rep_cb["crossbars"],
                "energy_pj_noskip": rep_cb["energy_pj"],
            }
            for cb in (2, 4, 8)
            for rep_cb in [
                dataclasses.replace(progq, cell_bits=cb).hardware_report()
            ]
        ],
    }


def _bench_network(name: str, cfg: CNNConfig, batch: int,
                   sparsities=SPARSITIES) -> dict:
    x = jax.random.normal(
        jax.random.PRNGKey(0),
        (batch, cfg.conv_channels[0][0], cfg.input_hw, cfg.input_hw),
    )
    entries = []
    dense_fn = jax.jit(lambda p, xx: cnn_apply(cfg, p, xx))
    for s in sparsities:
        params, bits = _pruned(cfg, s, num_patterns=8, seed=1)
        _, dense_us = timed(
            lambda: jax.block_until_ready(dense_fn(params, x)), repeats=3
        )
        prog = compile_network(cfg, params, bits)
        eng_fn = make_forward(prog)
        out_eng, eng_us = timed(
            lambda: jax.block_until_ready(eng_fn(x)), repeats=3
        )
        max_diff = float(
            jnp.abs(out_eng - dense_fn(params, x)).max()
        )
        _, stats = make_forward(prog, collect_stats=True)(x)
        rep = prog.hardware_report(
            skip_stats=stats, assumed_skip=ASSUMED_SKIP
        )
        comp_bytes, dense_bytes = prog.weight_bytes()
        entries.append(
            {
                "sparsity": s,
                "dense_us": dense_us,
                "engine_us": eng_us,
                "engine_vs_dense": eng_us / max(dense_us, 1e-9),
                "max_abs_diff": max_diff,
                "weight_bytes": comp_bytes,
                "dense_weight_bytes": dense_bytes,
                "energy_pj_noskip": rep["energy_pj"],
                "energy_pj_assumed": rep["energy_pj_assumed"],
                "energy_pj_measured": rep["energy_pj_measured"],
                "measured_vs_assumed_delta_pj":
                    rep["skip"]["measured_vs_assumed_delta_pj"],
                "measured_mean_skip": stats.mean_skip(),
                "quantized": _quantized_entry(
                    cfg, params, bits, x, eng_fn, eng_us, rep
                ),
                "hardware_report": {
                    k: v for k, v in rep.items() if k != "layers"
                },
            }
        )
    return {"network": name, "batch": batch, "input_hw": cfg.input_hw,
            "levels": entries}


# Bursty arrival trace for the service-throughput entry: burst sizes are
# fixed (not drawn at bench time) so batches_run / occupancy are
# deterministic and the baseline can gate them exactly.
SERVICE_BURSTS = (1, 7, 19, 2, 30, 5, 11, 3, 22)  # 100 requests
SERVICE_SLOTS = 8


def _service_throughput(batch_slots: int = SERVICE_SLOTS,
                        tracer: Tracer | None = None) -> dict:
    """Requests/s of ``InferenceService`` under a bursty 100-request
    arrival trace at fixed ``batch_slots``.

    The service executes every batch at the one fixed slot shape (dead
    slots zero-padded + masked), so the whole trace must hit a single
    jitted trace; the entry records that (``trace_count``), the exactness
    of the accumulated skip statistics vs a one-shot stats forward over
    the same images (``stats_exact``), and an ``overhead_vs_forward``
    ratio (service wall-clock per batch / bare forward wall-clock —
    machine speed cancels, so the baseline can gate it loosely).

    With a ``tracer`` (``--trace-out``) the same run also lands on the
    shared timeline: compile-phase spans, the per-request lifecycles of
    all 100 bursty-trace requests, and — after the timed region, so the
    throughput numbers stay clean — one instrumented per-layer forward
    whose measured wall-times feed a non-gated predicted-vs-measured
    ``drift`` section (``hardware_report(observed=...)``).
    """
    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params, bits = _pruned(cfg, 0.75, num_patterns=8, seed=1)
    prog = compile_network(
        cfg, params, bits, options=CompileOptions(tracer=tracer)
    )
    svc = InferenceService(prog, batch_slots=batch_slots,
                           collect_stats=True, tracer=tracer)
    n = sum(SERVICE_BURSTS)
    images = np.array(jax.random.normal(
        jax.random.PRNGKey(3), (n, cfg.conv_channels[0][0],
                                cfg.input_hw, cfg.input_hw)
    ), np.float32)

    # warm the one trace outside the timed region, then reset the stats
    # and metrics windows so the entry describes only the bursty trace
    svc.serve([Request(image=images[0])])
    svc.reset_stats()
    svc.reset_metrics()
    base_batches = svc.batches_run

    reqs = [Request(image=img) for img in images]
    it = iter(reqs)
    t0 = time.perf_counter()
    for burst in SERVICE_BURSTS:
        for _ in range(burst):
            svc.submit(next(it))
        svc.step()
    svc.run()
    dt = time.perf_counter() - t0

    batches = svc.batches_run - base_batches
    fwd = make_forward(prog, collect_stats=True)
    out, ref_stats = fwd(jnp.asarray(images))
    jax.block_until_ready(out)
    _, fwd_us = timed(
        lambda: jax.block_until_ready(
            svc._forward(jnp.asarray(images[:batch_slots]),
                         np.ones(batch_slots, bool))[0]
        ),
        repeats=5,
    )
    stats_exact = all(
        np.array_equal(svc.activation_stats.layers[k].counts,
                       ref_stats.layers[k].counts)
        and svc.activation_stats.layers[k].windows
        == ref_stats.layers[k].windows
        for k in ref_stats.layers
    )
    m = svc.metrics
    entry = {
        "requests": n,
        "batch_slots": batch_slots,
        "bursts": list(SERVICE_BURSTS),
        "requests_per_s": n / max(dt, 1e-9),
        "batches_run": batches,
        "trace_count": svc.trace_count(),
        "occupancy_mean": m["occupancy_mean"],
        "latency_mean_s": m["latency_mean_s"],
        "latency_p50_s": m["latency_p50_s"],
        "latency_p99_s": m["latency_p99_s"],
        "queue_wait_mean_s": m["queue_wait_mean_s"],
        "overhead_vs_forward": (dt * 1e6 / max(batches, 1))
        / max(fwd_us, 1e-9),
        "stats_exact": stats_exact,
    }
    if tracer is not None:
        # outside the timed region: one eager per-layer forward for the
        # execute-category spans, then the predicted-vs-measured drift
        # section (timing-dependent, so never baseline-gated)
        tfwd = make_forward(prog, tracer=tracer)
        jax.block_until_ready(tfwd(jnp.asarray(images[:batch_slots])))
        rep = prog.hardware_report(skip_stats=svc.activation_stats,
                                   observed=tfwd.observed_times())
        entry["drift"] = rep["drift"]
    return entry


# HTTP shed phase: more one-shot admissions than queue + slots can hold,
# so the front door must 429 some of them while serving the rest
HTTP_SHED_SLOTS = 4
HTTP_SHED_QUEUE = 8
HTTP_SHED_REQUESTS = 40


def _stream_http(host, port, payloads, timeout=600):
    """POST /v1/stream and read the chunked NDJSON reply line by line."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request(
            "POST", "/v1/stream",
            json.dumps({"requests": payloads}),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        lines = []
        while True:
            line = resp.readline()
            if not line:
                break
            lines.append(json.loads(line))
        return resp.status, lines
    finally:
        conn.close()


def _http_service_throughput(batch_slots: int = SERVICE_SLOTS) -> dict:
    """The same bursty trace through the asyncio HTTP front end, over a
    real socket (``repro.serve.ServingServer`` + ``/v1/stream``).

    Two servers over one compiled program:

      * **throughput** — all 100 requests on one streaming connection
        with an unbounded queue; the entry records req/s, the
        first-result SLO percentiles, mean slot occupancy (the
        ``check_baseline.py`` gate requires >= 90% through the HTTP
        path), and the single-trace invariant surviving socket-driven
        concurrency;
      * **shed** — a burst of ``HTTP_SHED_REQUESTS`` one-shot admissions
        against a small bounded queue, so the front door must shed: the
        entry records the served/shed split and a conservation check
        (served + shed == submitted, every shed line a well-formed
        overload response, nothing admitted ever dropped).  The exact
        shed count races the worker's drain speed, so only its bounds
        are gated.
    """
    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params, bits = _pruned(cfg, 0.75, num_patterns=8, seed=1)
    prog = compile_network(cfg, params, bits)
    n = sum(SERVICE_BURSTS)
    images = np.array(jax.random.normal(
        jax.random.PRNGKey(3), (n, cfg.conv_channels[0][0],
                                cfg.input_hw, cfg.input_hw)
    ), np.float32)
    payloads = [{"image": img.tolist()} for img in images]

    srv = ServingServer(
        classify_session(prog, batch_slots=batch_slots),
        admit_wait_s=0.02,
    )
    host, port = srv.start_in_thread()
    try:
        t0 = time.perf_counter()
        status, lines = _stream_http(host, port, payloads)
        dt = time.perf_counter() - t0
        m = srv.session.metrics
        entry = {
            "requests": n,
            "batch_slots": batch_slots,
            "all_ok": (
                status == 200
                and len(lines) == n
                and all(ln.get("ok") for ln in lines)
            ),
            "requests_per_s": n / max(dt, 1e-9),
            "first_result_p50_s": m["first_result_p50_s"],
            "first_result_p99_s": m["first_result_p99_s"],
            "occupancy_mean": m["occupancy_mean"],
            "batches_run": m["steps"],
            "trace_count": srv.session.trace_count(),
            "http_completed": srv.completed,
            "meter_rate_per_s": srv.meter.rate,
        }
    finally:
        srv.shutdown()

    shed_srv = ServingServer(
        classify_session(prog, batch_slots=HTTP_SHED_SLOTS,
                         max_queue=HTTP_SHED_QUEUE),
        admit_wait_s=0.0,
    )
    host, port = shed_srv.start_in_thread()
    try:
        status, lines = _stream_http(
            host, port, payloads[:HTTP_SHED_REQUESTS]
        )
        served = [ln for ln in lines if ln.get("ok")]
        shed = [ln for ln in lines if not ln.get("ok")]
        sm = shed_srv.session.metrics
        entry["shed"] = {
            "requests": HTTP_SHED_REQUESTS,
            "batch_slots": HTTP_SHED_SLOTS,
            "max_queue": HTTP_SHED_QUEUE,
            "served": len(served),
            "shed": len(shed),
            "trace_count": shed_srv.session.trace_count(),
            "conservation_ok": (
                status == 200
                and len(served) + len(shed) == HTTP_SHED_REQUESTS
                and len(served) == sm["completed"]
                and sm["rejected"] == len(shed)
                and all(
                    ln.get("error") == "overloaded"
                    and ln.get("retry_after_s", 0) > 0
                    for ln in shed
                )
            ),
        }
    finally:
        shed_srv.shutdown()
    return entry


def _sharded_entry(n_devices: int, batch: int, sparsity: float) -> dict:
    """Single-device vs ``(data, model) = (2, N/2)``-sharded forward of
    the same compiled program, on the first ``n_devices`` devices."""
    data = 2 if n_devices >= 2 else 1
    model = n_devices // data
    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params, bits = _pruned(cfg, sparsity, num_patterns=8, seed=1)
    prog = compile_network(cfg, params, bits)
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, 1, 12, 12))
    single = make_forward(prog)
    y1, single_us = timed(
        lambda: jax.block_until_ready(single(x)), repeats=5
    )
    mesh = make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:n_devices])
    sharded = make_forward(
        partition_network(prog, data=data, model=model), mesh=mesh
    )
    yn, sharded_us = timed(
        lambda: jax.block_until_ready(sharded(x)), repeats=5
    )
    return {
        "devices": n_devices, "mesh": [data, model], "batch": batch,
        "sparsity": sparsity,
        "single_device_us": single_us, "sharded_us": sharded_us,
        "speedup": single_us / max(sharded_us, 1e-9),
        "max_abs_diff": float(np.abs(np.asarray(yn) - np.asarray(y1)).max()),
    }


# The CPU backend must see the forced host-device count before it
# initializes, so on the CPU the sharded entry runs in a child process.
_SHARDED_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
import json
from benchmarks.bench_engine import _sharded_entry
print(json.dumps(_sharded_entry({n}, {batch}, {sparsity})))
"""


def _sharded_throughput(n_devices: int = 4, batch: int = 8,
                        sparsity: float = 0.75) -> dict:
    """1-vs-N-device throughput of the same compiled program.

    On an accelerator it runs in this process over every device the
    process sees (the process holds the chips, so a child could not get
    them).  On the CPU backend it runs in-process when ``n_devices``
    devices already exist, else in a child with that many virtualized
    host devices.  A failure raises.
    """
    if jax.default_backend() != "cpu":
        return _sharded_entry(jax.device_count(), batch, sparsity)
    if jax.device_count() >= n_devices:
        return _sharded_entry(n_devices, batch, sparsity)
    code = _SHARDED_CHILD.format(n=n_devices, batch=batch, sparsity=sparsity)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=600,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded entry failed on {n_devices} virtual devices:\n"
            f"{out.stderr[-2000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def _mapping_model_entry(name: str, cfg, params, bits,
                         sparsity: float | None = None) -> dict:
    """Fixed-vs-searched mapping numbers for one model.

    Compiles the same pruned network twice — the fixed paper scheme and
    ``optimize='auto'`` — and reports the deterministic chosen-vs-fixed
    crossbar area/energy ratios, whether the search is drift-free against
    the simulator pricing (``mapping_cost`` == report rows, exact
    equality), whether a standalone re-search reproduces the compiled
    choice byte-for-byte, and the search wall-clock relative to a fixed
    compile (a ratio, so machine speed cancels).
    """
    from repro.core.simulator import mapping_cost
    from repro.engine.lowering import conv_mapping_search

    # fixed compile: best-of-2 removes timer noise from the ratio gate
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        prog_fixed = compile_network(cfg, params, bits)
        times.append(time.perf_counter() - t0)
    fixed_compile_s = min(times)

    tr = Tracer()
    prog_auto = compile_network(
        cfg, params, bits,
        options=CompileOptions(optimize="auto", tracer=tr),
    )
    search_spans = [s for s in tr.spans("compile")
                    if s.name.startswith("search:")]
    search_s = float(sum(s.dur for s in search_spans))
    evaluations = int(sum(s.args.get("evaluations", 0)
                          for s in search_spans))

    # determinism: the standalone search must reproduce the compiled
    # choice exactly (same seed -> same candidate)
    deterministic = True
    for i, c in enumerate(prog_auto.convs, start=1):
        res = conv_mapping_search(
            np.asarray(params[f"conv{i}"]["w"]), bits.get(f"conv{i}"),
            c.out_hw,
        )
        deterministic &= res.chosen == c.mapping

    rf = prog_fixed.hardware_report()
    ra = prog_auto.hardware_report()

    # zero-drift: the search's cost model re-prices every chosen layer to
    # the exact report numbers (== on floats, not a tolerance)
    cost_exact = True
    for c, row in zip(prog_auto.convs, ra["layers"]):
        mc = mapping_cost(c.pattern_bits, c.mapping, c.out_hw ** 2,
                          c.kernel ** 2)
        cost_exact &= (
            mc.crossbars == row["crossbars"]
            and mc.area_cells == row["area_cells"]
            and mc.energy_pj == row["energy_pj"]
            and mc.cycles == row["cycles"]
        )

    area_ratio = ra["area_cells"] / max(rf["area_cells"], 1)
    energy_ratio = ra["energy_pj"] / max(rf["energy_pj"], 1e-9)
    return {
        "model": name,
        "sparsity": sparsity,
        "fixed": {"area_cells": rf["area_cells"],
                  "energy_pj": rf["energy_pj"],
                  "cycles": rf["cycles"],
                  "crossbars": rf["crossbars"]},
        "searched": {"area_cells": ra["area_cells"],
                     "energy_pj": ra["energy_pj"],
                     "cycles": ra["cycles"],
                     "crossbars": ra["crossbars"]},
        "chosen": ra["mapping"]["per_layer"],
        "fc_reorder": ra["mapping"]["fc_reorder"],
        "area_ratio": area_ratio,
        "energy_ratio": energy_ratio,
        "searched_le_fixed": (
            ra["area_cells"] <= rf["area_cells"]
            and ra["energy_pj"] <= rf["energy_pj"]
        ),
        "strictly_improved": (
            ra["area_cells"] < rf["area_cells"]
            or ra["energy_pj"] < rf["energy_pj"]
        ),
        "cost_model_exact": cost_exact,
        "search_deterministic": deterministic,
        "evaluations": evaluations,
        "search_s": search_s,
        "fixed_compile_s": fixed_compile_s,
        "search_overhead": search_s / max(fixed_compile_s, 1e-9),
    }


def _mapping_entry(smoke: bool) -> dict:
    """The ``mapping`` bench entry: searched must match-or-beat fixed on
    area *and* energy for every model here (``check_baseline.py`` gates
    the aggregate booleans and the deterministic ratios)."""
    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params, bits = _pruned(cfg, 0.75, num_patterns=8, seed=1)
    models = [_mapping_model_entry("mini_cnn", cfg, params, bits, 0.75)]
    if not smoke:
        vcfg, vparams, vbits = synthetic_vgg16("cifar10", num_classes=10)
        models.append(
            _mapping_model_entry("vgg16_cifar_synth", vcfg, vparams, vbits)
        )
    return {
        "models": models,
        "all_searched_le_fixed": all(
            m["searched_le_fixed"] for m in models
        ),
        "any_strictly_improved": any(
            m["strictly_improved"] for m in models
        ),
        "cost_model_exact": all(m["cost_model_exact"] for m in models),
        "search_deterministic": all(
            m["search_deterministic"] for m in models
        ),
    }


def _consistency_check() -> dict:
    """Engine hardware_report vs simulate_dataset on identical bits."""
    cfg, params, bits = synthetic_vgg16("cifar10", num_classes=10)
    prog = compile_network(cfg, params, bits)
    rep = prog.hardware_report()
    sim = simulate_dataset("cifar10", seed=0)
    engine_per_layer = [l["crossbars"] for l in rep["layers"]]
    sim_per_layer = [l.ours_crossbars for l in sim.layers]
    return {
        "dataset": "cifar10",
        "engine_crossbars": int(sum(engine_per_layer)),
        "simulator_crossbars": int(sum(sim_per_layer)),
        "per_layer_match": engine_per_layer == sim_per_layer,
    }


def _verify_overhead() -> dict:
    """Static-verifier cost relative to compile on the synthetic VGG.

    Both stored precisions are compiled and verified; compile and verify
    wall-times are summed so the ratio reflects the real cost of leaving
    ``verify`` on at every trust boundary.  ``check_baseline.py`` gates
    ``overhead_frac`` at < 10% of compile time and requires
    ``errors == 0`` — every program this bench compiles must pass.
    """
    from repro.analysis.verify import verify_network

    cfg, params, bits = synthetic_vgg16("cifar10", num_classes=10)
    compile_s = verify_s = 0.0
    errors = warnings_ = 0
    for precision in ("fp32", "int8"):
        t0 = time.perf_counter()
        prog = compile_network(
            cfg, params, bits, options=CompileOptions(precision=precision)
        )
        compile_s += time.perf_counter() - t0
        # verification is deterministic; best-of-2 removes timer noise
        # from the ratio gate
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            report = verify_network(prog)
            times.append(time.perf_counter() - t0)
        verify_s += min(times)
        errors += len(report.errors)
        warnings_ += len(report.warnings)
    return {
        "compile_s": compile_s,
        "verify_s": verify_s,
        "overhead_frac": verify_s / max(compile_s, 1e-9),
        "errors": errors,
        "warnings": warnings_,
    }


def _ranges_overhead() -> dict:
    """Range-certification cost relative to compile on the synthetic VGG.

    Both stored precisions are compiled once and range-analyzed twice
    (best-of-2 removes timer noise).  ``check_baseline.py`` gates
    ``overhead_frac`` at < 1.5x compile time (the pass touches every
    stored weight, so its floor is compile-comparable — the gate stops
    regressions, not physics), ``errors == 0`` on both precisions, and
    ``deterministic`` — two independent analyses of the same program
    must produce byte-identical certificates.  Warnings are reported
    but not gated: the deep VGG legitimately exceeds the fp32 range
    through the channel-norm eps division (rule V504).
    """
    from repro.analysis.ranges import analyze_network

    cfg, params, bits = synthetic_vgg16("cifar10", num_classes=10)
    compile_s = ranges_s = 0.0
    errors = warnings_ = 0
    deterministic = True
    certified_cells: dict = {}
    for precision in ("fp32", "int8"):
        t0 = time.perf_counter()
        prog = compile_network(
            cfg, params, bits, options=CompileOptions(precision=precision)
        )
        compile_s += time.perf_counter() - t0
        times = []
        manifests = []
        for _ in range(2):
            t0 = time.perf_counter()
            report, cert = analyze_network(prog)
            times.append(time.perf_counter() - t0)
            manifests.append(cert.to_manifest())
        ranges_s += min(times)
        deterministic &= manifests[0] == manifests[1]
        errors += len(report.errors)
        warnings_ += len(report.warnings)
        if precision == "int8":
            certified_cells = cert.certified_cells()
    return {
        "compile_s": compile_s,
        "ranges_s": ranges_s,
        "overhead_frac": ranges_s / max(compile_s, 1e-9),
        "errors": errors,
        "warnings": warnings_,
        "deterministic": deterministic,
        "certified_cells": certified_cells,
    }


def collect(quick: bool = False, smoke: bool = False,
            tracer: Tracer | None = None) -> dict:
    sparsities = SPARSITIES[1:2] if (quick or smoke) else SPARSITIES
    networks = [
        _bench_network(
            "mini_cnn",
            mini_cnn_config(num_classes=4, input_hw=12,
                            widths=(8, 16, 16)),
            batch=8,
            sparsities=sparsities,
        ),
    ]
    if not smoke:
        networks.append(
            _bench_network(
                "vgg16_cifar",
                vgg16_config(num_classes=10, input_hw=32),
                batch=2,
                sparsities=sparsities,
            )
        )
    report = {
        "networks": networks,
        "service": _service_throughput(tracer=tracer),
        "http_service": _http_service_throughput(),
        "sharded": _sharded_throughput(
            n_devices=2 if smoke else (4 if quick else 8)
        ),
        "consistency": _consistency_check(),
        "verify": _verify_overhead(),
        "ranges": _ranges_overhead(),
        "mapping": _mapping_entry(smoke),
    }
    return report


def run():
    """CSV rows for benchmarks.run."""
    report = collect(quick=True)
    for net in report["networks"]:
        for lv in net["levels"]:
            hw = lv["hardware_report"]
            q = lv["quantized"]
            yield (
                f"engine_{net['network']}_s{lv['sparsity']:.2f},"
                f"{lv['engine_us']:.1f},"
                f"dense_us={lv['dense_us']:.1f}"
                f";crossbars={hw['crossbars']}"
                f";area_eff={hw['area_efficiency']:.2f}"
                f";e_measured_pj={lv['energy_pj_measured']:.0f}"
                f";e_assumed_pj={lv['energy_pj_assumed']:.0f}"
            )
            yield (
                f"engine_{net['network']}_s{lv['sparsity']:.2f}_int8,"
                f"{q['engine_us']:.1f},"
                f"top1_agree={q['top1_agreement_vs_fp32']:.3f}"
                f";max_diff={q['max_abs_diff_vs_fp32']:.1e}"
                f";crossbars={q['crossbars']}"
                f";area_win={q['area_win_vs_fp32']:.2f}"
                f";energy_win={q['energy_win_vs_fp32']:.2f}"
            )
    sv = report["service"]
    yield (
        f"engine_service_{sv['batch_slots']}slots,"
        f"{sv['requests_per_s']:.1f},"
        f"requests={sv['requests']}"
        f";batches={sv['batches_run']}"
        f";traces={sv['trace_count']}"
        f";occupancy={sv['occupancy_mean']:.2f}"
        f";stats_exact={sv['stats_exact']}"
    )
    hs = report["http_service"]
    yield (
        f"engine_http_{hs['batch_slots']}slots,"
        f"{hs['requests_per_s']:.1f},"
        f"occupancy={hs['occupancy_mean']:.2f}"
        f";p50_s={hs['first_result_p50_s']:.4f}"
        f";p99_s={hs['first_result_p99_s']:.4f}"
        f";traces={hs['trace_count']}"
        f";shed={hs['shed']['shed']}"
        f";all_ok={hs['all_ok']}"
    )
    sh = report["sharded"]
    yield (
        f"engine_sharded_{sh['devices']}dev,"
        f"{sh['sharded_us']:.1f},"
        f"single_us={sh['single_device_us']:.1f}"
        f";speedup={sh['speedup']:.2f}"
        f";max_diff={sh['max_abs_diff']:.1e}"
    )
    c = report["consistency"]
    yield (
        f"engine_consistency,0.0,"
        f"engine={c['engine_crossbars']}"
        f";simulator={c['simulator_crossbars']}"
        f";match={c['per_layer_match']}"
    )
    mp = report["mapping"]
    for m in mp["models"]:
        yield (
            f"engine_mapping_{m['model']},"
            f"{m['search_s'] * 1e6:.1f},"
            f"area_ratio={m['area_ratio']:.4f}"
            f";energy_ratio={m['energy_ratio']:.4f}"
            f";le_fixed={m['searched_le_fixed']}"
            f";improved={m['strictly_improved']}"
            f";cost_exact={m['cost_model_exact']}"
            f";deterministic={m['search_deterministic']}"
            f";evals={m['evaluations']}"
        )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write JSON here (else stdout)")
    ap.add_argument("--quick", action="store_true",
                    help="single sparsity level")
    ap.add_argument("--smoke", action="store_true",
                    help="CI bench-regression config: mini-CNN only, one "
                         "sparsity, 2-device sharded entry")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write a Chrome trace-event JSON (Perfetto / "
                         "chrome://tracing) of the service entry: compile "
                         "phases, per-layer forward, request lifecycles")
    args = ap.parse_args()
    enable_compile_cache()
    tracer = Tracer() if args.trace_out else None
    report = collect(quick=args.quick, smoke=args.smoke, tracer=tracer)
    payload = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(payload + "\n")
        print(f"wrote {args.out}")
    else:
        print(payload)
    if tracer is not None:
        tracer.write(args.trace_out)
        print(f"wrote {args.trace_out}")
    if not report["consistency"]["per_layer_match"]:
        raise SystemExit("engine/simulator crossbar mismatch")


if __name__ == "__main__":
    main()
