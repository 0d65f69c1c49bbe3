"""Names on the profiler's timeline: the jitted forward's ``<layer>/<stage>``
scopes on the device side, the serving step's phases on the host side.

  * every operation the served forward traces carries a documented
    ``<layer>/<stage>`` in its ``op_name`` metadata, and the scopes change
    nothing but that metadata in the compiled program;
  * under ``jax.profiler`` each ``InferenceService.step`` is a
    ``service.step`` host event holding its four phases, whatever the
    tracer, and the HTTP front end's parse and admission are host events
    too;
  * the scheduler times every step: host work and the device wait.
"""

import contextlib
import glob
import json
import re
import unittest.mock

import jax
import numpy as np
import pytest

from repro.core.pruning import (
    build_dictionaries,
    magnitude_prune,
    project_params,
)
from repro.engine import InferenceService, compile_network
from repro.engine.executor import STAGES
from repro.models.cnn import conv_weight_names, init_cnn, mini_cnn_config
from repro.models.resnet import init_resnet, resnet_small_config
from repro.serve import Request, ServingServer, classify_session

PHASES = ("service.refill", "service.dispatch", "service.wait",
          "service.complete")


@pytest.fixture(scope="module")
def prog():
    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, 0.7)
    params, bits = project_params(params, build_dictionaries(params, names, 4))
    return compile_network(cfg, params, bits)


def _images(n, seed=3):
    return np.array(
        jax.random.normal(jax.random.PRNGKey(seed), (n, 1, 12, 12)),
        np.float32,
    )


def _served_hlo(prog, collect_stats):
    svc = InferenceService(prog, batch_slots=4, collect_stats=collect_stats)
    return svc.lower().compile().as_text()


def _instructions(hlo: str) -> str:
    """The compiled program's instructions without their metadata (and
    without the source-location tables that metadata points into)."""
    keep = ("%", "ROOT ", "ENTRY ", "HloModule ", "}")
    lines = [ln for ln in hlo.splitlines() if ln.lstrip().startswith(keep)]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))


@pytest.mark.parametrize("collect_stats", [False, True])
def test_every_forward_op_carries_a_layer_and_stage(prog, collect_stats):
    layers = [op.name for op in prog.convs] + ["gap", "fc"]
    scope = re.compile(
        rf"^jit\(forward\)/({'|'.join(layers)})/({'|'.join(STAGES)})(/|$)"
    )
    op_names = re.findall(r'op_name="([^"]*)"', _served_hlo(prog, collect_stats))
    # op_names outside jit(forward)/ are the arguments' names and those of
    # XLA's reduction regions, which no scope can reach
    traced = [n for n in op_names if n.startswith("jit(forward)/")]
    assert traced
    assert [n for n in traced if not scope.match(n)] == []
    seen = {scope.match(n).group(1, 2) for n in traced}
    want = {(c, s) for c in layers[:-2] for s in
            ("patches", "spmm", "permute", "epilogue")}
    want |= {("gap", "epilogue"), ("fc", "spmm"), ("fc", "permute"),
             ("fc", "epilogue")}
    if collect_stats:
        want |= {(c, "stats") for c in layers[:-2]}
    assert want <= seen
    assert all(s != "stats" for _, s in seen) or collect_stats


def test_every_resnet_forward_op_carries_a_layer_and_stage():
    """A ResNet's layers are torchvision's names; each block's conv3 adds
    its shortcut under ``<layer>/residual``."""
    cfg = resnet_small_config(input_hw=32)
    prog = compile_network(cfg, init_resnet(cfg, jax.random.PRNGKey(0)))
    layers = [op.name for op in prog.convs] + ["gap", "fc"]
    assert layers[:2] == ["stem", "layer1.0.downsample"]
    scope = re.compile(
        rf"^jit\(forward\)/({'|'.join(map(re.escape, layers))})/"
        rf"({'|'.join(STAGES)})(/|$)"
    )
    op_names = re.findall(r'op_name="([^"]*)"', _served_hlo(prog, False))
    traced = [n for n in op_names if n.startswith("jit(forward)/")]
    assert traced
    assert [n for n in traced if not scope.match(n)] == []
    seen = {scope.match(n).group(1, 2) for n in traced}
    residual = {op.name for op in prog.convs if op.residual is not None}
    assert residual == {"layer1.0.conv3", "layer2.0.conv3", "layer2.1.conv3"}
    assert {(n, "residual") for n in residual} <= seen
    assert {n for n, s in seen if s == "residual"} == residual
    want = {(c, s) for c in layers[:-2] for s in
            ("patches", "spmm", "permute", "epilogue")}
    # layer1.0.downsample's patches are layer1.0.conv1's (the same 1x1
    # view of the same input), which the compiled program builds once
    assert want - {("layer1.0.downsample", "patches")} <= seen


@pytest.mark.parametrize("collect_stats", [False, True])
def test_scopes_change_only_metadata(prog, collect_stats):
    scoped = _served_hlo(prog, collect_stats)
    with unittest.mock.patch.object(
        jax, "named_scope", lambda name: contextlib.nullcontext()
    ):
        plain = _served_hlo(prog, collect_stats)
    assert "conv1/patches" in scoped and "conv1/patches" not in plain
    assert _instructions(scoped) == _instructions(plain)


def _host_events(log_dir) -> list[tuple[str, int, int, str]]:
    """(name, start ns, end ns, thread line) of every host-plane event."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    end = ev.start_ns + ev.duration_ns
                    out.append((ev.name, ev.start_ns, end, line.name))
    return out


def test_service_step_phases_nest_in_step_on_the_profiler(prog, tmp_path):
    svc = InferenceService(prog, batch_slots=4)  # no tracer: NULL_TRACER
    svc.warmup()
    for img in _images(6):
        svc.submit(Request(image=img))
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.run()
    finally:
        jax.profiler.stop_trace()
    assert svc.batches_run == 2 and svc.trace_count() == 1
    events = _host_events(tmp_path)
    steps = [e for e in events if e[0] == "service.step"]
    assert len(steps) == 2
    for name in PHASES:
        phase = [e for e in events if e[0] == name]
        assert len(phase) == 2, name
        for _, s, e, line in phase:
            assert any(
                ss <= s and e <= se and sl == line for _, ss, se, sl in steps
            ), f"{name} outside every service.step"
    # the phases run in order inside each step
    for _, ss, se, _ in steps:
        starts = [next(s for n, s, _, _ in events
                       if n == name and ss <= s <= se) for name in PHASES]
        assert starts == sorted(starts)


def test_http_parse_and_admit_are_profiler_events(prog, tmp_path):
    import http.client

    sess = classify_session(prog, batch_slots=4)
    srv = ServingServer(sess, admit_wait_s=0.0)
    host, port = srv.start_in_thread()
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            conn = http.client.HTTPConnection(host, port, timeout=60)
            conn.request("POST", "/v1/run",
                         json.dumps({"image": _images(1)[0].tolist()}))
            resp = conn.getresponse()
            assert resp.status == 200
            resp.read()
            conn.close()
        finally:
            jax.profiler.stop_trace()
    finally:
        srv.shutdown()
    names = [e[0] for e in _host_events(tmp_path)]
    for name in ("serve.parse", "serve.admit", "service.step", *PHASES):
        assert name in names, name


def test_step_times_one_sample_per_step_and_reset(prog):
    svc = InferenceService(prog, batch_slots=4)
    svc.serve([Request(image=img) for img in _images(10)])
    m = svc.scheduler.metrics
    assert svc.batches_run == 3 and m.steps == 3
    assert m.step_host_hist.count == 3 and m.step_wait_hist.count == 3
    assert m.step_host_hist.sum > 0 and m.step_wait_hist.sum >= 0
    text = svc.metrics_text()
    assert "engine_service_step_host_seconds_count 3" in text
    assert "engine_service_step_wait_seconds_count 3" in text
    assert not svc.step()  # nothing to serve: no step, no sample
    assert svc.scheduler.metrics.step_host_hist.count == 3
    svc.reset_metrics()
    m = svc.scheduler.metrics
    assert m.step_host_hist.count == 0 and m.step_wait_hist.count == 0
    assert "engine_service_step_host_seconds_count 0" in svc.metrics_text()


def test_step_times_split_on_the_service_clock(prog):
    """Four clock reads per step: host = refill + dispatch + complete, wait
    = the device_get between them, on the clock the service was given."""
    ticks = iter(np.arange(100.0, 200.0, 1.0))
    svc = InferenceService(prog, batch_slots=4, clock=lambda: next(ticks))
    svc.warmup()
    svc.submit(Request(image=_images(1)[0]))
    before = next(ticks)
    svc.step()
    m = svc.scheduler.metrics
    assert m.step_host_hist.count == 1
    # every clock read is one tick; the four step reads bracket the
    # scheduler's own reads (admission, completion)
    host, wait = m.step_host_hist.sum, m.step_wait_hist.sum
    assert wait == 1.0
    assert host >= 2.0 and host + wait == next(ticks) - before - 2.0
