"""Mutation tests for the range & bit-width certification pass.

The contract pinned here mirrors ``test_analysis_verify.py``'s: a
pristine compiled program certifies clean end to end (compile -> save ->
load -> ranges) on both precisions, and each corruption family flags
exactly the V5xx rule that guards it — an inflated scale proves
accumulator overflow (V501) without tripping the saturation rule, a
saturating/denormal scale is V502, a zeroed scale over a live brick is
V503, non-finite payloads are V504, shrunken magnitudes expose
unreachable cell slices (V505), and a stale stored certificate is V506.
The certificate itself is bit-deterministic across processes and its
``certified_potential`` pricing matches ``hardware_report``'s own layer
rows exactly.
"""

import dataclasses
import inspect
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.analysis import ProgramFormatError
from repro.analysis.ranges import (
    DEFAULT_INPUT_RANGE,
    NORM_EPS,
    RangeCertificate,
    analyze_network,
    analyze_saved,
)
from repro.core.pruning import (
    build_dictionaries,
    magnitude_prune,
    project_params,
)
from repro.engine import CompileOptions, compile_network, serialize
from repro.models.cnn import conv_weight_names, init_cnn, mini_cnn_config
from repro.obs import Tracer


@pytest.fixture(scope="module")
def pruned():
    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, 0.7)
    dicts = build_dictionaries(params, names, 4)
    params, bits = project_params(params, dicts)
    return cfg, params, bits


def _compile(pruned, precision):
    cfg, params, bits = pruned
    return compile_network(
        cfg, params, bits,
        options=CompileOptions(
            block=16, tile=16, precision=precision, verify="strict"
        ),
    )


@pytest.fixture(scope="module")
def prog_fp32(pruned):
    return _compile(pruned, "fp32")


@pytest.fixture(scope="module")
def prog_int8(pruned):
    return _compile(pruned, "int8")


def _with_bp(prog, bp):
    conv0 = dataclasses.replace(prog.convs[0], bp=bp)
    return dataclasses.replace(prog, convs=[conv0] + prog.convs[1:])


def _np(bp, field):
    return np.array(getattr(bp, field))  # mutable host copy


def _active_slot(bp):
    """(tile, slot) of an active brick with nonzero weights."""
    w = _np(bp, "w_comp")
    nnz = _np(bp, "nnz")
    for t in range(w.shape[0]):
        for k in range(int(nnz[t])):
            if np.any(w[t, k]):
                return t, k
    raise AssertionError("no active nonzero brick in fixture")


def _with_scale(prog, value):
    bp = prog.convs[0].bp
    t, k = _active_slot(bp)
    s = _np(bp, "w_scales")
    s[t, k] = value
    return _with_bp(prog, dataclasses.replace(bp, w_scales=s))


# ------------------------------------------------- pristine programs


def test_pristine_fp32_certifies_clean(prog_fp32):
    report, cert = analyze_network(prog_fp32)
    assert report.clean, report.format()
    assert cert.precision == "fp32"
    assert cert.fp32_safe
    assert (cert.input_lo, cert.input_hi) == DEFAULT_INPUT_RANGE
    assert [e.name for e in cert.layers] == (
        [c.name for c in prog_fp32.convs] + ["fc"]
    )
    for entry in cert.layers:
        assert np.isfinite(entry.act_lo) and np.isfinite(entry.act_hi)
        assert entry.act_lo <= entry.act_hi
        assert entry.certified_cells is None  # fp32: no cell table


def test_pristine_int8_certifies_clean(prog_int8):
    report, cert = analyze_network(prog_int8)
    assert report.clean, report.format()
    stored = prog_int8.cells_per_weight
    for conv in prog_int8.convs:
        entry = cert.layer(conv.name)
        assert entry.stored_cells == stored
        # per-brick quantization saturates each brick at QMAX on its own
        # scale, so a pristine program certifies exactly what it stores
        assert entry.certified_cells == stored
        assert 0 < entry.acc_int32_max < 2**31
        assert 0.0 < entry.acc_fp32_max < float(np.finfo(np.float32).max)
    assert set(cert.certified_cells()) == (
        {c.name for c in prog_int8.convs} | {"fc"}
    )


@pytest.mark.parametrize("precision", ["fp32", "int8"])
def test_end_to_end_compile_save_load_ranges(pruned, precision, tmp_path):
    prog = _compile(pruned, precision)
    assert prog.certificate is not None  # attached under verify="strict"
    d = str(tmp_path / f"prog_{precision}")
    serialize.save_program(d, prog)
    loaded = serialize.load_program(d)
    assert loaded.certificate is not None
    assert loaded.certificate.to_manifest() == prog.certificate.to_manifest()
    report, cert = analyze_saved(d)
    assert report.ok, report.format()
    assert not {r for r in report.rules() if r.startswith("V5")} - {"V504"}
    assert cert.to_manifest() == prog.certificate.to_manifest()


def test_compile_emits_ranges_span(pruned):
    cfg, params, bits = pruned
    tr = Tracer()
    prog = compile_network(
        cfg, params, bits,
        options=CompileOptions(
            block=16, tile=16, precision="int8", verify="warn", tracer=tr
        ),
    )
    spans = [s for s in tr.spans("compile") if s.name == "ranges"]
    assert len(spans) == 1
    assert spans[0].args["fp32_safe"] is True
    assert spans[0].args["certified_cells"] == (
        prog.certificate.certified_cells()
    )


def test_norm_eps_matches_channel_norm_default():
    from repro.models.cnn import channel_norm

    default = inspect.signature(channel_norm).parameters["eps"].default
    assert default == NORM_EPS


# ------------------------------------------------- V5xx mutations


def test_v501_inflated_scale_proves_fp32_overflow(prog_int8):
    # 1e35 folds to ~1e40 in the accumulator (> fp32 max) while staying
    # below the V502 saturation threshold (1e35 * 127 < fp32 max): the
    # overflow rule must fire on its own evidence, not via scale health
    report, _ = analyze_network(_with_scale(prog_int8, 1e35))
    assert "V501" in report.rules(), report.format()
    assert "V502" not in report.rules(), report.format()
    assert not report.ok


def test_v502_saturating_scale(prog_int8):
    report, _ = analyze_network(_with_scale(prog_int8, 1e38))
    assert "V502" in report.rules(), report.format()
    assert not report.ok


def test_v502_denormal_scale(prog_int8):
    report, _ = analyze_network(_with_scale(prog_int8, 1e-40))
    assert "V502" in report.rules(), report.format()
    assert any("denormal" in d.message for d in report.errors)


def test_v503_dead_scale_group_is_a_warning(prog_int8):
    report, _ = analyze_network(_with_scale(prog_int8, 0.0))
    assert "V503" in report.rules(), report.format()
    assert report.ok  # warning: semantic twin of verify's V112 error
    assert any(d.rule == "V503" for d in report.warnings)


def test_v504_nonfinite_bias_is_an_error(prog_fp32):
    bias = np.array(prog_fp32.convs[0].bias)
    bias[0] = np.inf
    conv0 = dataclasses.replace(prog_fp32.convs[0], bias=bias)
    broken = dataclasses.replace(
        prog_fp32, convs=[conv0] + prog_fp32.convs[1:]
    )
    report, cert = analyze_network(broken)
    assert "V504" in report.rules(), report.format()
    assert not report.ok
    assert not cert.fp32_safe


def test_v504_fp32_exceedance_is_a_warning(prog_fp32):
    # an adversarially wide declared input range pushes finite bounds
    # past the fp32 range: certifiable, but not fp32-safe
    report, cert = analyze_network(prog_fp32, input_range=(-1e38, 1e38))
    assert report.ok, report.format()
    assert any(d.rule == "V504" for d in report.warnings)
    assert not cert.fp32_safe


def test_v505_shrunken_magnitudes_expose_unreachable_cells(prog_int8):
    bp = prog_int8.convs[0].bp
    w = _np(bp, "w_comp")
    broken = _with_bp(
        prog_int8,
        dataclasses.replace(bp, w_comp=np.clip(w, -7, 7)),
    )
    report, cert = analyze_network(broken)
    assert "V505" in report.rules(), report.format()
    assert report.ok  # headroom is a finding, not a defect
    entry = cert.layer(prog_int8.convs[0].name)
    assert entry.certified_cells == 1
    assert entry.stored_cells == 2


def test_v506_stale_stored_certificate(prog_int8, tmp_path):
    d = str(tmp_path / "prog")
    serialize.save_program(d, prog_int8)
    path = os.path.join(d, "program.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["certificate"]["layers"][0]["act_hi"] *= 2.0
    with open(path, "w") as f:
        json.dump(manifest, f)
    report, _ = analyze_saved(d)
    assert "V506" in report.rules(), report.format()
    assert not report.ok


# ------------------------------------------------- determinism


def test_certificate_deterministic_across_processes(prog_int8, tmp_path):
    d = str(tmp_path / "prog")
    serialize.save_program(d, prog_int8)
    here = json.dumps(
        analyze_saved(d)[1].to_manifest(), sort_keys=True
    )
    code = (
        "import json\n"
        "from repro.analysis.ranges import analyze_saved\n"
        f"_, cert = analyze_saved({d!r})\n"
        "print(json.dumps(cert.to_manifest(), sort_keys=True))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, check=True,
    )
    assert out.stdout.strip() == here


def test_certificate_manifest_round_trip(prog_int8):
    cert = prog_int8.certificate
    back = RangeCertificate.from_manifest(
        json.loads(json.dumps(cert.to_manifest()))
    )
    assert back == cert


# ------------------------------------------------- certified pricing


def test_certified_potential_zero_drift_against_layer_rows(prog_int8):
    rep = prog_int8.hardware_report()
    cp = rep["certified_potential"]
    assert cp["available"] is True
    by_name = {row["name"]: row for row in rep["layers"]}
    assert len(cp["layers"]) == len(prog_int8.convs)
    for row in cp["layers"]:
        hw = by_name[row["name"]]
        # same pricing chain (core/simulator.mapping_cost): exact equality
        assert row["area_cells"] == hw["area_cells"]
        assert row["energy_pj"] == hw["energy_pj"]
        assert row["cycles"] == hw["cycles"]
        assert row["certified_cells"] <= row["stored_cells"]
        assert row["certified_area_cells"] <= row["area_cells"]
    assert cp["area_win"] >= 1.0
    assert cp["energy_win"] >= 1.0
    assert cp["fp32_safe"] is True


def test_certified_potential_prices_v505_headroom(prog_int8):
    from repro.core.mapping import CrossbarConfig

    # halve every stored magnitude's bit budget: the recertified program
    # must price a strictly smaller certified area than its stored one.
    # Priced on a crossbar narrow enough that the per-weight cell count
    # decides the column-band count (on the paper's 512-wide array the
    # mini CNN fits one band at either width, so the win would round to
    # zero — a granularity fact, not a pricing one).
    convs = []
    for c in prog_int8.convs:
        w = _np(c.bp, "w_comp")
        convs.append(dataclasses.replace(
            c, bp=dataclasses.replace(c.bp, w_comp=np.clip(w, -7, 7))
        ))
    shrunk = dataclasses.replace(prog_int8, convs=convs)
    _, cert = analyze_network(shrunk)
    shrunk = dataclasses.replace(shrunk, certificate=cert)
    narrow = CrossbarConfig(rows=9, cols=8, ou_rows=9, ou_cols=8)
    cp = shrunk.hardware_report(config=narrow)["certified_potential"]
    for row in cp["layers"]:
        assert (row["certified_cells"], row["stored_cells"]) == (1, 2)
        assert row["certified_area_cells"] < row["area_cells"]
    assert cp["certified_area_cells"] < cp["area_cells"]
    assert cp["area_win"] > 1.0


def test_certified_potential_unavailable_on_fp32(prog_fp32):
    cp = prog_fp32.hardware_report()["certified_potential"]
    assert cp["available"] is False
    assert "fp32" in cp["reason"]


# ------------------------------------------------- manifest


def test_manifest_v4_carries_certificate(prog_int8, tmp_path):
    d = str(tmp_path / "prog")
    serialize.save_program(d, prog_int8)
    with open(os.path.join(d, "program.json")) as f:
        manifest = json.load(f)
    assert manifest["format_version"] == 6
    assert manifest["certificate"]["precision"] == "int8"


def test_v3_manifest_loads_without_certificate(pruned, tmp_path):
    """A program compiled without ``verify`` carries no certificate: its
    save has none, loads with none, and still certifies clean."""
    cfg, params, bits = pruned
    prog = compile_network(
        cfg, params, bits,
        options=CompileOptions(block=16, tile=16, precision="int8"),
    )
    assert prog.certificate is None
    d = str(tmp_path / "prog")
    serialize.save_program(d, prog)
    with open(os.path.join(d, "program.json")) as f:
        assert "certificate" not in json.load(f)
    loaded = serialize.load_program(d)
    assert loaded.certificate is None
    # a certificate-less save still certifies — it just can't cross-check
    report, cert = analyze_saved(d)
    assert report.ok, report.format()
    assert cert is not None
    assert "V506" not in report.rules()


def test_malformed_certificate_entry_is_m003(prog_int8, tmp_path):
    d = str(tmp_path / "prog")
    serialize.save_program(d, prog_int8)
    path = os.path.join(d, "program.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["certificate"] = {"input_lo": "not a number"}
    with open(path, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(ProgramFormatError) as e:
        serialize.load_program(d)
    assert e.value.rule == "M003"


# ------------------------------------------------- CLI


def test_cli_ranges_and_all(prog_int8, tmp_path, capsys):
    from repro.analysis.__main__ import main

    d = str(tmp_path / "prog")
    serialize.save_program(d, prog_int8)
    clean_py = tmp_path / "clean.py"
    clean_py.write_text("def f(x):\n    return x\n")

    assert main(["ranges", d]) == 0
    capsys.readouterr()
    assert main(["ranges", d, "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["certificate"]["precision"] == "int8"
    assert payload["report"]["ok"] is True

    assert main(["all", d, "--paths", str(clean_py)]) == 0
    merged = json.loads(capsys.readouterr().out)
    assert merged["ok"] is True and merged["exit_code"] == 0
    assert {"verify", "lint", "ranges"} <= set(merged)


def test_cli_exit_codes_isolate_failure_classes(prog_int8, tmp_path, capsys):
    from repro.analysis.__main__ import main

    d = str(tmp_path / "prog")
    serialize.save_program(d, prog_int8)
    path = os.path.join(d, "program.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["certificate"]["layers"][0]["act_hi"] *= 2.0  # V506 (ranges)
    with open(path, "w") as f:
        json.dump(manifest, f)
    dirty_py = tmp_path / "dirty.py"
    dirty_py.write_text("def f(x, acc=[]):\n    return acc\n")  # L003

    assert main(["ranges", d]) == 1
    capsys.readouterr()
    # verify passes (structure intact), lint fails (+2), ranges fails (+4)
    assert main(["all", d, "--paths", str(dirty_py)]) == 6
    merged = json.loads(capsys.readouterr().out)
    assert merged["exit_code"] == 6
    assert merged["verify"]["ok"] is True


def test_cli_input_range_override(prog_fp32, tmp_path, capsys):
    from repro.analysis.__main__ import main

    d = str(tmp_path / "prog")
    serialize.save_program(d, prog_fp32)
    # `=` form: argparse would otherwise read "-1e38" as an option
    rc = main(["ranges", d, "--json", "--input-lo=-1e38", "--input-hi", "1e38"])
    assert rc == 0  # V504 exceedance is a warning, not an error
    payload = json.loads(capsys.readouterr().out)
    assert payload["certificate"]["fp32_safe"] is False
    assert payload["certificate"]["input_hi"] == 1e38
