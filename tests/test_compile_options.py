"""CompileOptions: the one-object compile surface.

``compile_network(cfg, params, bits, options=CompileOptions(...))`` is the
only compile call: each option field is validated when the options are
built and reaches the program it compiles.
"""

import inspect
import warnings

import jax
import numpy as np
import pytest

from repro.core.pruning import (
    build_dictionaries,
    magnitude_prune,
    project_params,
)
from repro.engine import CompileOptions, compile_network
from repro.models.cnn import conv_weight_names, init_cnn, mini_cnn_config
from repro.obs.trace import Tracer


@pytest.fixture(scope="module")
def mini():
    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, 0.7)
    dicts = build_dictionaries(params, names, 4)
    params, bits = project_params(params, dicts)
    return cfg, params, bits


def test_options_form_does_not_warn(mini):
    cfg, params, bits = mini
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        compile_network(cfg, params, bits, options=CompileOptions())
        compile_network(cfg, params, bits)  # None: the default options


def test_compile_network_signature():
    """Options go by keyword only; there is no other compile surface."""
    params = inspect.signature(compile_network).parameters
    assert list(params) == ["cfg", "params", "pattern_bits", "options"]
    assert params["pattern_bits"].default is None
    assert params["options"].kind is inspect.Parameter.KEYWORD_ONLY
    assert params["options"].default is None
    defaults = {f: getattr(CompileOptions(), f)
                for f in CompileOptions.__dataclass_fields__}
    assert defaults == {"block": 128, "tile": 128, "precision": "fp32",
                        "cell_bits": 4, "verify": None, "optimize": None,
                        "tracer": None}


@pytest.mark.parametrize(
    "field, value",
    [("precision", "fp16"), ("cell_bits", 0), ("verify", "bogus"),
     ("optimize", 42)],
)
def test_options_validation(field, value):
    with pytest.raises(ValueError, match=field):
        CompileOptions(**{field: value})


def _check_geometry(prog, _):
    assert (prog.block, prog.tile) == (16, 16)
    for bp in [c.bp for c in prog.convs] + [prog.fc.bp]:
        assert (bp.block, bp.tile) == (16, 16)


def _check_precision(prog, base):
    assert prog.precision == "int8"
    for bp in [c.bp for c in prog.convs] + [prog.fc.bp]:
        assert bp.w_scales is not None
    assert all(c.bp.w_scales is None for c in base.convs)


def _check_cell_bits(prog, _):
    assert prog.cell_bits == 2
    rep = prog.hardware_report()["precision"]
    assert rep["cell_bits"] == 2
    assert rep["cells_per_weight"] == 4  # ceil(8 / 2) slices per weight


def _check_verify(prog, base):
    assert base.certificate is None
    assert prog.certificate is not None
    assert prog.certificate.fp32_safe


def _check_optimize(prog, base):
    assert all(c.mapping is None for c in base.convs)
    assert all(c.mapping is not None for c in prog.convs)


def _check_tracer(_prog, _base, tracer):
    names = {e["name"] for e in tracer.events()}
    assert {"compile_network", "lower:conv1", "lower:fc"} <= names


@pytest.mark.parametrize(
    "field", ["block_tile", "precision", "cell_bits", "verify", "optimize",
              "tracer"],
)
def test_each_option_reaches_the_program(mini, field):
    """Each field of the options shows on the compiled program: against a
    compile with the default options, it changes what it names."""
    cfg, params, bits = mini
    tracer = Tracer()
    options, check = {
        "block_tile": (CompileOptions(block=16, tile=16), _check_geometry),
        "precision": (CompileOptions(precision="int8"), _check_precision),
        "cell_bits": (CompileOptions(precision="int8", cell_bits=2),
                      _check_cell_bits),
        "verify": (CompileOptions(verify="strict"), _check_verify),
        "optimize": (CompileOptions(optimize="auto"), _check_optimize),
        "tracer": (CompileOptions(tracer=tracer),
                   lambda p, b: _check_tracer(p, b, tracer)),
    }[field]
    base = compile_network(cfg, params, bits)
    prog = compile_network(cfg, params, bits, options=options)
    check(prog, base)
    # the compile-pass switches that only observe leave the bricks as they
    # were
    if field in ("verify", "tracer"):
        for a, b in zip([*base.convs, base.fc], [*prog.convs, prog.fc]):
            np.testing.assert_array_equal(np.asarray(a.bp.w_comp),
                                          np.asarray(b.bp.w_comp))
