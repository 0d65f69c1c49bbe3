"""The fold of each layer's stored column order into the weight rows of the
layers that read it.

Every tensor the forward keeps stays in the order its producer's kernel
wrote it; the lowering builds each consumer's rows in that order
(``in_order``), and ``CompiledNetwork.layout`` leaves a gather (the
``permute`` stage) only where two orders meet that differ. Checked here on
a mini VGG chain and ``resnet_small_config``, lowered at 16-row blocks and
16-column tiles so that masks differ between columns and every reorder
strategy permutes something:

  * the folded forward matches the float32 reference on the XLA path and
    in the Pallas interpreter, under each reorder strategy, with the real
    outputs of a layer narrower than its tile leading its stored columns;
  * a residual add whose addends carry different orders gathers once;
  * an int8 program folds the same orders;
  * the skip counters equal those of the same program run the gathering
    way, bit for bit;
  * the compiled HLO holds exactly the gathers the program counts;
  * format 6 saves the orders, a format-5 save still loads and gathers,
    and the verifier rejects an order that was corrupted.
"""

import contextlib
import dataclasses
import json
import os
import re
import unittest.mock

import jax
import numpy as np
import pytest
from conftest import pre_v6_columns, unfolded

from repro.analysis.diagnostics import VerificationError
from repro.analysis.verify import verify_network
from repro.core import sparse
from repro.core.pruning import (
    build_dictionaries,
    magnitude_prune,
    project_params,
)
from repro.engine import (
    CompileOptions,
    compile_network,
    load_program,
    make_forward,
    save_program,
)
from repro.engine.lowering import lower_matrix
from repro.engine.program import leading_order
from repro.models.cnn import (
    cnn_apply,
    conv_weight_names,
    init_cnn,
    mini_cnn_config,
)
from repro.models.resnet import init_resnet, resnet_apply, resnet_small_config
from repro.obs.trace import Tracer

BLOCK = TILE = 16
BACKENDS = [("xla", None), ("pallas", True)]
# conv1 (8 channels) is narrower than its 16-column tile
VGG_WIDTHS = (8, 16, 32)


@contextlib.contextmanager
def _every_layer_reorders(strategy: str):
    """Lower every layer, the FC too, under ``strategy``."""
    real = sparse.reorder_columns
    with unittest.mock.patch.object(
        sparse, "reorder_columns", lambda masks, _="pattern":
        real(masks, strategy),
    ):
        yield


def _vgg():
    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=VGG_WIDTHS)
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, 0.7)
    params, bits = project_params(params, build_dictionaries(params, names, 4))
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 1, 12, 12))
    return cfg, params, bits, x, cnn_apply


def _resnet():
    """Its 3x3 convs pattern-pruned, the rest dense, as in
    ``tests/test_resnet.py``."""
    cfg = resnet_small_config(input_hw=32)
    params = init_resnet(cfg, jax.random.PRNGKey(0))
    names = [s.name for s in cfg.layers() if s.kernel == 3]
    params = magnitude_prune(params, names, 0.7)
    params, bits = project_params(params, build_dictionaries(params, names, 4))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 3, 32, 32))
    return cfg, params, bits, x, resnet_apply


NETS = {"vgg": _vgg, "resnet": _resnet}


def _compile(net: str, strategy: str = "pattern", **kw):
    cfg, params, bits, x, ref = NETS[net]()
    with _every_layer_reorders(strategy):
        prog = compile_network(cfg, params, bits, options=CompileOptions(
            block=BLOCK, tile=TILE, verify="strict", **kw))
    return prog, x, ref(cfg, params, x)


def _assert_matches_reference(net: str, out, ref) -> None:
    """Within the tolerance the family's own tests hold the forward to:
    ``tests/test_engine.py`` for the chain, ``tests/test_resnet.py``'s
    relative error for the ResNet."""
    out, ref = np.asarray(out), np.asarray(ref)
    if net == "vgg":
        np.testing.assert_allclose(out, ref, atol=1e-3)
    else:
        assert np.abs(out - ref).max() / np.abs(ref).max() <= 1e-5


def _logits(prog, x, backend="xla", interpret=None):
    return np.asarray(
        make_forward(prog, backend=backend, interpret=interpret)(x)
    )


def _hlo_gathers(prog, x) -> int:
    """The gathers in the ``permute`` scope of the compiled forward."""
    hlo = make_forward(prog, backend="xla").lower(
        x, np.ones(x.shape[0], bool)
    ).compile().as_text()
    return len(re.findall(
        r"= \S+ gather\(.*op_name=\"[^\"]*/permute/", hlo
    ))


@pytest.mark.parametrize("backend,interpret", BACKENDS)
@pytest.mark.parametrize("strategy", sparse.REORDERS)
@pytest.mark.parametrize("net", NETS)
def test_folded_forward_matches_reference(net, strategy, backend,
                                          interpret):
    prog, x, ref = _compile(net, strategy)
    layout = prog.layout()
    # the rows of some layer read a stored order that is not channel order
    assert any(not np.array_equal(c.in_order, np.arange(len(c.in_order)))
               for c in [*prog.convs, prog.fc])
    # ... and no conv gathers: each reads its source as stored
    assert all(layout[c.name].gathers == 0 for c in prog.convs)
    assert prog.gathers() == layout["fc"].gathers <= 1
    _assert_matches_reference(net, _logits(prog, x, backend, interpret), ref)


@pytest.mark.parametrize("strategy", sparse.REORDERS)
def test_narrow_layer_real_channels_lead(strategy):
    """conv1's 8 channels are the first 8 of its 16 stored columns under
    every strategy: the padding sorts last, so the epilogue slices them."""
    prog, _, _ = _compile("vgg", strategy)
    conv1 = prog.convs[0]
    assert conv1.c_out < conv1.bp.n_out == TILE
    head = leading_order(conv1.bp, conv1.c_out)
    assert head is not None
    np.testing.assert_array_equal(
        np.sort(conv1.bp.new_order[conv1.c_out:]),
        np.arange(conv1.c_out, conv1.bp.n_out),
    )
    np.testing.assert_array_equal(prog.convs[1].in_order, head)
    assert not prog.layout()["conv1"].gather_out


def _residual_mismatch():
    """resnet_small with layer1.0.downsample's columns stored reversed:
    the shortcut then reaches layer1.0.conv3's add in another order."""
    prog, x, ref = _compile("resnet")
    i = [c.name for c in prog.convs].index("layer1.0.downsample")
    ds = prog.convs[i]
    wm = np.asarray(ds.bp.dense())[: ds.k_unpadded]  # rows as stored
    reverse = np.arange(ds.bp.n_out)[::-1].astype(np.int32)
    assert ds.c_out == ds.bp.n_out  # no padding to keep last
    with unittest.mock.patch.object(
        sparse, "reorder_columns", lambda masks, _="pattern": reverse
    ):
        bp = lower_matrix(wm, BLOCK, TILE)
    convs = list(prog.convs)
    convs[i] = dataclasses.replace(ds, bp=bp)
    return prog, dataclasses.replace(prog, convs=convs,
                                     certificate=None), x, ref


def test_residual_with_two_orders_gathers_once():
    prog, mixed, x, ref = _residual_mismatch()
    assert verify_network(mixed).ok
    layout, base = mixed.layout(), prog.layout()
    gathering = {n for n, lay in layout.items() if lay.gathers}
    assert gathering - {"fc"} == {"layer1.0.conv3"}
    assert layout["layer1.0.conv3"].gather_residual is not None
    assert layout["layer1.0.conv3"].gathers == 1
    assert mixed.gathers() == prog.gathers() + 1
    assert base["layer1.0.conv3"].gather_residual is None
    np.testing.assert_allclose(_logits(mixed, x), _logits(prog, x),
                               rtol=1e-5, atol=1e-5)
    _assert_matches_reference("resnet", _logits(mixed, x), ref)


@pytest.mark.parametrize("net", NETS)
def test_int8_program_folds_like_fp32(net):
    """Quantization acts per brick after the reorder, so an int8 program
    carries the fp32 one's orders and agrees with it to quantization
    error (the bound of ``tests/test_quantize.py``)."""
    prog, x, _ = _compile(net)
    progq, _, _ = _compile(net, precision="int8")
    for a, b in zip([*prog.convs, prog.fc], [*progq.convs, progq.fc]):
        np.testing.assert_array_equal(a.in_order, b.in_order)
    assert progq.gathers() == prog.gathers()
    ref, out = _logits(prog, x), _logits(progq, x)
    assert np.abs(out - ref).max() / np.abs(ref).max() < 0.05


def _gathering(prog):
    """``prog`` as a compiler before format 6 lowered it: channel-order
    rows and padding first, so every narrow layer gathers its output."""
    with pre_v6_columns():
        return unfolded(prog)


@pytest.mark.parametrize("net", NETS)
def test_stats_equal_the_gather_path(net):
    prog, x, _ = _compile(net)
    old = _gathering(prog)
    assert old.gathers() > prog.gathers()
    _, folded = make_forward(prog, backend="xla", collect_stats=True)(x)
    _, gathered = make_forward(old, backend="xla", collect_stats=True)(x)
    assert folded.layers.keys() == gathered.layers.keys()
    for name, st in folded.layers.items():
        np.testing.assert_array_equal(st.counts, gathered.layers[name].counts)
        assert st.windows == gathered.layers[name].windows


@pytest.mark.parametrize("case", ["vgg", "resnet", "vgg_pre_v6",
                                  "resnet_pre_v6", "residual"])
def test_compiled_hlo_holds_the_counted_gathers(case):
    if case == "residual":
        _, prog, x, _ = _residual_mismatch()
    else:
        prog, x, _ = _compile(case.split("_")[0])
        if case.endswith("pre_v6"):
            prog = _gathering(prog)
    n = prog.gathers()
    assert _hlo_gathers(prog, x) == n
    marked = [op for op, d in prog.op_list() if " gather(" in d]
    assert len(marked) == sum(1 for lay in prog.layout().values()
                              if lay.gathers)
    if case.endswith("pre_v6"):
        assert n > 1


def test_compile_span_counts_the_gathers():
    cfg, params, bits, _, _ = _vgg()
    tracer = Tracer()
    prog = compile_network(cfg, params, bits, options=CompileOptions(
        block=BLOCK, tile=TILE, tracer=tracer))
    (span,) = [s for s in tracer.spans("compile")
               if s.name == "compile_network"]
    assert span.args["gathers"] == prog.gathers()


# ------------------------------------------------------- the saved program


def test_v6_roundtrips_input_orders(tmp_path):
    prog, x, _ = _compile("vgg", "hybrid")
    path = save_program(str(tmp_path / "v6"), prog)
    with open(os.path.join(path, "program.json")) as f:
        assert json.load(f)["format_version"] == 6
    loaded = load_program(path)
    for a, b in zip([*prog.convs, prog.fc], [*loaded.convs, loaded.fc]):
        np.testing.assert_array_equal(a.in_order, b.in_order)
    assert loaded.gathers() == prog.gathers()
    np.testing.assert_array_equal(_logits(loaded, x), _logits(prog, x))


def _corrupt(order: np.ndarray, how: str) -> np.ndarray:
    order = np.array(order)
    if how == "duplicate":
        order[1] = order[0]
    else:  # swap: still a permutation, but not the one the rows read
        order[[0, 1]] = order[[1, 0]]
    return order


@pytest.mark.parametrize("how", ["duplicate", "swap"])
def test_verifier_rejects_a_corrupted_in_order(how, tmp_path):
    prog, _, _ = _compile("vgg")
    assert verify_network(prog).ok
    convs = list(prog.convs)
    convs[1] = dataclasses.replace(
        convs[1], in_order=_corrupt(convs[1].in_order, how)
    )
    bad = dataclasses.replace(prog, convs=convs)
    report = verify_network(bad)
    assert "V208" in report.rules() and not report.ok
    # the same corruption in a saved program stops its load
    path = save_program(str(tmp_path / "p"), prog)
    fname = os.path.join(path, "conv2.in_order.npy")
    np.save(fname, _corrupt(np.load(fname), how))
    with pytest.raises(VerificationError, match="V208"):
        load_program(path)
