"""Lowering: pattern-pruned CNN params -> executable ``CompiledNetwork``.

Per conv layer the dense weights ``[C_out, C_in, K, K]`` are viewed as the
im2col matmul ``[C_in*K*K, C_out]`` — rows channel-major, or tap-major
where K spans more than one block (:func:`patch_order`) — zero-padded up
to (block, tile) multiples, and compressed into a
:class:`BlockPatternWeight` via the *exact* path of
``core/sparse.build_block_pattern``: block masks are the true nonzero
structure (``nonzero_block_masks``), so reorder -> compress -> index
produces real kernel operands and the compressed program computes
bit-the-same weights as the pruned dense network.  The FC head is lowered
onto the same path, and so are dense convs (a ResNet's stem, 1x1 convs and
shortcuts): their every brick is nonzero and stored.

A layer whose params carry batch-norm statistics (``{w, bn: {gamma, beta,
mean, var}}``) is folded into one weight and bias first
(:func:`fold_bn`); a per-output-channel scale keeps every zero weight zero,
so the pattern bits hold.

Pattern bits (``core/pruning.PruneResult.pattern_bits``) ride along per
layer so the compiled artifact can be priced on the crossbar model
(``CompiledNetwork.hardware_report``); when absent they are recovered from
the weights' nonzero masks.
"""

from __future__ import annotations

import dataclasses
import warnings

import numpy as np

from repro.core.mapping import CrossbarConfig, MappingCandidate
from repro.core.mapsearch import (
    MappingSearchConfig,
    MappingSearchResult,
    choose_fc_reorder,
    search_layer_mapping,
)
from repro.core.patterns import kernel_masks, masks_to_bits
from repro.core.quantize import n_cell_slices, quantize_bp
from repro.core.sparse import (
    BlockPatternWeight,
    build_block_pattern,
    nonzero_block_masks,
)
from repro.engine.program import CompiledConv, CompiledFC, CompiledNetwork
from repro.models.cnn import CNNConfig, ConvSpec, out_sizes
from repro.models.resnet import ResNetConfig
from repro.obs.trace import NULL_TRACER, Tracer

__all__ = ["EngineConfig", "CompileOptions", "PRECISIONS", "PATCH_ORDERS",
           "patch_order", "conv_matrix", "fold_bn", "lower_matrix",
           "lower_conv",
           "lower_fc", "conv_mapping_search", "compile_network"]

PRECISIONS = ("fp32", "int8")
# im2col feature orders: 'channel' (row c*k*k + tap) and 'tap' (tap*c_in + c)
PATCH_ORDERS = ("channel", "tap")
# fewest input channels a tap-major layer may have (see patch_order)
MIN_TAP_CHANNELS = 8


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Compile-time geometry of the spmm lowering.

    Defaults match the Pallas kernel's MXU-aligned bricks; smaller values
    trade alignment for finer-grained zero compression (useful on the XLA
    CPU path where kernel-granular blocks expose the pruning sparsity).

    ``precision`` selects the stored weight representation: 'fp32' (the
    historical exact path) or 'int8' — per-row-group symmetric int8
    bricks + fp32 scales (``core/quantize.py``), the paper's bit-sliced
    cell storage made executable.  ``cell_bits`` is the RRAM cell width
    the int payload is sliced over for hardware pricing (4-bit cells by
    default, matching ``CrossbarConfig``); it does not change the stored
    numbers, only how ``hardware_report`` derives cells-per-weight.
    """

    block: int = 128
    tile: int = 128
    precision: str = "fp32"
    cell_bits: int = 4

    def __post_init__(self):
        if self.precision not in PRECISIONS:
            raise ValueError(
                f"precision must be one of {PRECISIONS}, got "
                f"{self.precision!r}"
            )
        if self.cell_bits < 1:
            raise ValueError(f"cell_bits must be >= 1, got {self.cell_bits}")


@dataclasses.dataclass(frozen=True)
class CompileOptions:
    """Everything :func:`compile_network` accepts beyond the network itself.

    One frozen object in place of the loose kwargs that accreted on the
    compile entry point (``ecfg``/``precision``/``tracer``/``verify``/
    ``optimize``) — build it once, thread it through configs and tests,
    and the compile call stays ``compile_network(cfg, params, bits,
    options=opts)`` no matter how many knobs exist.

    The geometry fields mirror :class:`EngineConfig` (same defaults, same
    validation); :meth:`engine_config` projects them back out for the
    ``lower_*`` helpers, which keep taking a plain ``EngineConfig``.

    ``verify``/``optimize``/``tracer`` carry the compile-pass switches —
    see :func:`compile_network` for their semantics.
    """

    block: int = 128
    tile: int = 128
    precision: str = "fp32"
    cell_bits: int = 4
    verify: str | None = None
    optimize: "str | MappingSearchConfig | None" = None
    tracer: Tracer | None = None

    def __post_init__(self):
        if self.precision not in PRECISIONS:
            raise ValueError(
                f"precision must be one of {PRECISIONS}, got "
                f"{self.precision!r}"
            )
        if self.cell_bits < 1:
            raise ValueError(f"cell_bits must be >= 1, got {self.cell_bits}")
        if self.verify not in (None, "warn", "strict"):
            raise ValueError(
                f"verify must be None, 'warn' or 'strict', got "
                f"{self.verify!r}"
            )
        if self.optimize is not None and self.optimize != "auto" and not (
            isinstance(self.optimize, MappingSearchConfig)
        ):
            raise ValueError(
                f"optimize must be None, 'auto' or a MappingSearchConfig, "
                f"got {self.optimize!r}"
            )

    @classmethod
    def from_engine_config(cls, ecfg: EngineConfig, **kw) -> "CompileOptions":
        """Lift a lowering geometry into full compile options."""
        return cls(block=ecfg.block, tile=ecfg.tile,
                   precision=ecfg.precision, cell_bits=ecfg.cell_bits, **kw)

    def engine_config(self) -> EngineConfig:
        """The :class:`EngineConfig` these options imply."""
        return EngineConfig(block=self.block, tile=self.tile,
                            precision=self.precision,
                            cell_bits=self.cell_bits)


def _pad_axis(a: np.ndarray, axis: int, mult: int) -> np.ndarray:
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return np.pad(a, widths)


def patch_order(c_in: int, kernel: int, block: int) -> str:
    """The im2col feature order of a conv layer: ``'tap'`` where its K
    (``c_in * kernel**2``) spans more than one ``block`` and it has at
    least :data:`MIN_TAP_CHANNELS` input channels, else ``'channel'``.

    Tap-major patches are the ``kernel**2`` shifted NHWC slices side by
    side, a plain lane-aligned concatenation, and a K-block then holds
    one or two taps of every channel, so a tap no kernel of the layer
    uses leaves whole zero bricks for the lossless compression to drop.
    A K that fits one block gains no bricks from the order, and its
    narrow slices (3 lanes for RGB) concatenate worse than they
    transpose, so it stays channel-major.  So does a layer on a
    few-channel input at any K: on the chip each of its ``kernel**2``
    slices is padded to 128 lanes, which made the tap-major 7x7 stem of
    ResNet-50 (3 channels, K = 147) ask for 38 GB at batch 128.
    """
    if c_in < MIN_TAP_CHANNELS:
        return "channel"
    return "tap" if c_in * kernel * kernel > block else "channel"


def conv_matrix(w: np.ndarray, order: str = "channel") -> np.ndarray:
    """[C_out, C_in, Kh, Kw] -> im2col matmul view [C_in*Kh*Kw, C_out].

    The rows follow the executor's patch layout for ``order``
    (:data:`PATCH_ORDERS`): ``'channel'`` puts weight ``(c, dy, dx)`` at
    row ``c * Kh*Kw + (dy*Kw + dx)``, ``'tap'`` at row
    ``(dy*Kw + dx) * C_in + c``.
    """
    w = np.asarray(w)
    co, ci = w.shape[:2]
    if order == "channel":
        return w.reshape(co, -1).T
    if order == "tap":
        return w.reshape(co, ci, -1).transpose(2, 1, 0).reshape(-1, co)
    raise ValueError(f"order must be one of {PATCH_ORDERS}, got {order!r}")


def lower_matrix(
    wm: np.ndarray, block: int, tile: int, precision: str = "fp32",
    tracer: Tracer | None = None, reorder: str = "pattern",
) -> BlockPatternWeight:
    """Pad a dense [K, N] matrix to (block, tile) multiples and compress it
    losslessly from its nonzero structure; ``precision='int8'`` then
    quantizes the compressed bricks (``core/quantize.quantize_bp``).

    ``reorder`` selects the column-permutation strategy
    (``core/sparse.REORDERS``); every strategy yields the same semantics
    through the stored inverse permutation.

    With a ``tracer`` the lowering phases land as ``compile``-category
    spans: ``prune`` (nonzero-structure mask discovery), ``reorder`` +
    ``pack`` (inside ``build_block_pattern``), ``quantize``."""
    if precision not in PRECISIONS:
        raise ValueError(
            f"precision must be one of {PRECISIONS}, got {precision!r}"
        )
    tracer = tracer or NULL_TRACER
    wp = _pad_axis(_pad_axis(np.asarray(wm, np.float32), 0, block), 1, tile)
    with tracer.span("prune", cat="compile", shape=list(wp.shape)):
        masks = nonzero_block_masks(wp, block)
    bp = build_block_pattern(wp, block=block, tile=tile, masks=masks,
                             tracer=tracer, reorder=reorder)
    if precision == "int8":
        with tracer.span("quantize", cat="compile", shape=list(wp.shape)):
            bp = quantize_bp(bp)
    return bp


def fold_bn(w, bn: dict, eps: float) -> tuple[np.ndarray, np.ndarray]:
    """Inference batch norm after a bias-free conv, folded into the conv:
    ``(w * s, beta - mean * s)`` per output channel, ``s = gamma /
    sqrt(var + eps)``, computed in float64 and stored as float32."""
    scale = np.asarray(bn["gamma"], np.float64) / np.sqrt(
        np.asarray(bn["var"], np.float64) + eps
    )
    w = np.asarray(w, np.float64) * scale[:, None, None, None]
    b = np.asarray(bn["beta"], np.float64) - np.asarray(
        bn["mean"], np.float64
    ) * scale
    return w.astype(np.float32), b.astype(np.float32)


def lower_conv(
    spec: ConvSpec,
    w: np.ndarray,
    b: np.ndarray,
    pattern_bits: np.ndarray | None,
    out_hw: int,
    ecfg: EngineConfig,
    tracer: Tracer | None = None,
    mapping: MappingCandidate | None = None,
) -> CompiledConv:
    """One conv ``spec`` with weight ``w`` and bias ``b`` as an im2col
    spmm.  Pattern bits not given are read off the weights' nonzero
    masks: a dense kernel of any size gets its all-ones pattern."""
    name = spec.name
    w = np.asarray(w, np.float32)
    c_out, c_in, kh, kw = w.shape
    if kh != kw:
        raise ValueError(f"{name}: non-square kernel {kh}x{kw}")
    if (c_in, c_out, kh) != (spec.c_in, spec.c_out, spec.kernel):
        raise ValueError(
            f"{name}: weight {w.shape} does not match the layer's "
            f"(c_out, c_in, k) = ({spec.c_out}, {spec.c_in}, {spec.kernel})"
        )
    if pattern_bits is None:
        pattern_bits = masks_to_bits(kernel_masks(w))
    reorder = mapping.reorder if mapping is not None else "pattern"
    order = patch_order(c_in, kh, ecfg.block)
    return CompiledConv(
        name=name,
        c_in=c_in,
        c_out=c_out,
        kernel=kh,
        out_hw=out_hw,
        bp=lower_matrix(conv_matrix(w, order), ecfg.block, ecfg.tile,
                        ecfg.precision, tracer=tracer, reorder=reorder),
        bias=np.asarray(b, np.float32).copy(),
        pattern_bits=np.asarray(pattern_bits, np.int64).copy(),
        mapping=mapping,
        patch_order=order,
        stride=spec.stride,
        src=spec.src,
        residual=spec.residual,
        relu=spec.relu,
        norm=spec.norm,
        pool=spec.pool,
    )


def lower_fc(
    w: np.ndarray, b: np.ndarray, ecfg: EngineConfig,
    tracer: Tracer | None = None, reorder: str = "pattern",
) -> CompiledFC:
    w = np.asarray(w, np.float32)
    d_in, d_out = w.shape
    return CompiledFC(
        d_in=d_in,
        d_out=d_out,
        bp=lower_matrix(w, ecfg.block, ecfg.tile, ecfg.precision,
                        tracer=tracer, reorder=reorder),
        bias=np.asarray(b, np.float32).copy(),
        reorder=reorder,
    )


def _fixed_candidate(ecfg: EngineConfig) -> MappingCandidate:
    """The fixed scheme a search must match-or-beat: the paper's default
    geometry, with cells/weight derived from the program's precision the
    same way ``hardware_report`` derives it."""
    base = CrossbarConfig()
    cells = (
        n_cell_slices(ecfg.cell_bits)
        if ecfg.precision == "int8"
        else base.cells_per_weight
    )
    return MappingCandidate(
        rows=base.rows,
        cols=base.cols,
        cells_per_weight=cells,
        ou_rows=base.ou_rows,
        ou_cols=base.ou_cols,
    )


def conv_mapping_search(
    w: np.ndarray,
    pattern_bits: np.ndarray | None,
    out_hw: int,
    ecfg: EngineConfig = EngineConfig(),
    search: MappingSearchConfig | None = None,
) -> MappingSearchResult:
    """Run the mapping design-space search for one conv layer.

    Builds exactly the search inputs ``compile_network(optimize=...)``
    uses — the layer's pattern bits, the block masks of the padded
    matmul view in the rows the lowering stores (:func:`patch_order`),
    the precision-derived fixed scheme — and returns the full
    :class:`~repro.core.mapsearch.MappingSearchResult` (benchmarks call
    this standalone to time the search and check determinism against the
    compiled program).
    """
    w = np.asarray(w, np.float32)
    if pattern_bits is None:
        pattern_bits = masks_to_bits(kernel_masks(w))
    kernel_size = w.shape[2] * w.shape[3]
    order = patch_order(w.shape[1], w.shape[2], ecfg.block)
    wp = _pad_axis(
        _pad_axis(conv_matrix(w, order), 0, ecfg.block), 1, ecfg.tile
    )
    masks = nonzero_block_masks(wp, ecfg.block)
    return search_layer_mapping(
        np.asarray(pattern_bits, np.int64),
        kernel_size=kernel_size,
        windows=out_hw * out_hw,
        fixed=_fixed_candidate(ecfg),
        search=search,
        masks=masks,
        tile=ecfg.tile,
    )


def compile_network(
    cfg: CNNConfig | ResNetConfig,
    params: dict,
    pattern_bits: dict[str, np.ndarray] | None = None,
    ecfg: EngineConfig | None = None,
    precision: str | None = None,
    tracer: Tracer | None = None,
    verify: str | None = None,
    optimize: "str | MappingSearchConfig | None" = None,
    *,
    options: CompileOptions | None = None,
) -> CompiledNetwork:
    """Lower a (pruned) CNN end-to-end into a :class:`CompiledNetwork`.

    Args:
      cfg: network geometry (``models.cnn.CNNConfig`` or
        ``models.resnet.ResNetConfig``): its ``layers()`` are lowered in
        order, one loop for every network.
      params: parameter pytree ``{<layer>: {w, b} or {w, bn}, ..., fc: {w,
        b}}``; a layer with ``bn`` statistics is folded (:func:`fold_bn`,
        eps ``cfg.bn_eps``).
      pattern_bits: per-conv packed pattern bitmasks
        (``PruneResult.pattern_bits``); recovered from the weights' nonzero
        structure for layers not listed (dense layers: all-ones).
      options: a :class:`CompileOptions` carrying the lowering geometry
        and every compile-pass switch.  This is the preferred form; the
        loose keyword arguments below are deprecated aliases kept for one
        release and cannot be combined with ``options=``.
      ecfg: deprecated — spmm lowering geometry (block/tile, stored
        precision); use the matching :class:`CompileOptions` fields.
      precision: deprecated — shorthand override of ``ecfg.precision``
        ('fp32'/'int8'); use ``CompileOptions(precision=...)``.
      tracer: deprecated alias of ``CompileOptions(tracer=...)``: optional
        span tracer (``obs/trace.py``).  The whole compile becomes a
        ``compile_network`` span containing one ``lower:<name>`` span per
        layer, each wrapping its phase spans
        (prune -> reorder -> pack -> quantize), so a Perfetto load of the
        trace shows exactly where compile time goes.
      verify: deprecated alias of ``CompileOptions(verify=...)``:
        post-condition check of the compiled program via
        ``repro.analysis.verify`` — ``'strict'`` raises
        :class:`~repro.analysis.diagnostics.VerificationError` on any
        error diagnostic, ``'warn'`` emits a Python warning instead,
        ``None`` (default) skips the pass on this hot compile path.
        When the structural pass is clean, the range certification pass
        (``repro.analysis.ranges``, its own ``ranges`` compile span)
        also runs: V5xx diagnostics join the same report and the
        resulting :class:`~repro.analysis.ranges.RangeCertificate` is
        attached as ``program.certificate``.
      optimize: deprecated alias of ``CompileOptions(optimize=...)``:
        per-layer mapping design-space search
        (``core/mapsearch.py``) — ``'auto'`` uses the default
        :class:`~repro.core.mapsearch.MappingSearchConfig`, or pass a
        config to pick axes/seed/budget; ``None`` (default) keeps the
        fixed paper scheme.  The chosen candidates ride on
        ``CompiledConv.mapping`` (priced by ``hardware_report``, saved in
        manifest v3) and each layer's search lands as a
        ``search:<name>`` compile span.

    The deprecated-kwargs form compiles a bit-identical program to the
    equivalent ``options=`` form (``tests/test_compile_options.py`` pins
    this), it just warns on the way.
    """
    legacy = [
        name for name, value in (
            ("ecfg", ecfg), ("precision", precision), ("tracer", tracer),
            ("verify", verify), ("optimize", optimize),
        ) if value is not None
    ]
    if options is not None:
        if legacy:
            raise TypeError(
                "compile_network: pass options=CompileOptions(...) alone; "
                f"also got deprecated kwarg(s) {legacy}"
            )
    else:
        if legacy:
            warnings.warn(
                "compile_network's loose kwargs "
                "(ecfg/precision/tracer/verify/optimize) are deprecated; "
                "pass options=CompileOptions(...) instead",
                DeprecationWarning, stacklevel=2,
            )
        base = ecfg if ecfg is not None else EngineConfig()
        options = CompileOptions(
            block=base.block,
            tile=base.tile,
            precision=precision if precision is not None else base.precision,
            cell_bits=base.cell_bits,
            verify=verify,
            optimize=optimize,
            tracer=tracer,
        )
    ecfg = options.engine_config()
    verify = options.verify
    if isinstance(options.optimize, MappingSearchConfig):
        search_cfg = options.optimize
    elif options.optimize == "auto":
        search_cfg = MappingSearchConfig()
    else:
        search_cfg = None
    tracer = options.tracer or NULL_TRACER
    pattern_bits = pattern_bits or {}
    convs = []
    layers = cfg.layers()
    sizes = out_sizes(layers, cfg.input_hw)
    with tracer.span(
        "compile_network", cat="compile",
        layers=len(layers) + 1, precision=ecfg.precision,
        optimize=search_cfg is not None,
    ):
        for spec in layers:
            name = spec.name
            hw = sizes[name][0]
            p = params[name]
            w, b = (fold_bn(p["w"], p["bn"], cfg.bn_eps) if "bn" in p
                    else (p["w"], p["b"]))
            mapping = None
            if search_cfg is not None:
                with tracer.span(f"search:{name}", cat="compile") as sp:
                    res = conv_mapping_search(
                        w, pattern_bits.get(name), hw, ecfg, search_cfg,
                    )
                    mapping = res.chosen
                    sp.args.update(
                        evaluations=res.evaluations,
                        improved=res.improved,
                        chosen=mapping.to_manifest(),
                        area_cells=res.cost.area_cells,
                        fixed_area_cells=res.fixed_cost.area_cells,
                    )
            with tracer.span(f"lower:{name}", cat="compile"):
                convs.append(
                    lower_conv(
                        spec,
                        w,
                        b,
                        pattern_bits.get(name),
                        out_hw=hw,
                        ecfg=ecfg,
                        tracer=tracer,
                        mapping=mapping,
                    )
                )
        fc_reorder = "pattern"
        if search_cfg is not None:
            with tracer.span("search:fc", cat="compile") as sp:
                wfc = _pad_axis(
                    _pad_axis(
                        np.asarray(params["fc"]["w"], np.float32),
                        0, ecfg.block,
                    ),
                    1, ecfg.tile,
                )
                fc_reorder, counts = choose_fc_reorder(
                    nonzero_block_masks(wfc, ecfg.block),
                    ecfg.tile, search_cfg.reorders,
                )
                sp.args.update(chosen=fc_reorder, bricks=counts)
        with tracer.span("lower:fc", cat="compile"):
            fc = lower_fc(params["fc"]["w"], params["fc"]["b"], ecfg,
                          tracer=tracer, reorder=fc_reorder)
    program = CompiledNetwork(
        config=cfg, convs=convs, fc=fc, block=ecfg.block, tile=ecfg.tile,
        precision=ecfg.precision, cell_bits=ecfg.cell_bits,
    )
    if verify is not None:
        from repro.analysis.ranges import analyze_network
        from repro.analysis.verify import verify_network

        with tracer.span("verify", cat="compile"):
            report = verify_network(program)
        # the range certification pass only runs over structurally sound
        # programs (its interval math assumes the verifier's contracts);
        # V5xx diagnostics land in the same report, the certificate rides
        # on the program (priced by hardware_report, saved in manifest v4)
        if report.ok:
            with tracer.span("ranges", cat="compile") as sp:
                report, cert = analyze_network(program, report=report)
                program.certificate = cert
                sp.args.update(
                    fp32_safe=cert.fp32_safe,
                    certified_cells=cert.certified_cells(),
                )
        if verify == "strict":
            report.raise_if_errors("compile_network")
        elif not report.ok:
            warnings.warn(
                "compile_network produced a program that fails "
                "verification:\n" + report.format(),
                stacklevel=2,
            )
    return program
