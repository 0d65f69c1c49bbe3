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
shortcuts): their every brick is nonzero and stored.  A grouped conv
(``ConvSpec.groups``; a MobileNetV2's depthwise 3x3s) is the same spmm on
its block-diagonal matrix (:func:`conv_matrix`), whose zero bricks the
compression drops like any other.

A layer whose params carry batch-norm statistics (``{w, bn: {gamma, beta,
mean, var}}``) is folded into one weight and bias first
(:func:`fold_bn`); a per-output-channel scale keeps every zero weight zero,
so the pattern bits hold.

Pattern bits (``core/pruning.PruneResult.pattern_bits``) ride along per
layer so the compiled artifact can be priced on the crossbar model
(``CompiledNetwork.hardware_report``); when absent they are recovered from
the weights' nonzero masks.

Channel orders: a layer's kernel writes its outputs in its reordered
column order, and the forward keeps them so.  Each conv's weight rows (and
the FC's) are therefore built in the channel order of the tensor they
read — the image's, or the producing conv's stored order — and recorded
as its ``in_order``, which leaves the forward no gather between layers
(``program.CompiledNetwork.layout``).

Kernel reordering for depthwise convs: a depthwise conv's stored columns
keep its input's order, so output tile ``j`` reads only the ``j``-th
block of channels of each tap.  Its kernels are instead reordered one
layer up: :func:`compile_network` stores the columns of the conv that
produces a depthwise's input (where that producer is dense and feeds
nothing else) in the stable sort of the depthwise's kernels by pattern,
so each block of channels holds few patterns and a tap that none of them
uses leaves a zero brick.
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
    predicted_tile_nnz,
)
from repro.engine.program import (
    CompiledConv,
    CompiledFC,
    CompiledNetwork,
    leading_order,
)
from repro.models.cnn import CNNConfig, ConvSpec, out_sizes
from repro.models.mobilenet import MobileNetV2Config
from repro.models.resnet import ResNetConfig
from repro.obs.trace import NULL_TRACER, Tracer

__all__ = ["CompileOptions", "PRECISIONS", "PATCH_ORDERS",
           "patch_order", "group_dense", "conv_matrix", "fold_bn",
           "lower_matrix",
           "lower_conv",
           "lower_fc", "conv_mapping_search", "compile_network"]

PRECISIONS = ("fp32", "int8")
# im2col feature orders: 'channel' (row c*k*k + tap) and 'tap' (tap*c_in + c)
PATCH_ORDERS = ("channel", "tap")
# fewest input channels a tap-major layer may have (see patch_order)
MIN_TAP_CHANNELS = 8


@dataclasses.dataclass(frozen=True)
class CompileOptions:
    """Everything :func:`compile_network` accepts beyond the network itself.

    The lowering geometry:

    * ``block``/``tile`` — the spmm brick's K rows and N columns.  The
      defaults match the Pallas kernel's MXU-aligned bricks; smaller
      values trade alignment for finer-grained zero compression (useful
      on the XLA CPU path, where kernel-granular blocks expose the
      pruning sparsity).
    * ``precision`` — the stored weight representation: 'fp32' (exact)
      or 'int8', per-row-group symmetric int8 bricks + fp32 scales
      (``core/quantize.py``), the paper's bit-sliced cell storage made
      executable.
    * ``cell_bits`` — the RRAM cell width the int payload is sliced over
      for hardware pricing (4-bit cells by default, matching
      ``CrossbarConfig``); it does not change the stored numbers, only
      how ``hardware_report`` derives cells-per-weight.

    The compile-pass switches:

    * ``verify`` — post-condition check of the compiled program via
      ``repro.analysis.verify``: ``'strict'`` raises
      :class:`~repro.analysis.diagnostics.VerificationError` on any error
      diagnostic, ``'warn'`` emits a Python warning instead, ``None``
      (default) skips the pass on this hot compile path.  When the
      structural pass is clean, the range certification pass
      (``repro.analysis.ranges``, its own ``ranges`` compile span) also
      runs: V5xx diagnostics join the same report and the resulting
      :class:`~repro.analysis.ranges.RangeCertificate` is attached as
      ``program.certificate``.
    * ``optimize`` — per-layer mapping design-space search
      (``core/mapsearch.py``): ``'auto'`` uses the default
      :class:`~repro.core.mapsearch.MappingSearchConfig`, or pass a config
      to pick axes/seed/budget; ``None`` (default) keeps the fixed paper
      scheme.  The chosen candidates ride on ``CompiledConv.mapping``
      (priced by ``hardware_report``, saved in the manifest) and each
      layer's search lands as a ``search:<name>`` compile span.
    * ``tracer`` — optional span tracer (``obs/trace.py``).  The whole
      compile becomes a ``compile_network`` span containing one
      ``lower:<name>`` span per layer, each wrapping its phase spans
      (prune -> reorder -> pack -> quantize), so a Perfetto load of the
      trace shows exactly where compile time goes.  The
      ``compile_network`` span's ``gathers`` arg counts the channel
      gathers the forward will trace (``CompiledNetwork.gathers``).
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


def group_dense(w: np.ndarray, groups: int = 1) -> np.ndarray:
    """A grouped conv's weight ``[C_out, C_in/groups, Kh, Kw]`` as the
    full ``[C_out, C_in, Kh, Kw]`` one, zero outside each output channel's
    group (output channel ``o`` of group ``g = o // (C_out/groups)`` reads
    input channels ``g * C_in/groups`` on)."""
    w = np.asarray(w)
    if groups == 1:
        return w
    co, cig = w.shape[:2]
    if co % groups:
        raise ValueError(f"C_out={co} is not a multiple of groups={groups}")
    first = (np.arange(co) // (co // groups)) * cig
    out = np.zeros((co, cig * groups) + w.shape[2:], w.dtype)
    out[np.arange(co)[:, None], first[:, None] + np.arange(cig)] = w
    return out


def conv_matrix(
    w: np.ndarray, order: str = "channel", in_order=None, groups: int = 1,
) -> np.ndarray:
    """[C_out, C_in/groups, Kh, Kw] -> im2col matmul view [C_in*Kh*Kw,
    C_out].

    The rows follow the executor's patch layout for ``order``
    (:data:`PATCH_ORDERS`): ``'channel'`` puts weight ``(c, dy, dx)`` at
    row ``c * Kh*Kw + (dy*Kw + dx)``, ``'tap'`` at row
    ``(dy*Kw + dx) * C_in + c``, where ``c`` is the position of the input
    channel in ``in_order`` (position ``i`` holds channel
    ``in_order[i]``; None: channel order).  A grouped conv's matrix is
    block-diagonal (:func:`group_dense`): zero where an output channel
    meets an input channel outside its group.
    """
    w = group_dense(w, groups)
    if in_order is not None:
        w = w[:, np.asarray(in_order)]
    co, ci = w.shape[:2]
    if order == "channel":
        return w.reshape(co, -1).T
    if order == "tap":
        return w.reshape(co, ci, -1).transpose(2, 1, 0).reshape(-1, co)
    raise ValueError(f"order must be one of {PATCH_ORDERS}, got {order!r}")


def lower_matrix(
    wm: np.ndarray, block: int, tile: int, precision: str = "fp32",
    tracer: Tracer | None = None, reorder: str = "pattern",
    order: np.ndarray | None = None,
) -> BlockPatternWeight:
    """Pad a dense [K, N] matrix to (block, tile) multiples and compress it
    losslessly from its nonzero structure; ``precision='int8'`` then
    quantizes the compressed bricks (``core/quantize.quantize_bp``).

    ``reorder`` selects the column-permutation strategy
    (``core/sparse.REORDERS``); every strategy yields the same semantics
    through the stored inverse permutation.  ``order`` (a permutation of
    the N real columns) fixes the stored column order instead, the
    padding columns last.

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
    if order is not None:
        order = np.concatenate([np.asarray(order, np.int32), np.arange(
            len(order), wp.shape[1], dtype=np.int32)])
    bp = build_block_pattern(wp, block=block, tile=tile, masks=masks,
                             tracer=tracer, reorder=reorder, fixed_order=order)
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
    options: CompileOptions,
    tracer: Tracer | None = None,
    mapping: MappingCandidate | None = None,
    in_order: np.ndarray | None = None,
    out_order: np.ndarray | None = None,
) -> CompiledConv:
    """One conv ``spec`` with weight ``w`` ``[c_out, c_in // groups, k,
    k]`` and bias ``b`` as an im2col spmm, its rows in ``in_order``, the
    channel order of the tensor it reads (None: channel order).  Its
    stored columns are in ``out_order`` where given, in ``in_order`` for a
    depthwise conv, else in the column reorder's own.  Pattern bits
    ``[c_out, c_in // groups]`` not given are read off the weights'
    nonzero masks: a dense kernel of any size gets its all-ones pattern."""
    name = spec.name
    w = np.asarray(w, np.float32)
    c_out, c_in_g, kh, kw = w.shape
    g = spec.groups
    if kh != kw:
        raise ValueError(f"{name}: non-square kernel {kh}x{kw}")
    if spec.c_in % g or spec.c_out % g:
        raise ValueError(
            f"{name}: groups={g} does not divide c_in={spec.c_in} and "
            f"c_out={spec.c_out}"
        )
    if (c_in_g, c_out, kh) != (spec.c_in // g, spec.c_out, spec.kernel):
        raise ValueError(
            f"{name}: weight {w.shape} does not match the layer's "
            f"(c_out, c_in // groups, k) = ({spec.c_out}, "
            f"{spec.c_in // g}, {spec.kernel})"
        )
    c_in = spec.c_in
    if pattern_bits is None:
        pattern_bits = masks_to_bits(kernel_masks(w))
    reorder = mapping.reorder if mapping is not None else "pattern"
    order = patch_order(c_in, kh, options.block)
    in_order = _order_or_identity(in_order, c_in)
    if out_order is None and spec.depthwise:
        out_order = in_order
    return CompiledConv(
        name=name,
        c_in=c_in,
        c_out=c_out,
        kernel=kh,
        out_hw=out_hw,
        bp=lower_matrix(conv_matrix(w, order, in_order, g), options.block,
                        options.tile, options.precision, tracer=tracer,
                        reorder=reorder, order=out_order),
        bias=np.asarray(b, np.float32).copy(),
        pattern_bits=np.asarray(pattern_bits, np.int64).copy(),
        mapping=mapping,
        patch_order=order,
        stride=spec.stride,
        src=spec.src,
        residual=spec.residual,
        activation=spec.activation,
        norm=spec.norm,
        pool=spec.pool,
        in_order=in_order,
        groups=g,
    )


def _order_or_identity(order, n: int) -> np.ndarray:
    if order is None:
        return np.arange(n, dtype=np.int32)
    return np.asarray(order, np.int32).copy()


def lower_fc(
    w: np.ndarray, b: np.ndarray, options: CompileOptions,
    tracer: Tracer | None = None, reorder: str = "pattern",
    in_order: np.ndarray | None = None,
) -> CompiledFC:
    """The FC head ``w`` [d_in, d_out], its rows in ``in_order`` (None:
    channel order)."""
    w = np.asarray(w, np.float32)
    d_in, d_out = w.shape
    in_order = _order_or_identity(in_order, d_in)
    return CompiledFC(
        d_in=d_in,
        d_out=d_out,
        bp=lower_matrix(w[in_order], options.block, options.tile,
                        options.precision, tracer=tracer, reorder=reorder),
        bias=np.asarray(b, np.float32).copy(),
        reorder=reorder,
        in_order=in_order,
    )


def _fixed_candidate(options: CompileOptions) -> MappingCandidate:
    """The fixed scheme a search must match-or-beat: the paper's default
    geometry, with cells/weight derived from the program's precision the
    same way ``hardware_report`` derives it."""
    base = CrossbarConfig()
    cells = (
        n_cell_slices(options.cell_bits)
        if options.precision == "int8"
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
    options: CompileOptions = CompileOptions(),
    search: MappingSearchConfig | None = None,
) -> MappingSearchResult:
    """Run the mapping design-space search for one conv layer.

    Builds exactly the search inputs ``CompileOptions(optimize=...)``
    uses — the layer's pattern bits, the block masks of the padded
    matmul view in the row order the lowering stores (:func:`patch_order`;
    input channels in channel order, before the layer's ``in_order``
    permutes them), the precision-derived fixed scheme — and returns the
    full
    :class:`~repro.core.mapsearch.MappingSearchResult` (benchmarks call
    this standalone to time the search and check determinism against the
    compiled program).
    """
    w = np.asarray(w, np.float32)
    if pattern_bits is None:
        pattern_bits = masks_to_bits(kernel_masks(w))
    kernel_size = w.shape[2] * w.shape[3]
    order = patch_order(w.shape[1], w.shape[2], options.block)
    wp = _pad_axis(
        _pad_axis(conv_matrix(w, order), 0, options.block), 1, options.tile
    )
    masks = nonzero_block_masks(wp, options.block)
    return search_layer_mapping(
        np.asarray(pattern_bits, np.int64),
        kernel_size=kernel_size,
        windows=out_hw * out_hw,
        fixed=_fixed_candidate(options),
        search=search,
        masks=masks,
        tile=options.tile,
    )


def _layer_bits(params: dict, pattern_bits: dict, name: str) -> np.ndarray:
    bits = pattern_bits.get(name)
    if bits is None:
        bits = masks_to_bits(kernel_masks(np.asarray(params[name]["w"])))
    return np.asarray(bits, np.int64)


def depthwise_producer_orders(
    layers: list[ConvSpec], params: dict, pattern_bits: dict
) -> dict[str, np.ndarray]:
    """``{producer: column order}``: the kernel reordering of each
    depthwise conv, imposed on the conv that produces its input.

    The order is the stable sort of the depthwise's kernels by pattern
    bits.  It is imposed only where the producer is a dense full conv
    (every kernel all ones, so no column order stores fewer of its bricks
    than another), adds no residual, and is read by nothing but the
    depthwise conv (no other layer, nor the FC through the pool): then
    the tensor's new order costs no gather anywhere."""
    reads: dict[str, int] = {}
    prev = "input"
    for spec in layers:
        for t in (prev if spec.src is None else spec.src, spec.residual):
            if t is not None:
                reads[t] = reads.get(t, 0) + 1
        prev = spec.name
    reads[prev] = reads.get(prev, 0) + 1  # the FC, through the pool
    by_name = {spec.name: spec for spec in layers}
    out = {}
    prev = "input"
    for spec in layers:
        src = prev if spec.src is None else spec.src
        prev = spec.name
        producer = by_name.get(src)
        if (not spec.depthwise or producer is None or producer.groups != 1
                or producer.residual is not None or reads[src] != 1):
            continue
        full = (1 << producer.kernel ** 2) - 1
        if not np.all(_layer_bits(params, pattern_bits, src) == full):
            continue
        bits = _layer_bits(params, pattern_bits, spec.name)[:, 0]
        out[src] = np.argsort(bits, kind="stable").astype(np.int32)
    return out


def _channel_order_bricks(spec: ConvSpec, w: np.ndarray,
                          options: CompileOptions) -> int:
    """The bricks a conv ``spec`` of weight ``w`` stores with its rows and
    columns in channel order (a depthwise conv whose input is)."""
    order = patch_order(spec.c_in, spec.kernel, options.block)
    wp = _pad_axis(_pad_axis(conv_matrix(w, order, None, spec.groups), 0,
                             options.block), 1, options.tile)
    masks = nonzero_block_masks(wp, options.block)
    return int(predicted_tile_nnz(masks, np.arange(wp.shape[1]),
                                  options.tile).sum())


def compile_network(
    cfg: CNNConfig | ResNetConfig | MobileNetV2Config,
    params: dict,
    pattern_bits: dict[str, np.ndarray] | None = None,
    *,
    options: CompileOptions | None = None,
) -> CompiledNetwork:
    """Lower a (pruned) CNN end-to-end into a :class:`CompiledNetwork`.

    Args:
      cfg: network geometry (``models.cnn.CNNConfig``,
        ``models.resnet.ResNetConfig`` or
        ``models.mobilenet.MobileNetV2Config``): its ``layers()`` are
        lowered in order, one loop for every network.
      params: parameter pytree ``{<layer>: {w, b} or {w, bn}, ..., fc: {w,
        b}}``; a layer with ``bn`` statistics is folded (:func:`fold_bn`,
        eps ``cfg.bn_eps``).
      pattern_bits: per-conv packed pattern bitmasks
        (``PruneResult.pattern_bits``; ``[c_out, c_in // groups]``);
        recovered from the weights' nonzero structure for layers not
        listed (dense layers: all-ones).
      options: the lowering geometry and every compile-pass switch
        (:class:`CompileOptions`); None: ``CompileOptions()``.
    """
    if options is None:
        options = CompileOptions()
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
    if search_cfg is not None:
        grouped = [spec.name for spec in layers if spec.groups > 1]
        if grouped:
            raise NotImplementedError(
                "optimize: the mapping search prices full convs only; "
                f"grouped convs {grouped[:3]} are not in its space"
            )
    sizes = out_sizes(layers, cfg.input_hw)
    producer_orders = depthwise_producer_orders(layers, params, pattern_bits)
    dw_bricks = dw_channel_bricks = 0
    # the channel order of each tensor the forward keeps; None: channel
    # order (the image's)
    orders: dict[str, np.ndarray | None] = {"input": None}
    prev = "input"
    with tracer.span(
        "compile_network", cat="compile",
        layers=len(layers) + 1, precision=options.precision,
        optimize=search_cfg is not None,
        depthwise=sum(spec.depthwise for spec in layers),
    ) as compile_span:
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
                        w, pattern_bits.get(name), hw, options, search_cfg,
                    )
                    mapping = res.chosen
                    sp.args.update(
                        evaluations=res.evaluations,
                        improved=res.improved,
                        chosen=mapping.to_manifest(),
                        area_cells=res.cost.area_cells,
                        fixed_area_cells=res.fixed_cost.area_cells,
                    )
            with tracer.span(f"lower:{name}", cat="compile") as sp:
                conv = lower_conv(
                    spec,
                    w,
                    b,
                    pattern_bits.get(name),
                    out_hw=hw,
                    options=options,
                    tracer=tracer,
                    mapping=mapping,
                    in_order=orders[prev if spec.src is None else spec.src],
                    out_order=producer_orders.get(name),
                )
                bricks = int(np.sum(conv.bp.nnz))
                sp.args.update(groups=spec.groups, bricks=bricks)
                if spec.depthwise:
                    channel_bricks = _channel_order_bricks(spec, w, options)
                    sp.args.update(bricks_channel_order=channel_bricks)
                    dw_bricks += bricks
                    dw_channel_bricks += channel_bricks
            convs.append(conv)
            # a residual add keeps the conv's own order
            orders[name] = leading_order(conv.bp, conv.c_out)
            prev = name
        fc_reorder = "pattern"
        if search_cfg is not None:
            with tracer.span("search:fc", cat="compile") as sp:
                wfc = _pad_axis(
                    _pad_axis(
                        np.asarray(params["fc"]["w"], np.float32),
                        0, options.block,
                    ),
                    1, options.tile,
                )
                fc_reorder, counts = choose_fc_reorder(
                    nonzero_block_masks(wfc, options.block),
                    options.tile, search_cfg.reorders,
                )
                sp.args.update(chosen=fc_reorder, bricks=counts)
        with tracer.span("lower:fc", cat="compile"):
            # the global average pool keeps the last conv's order
            fc = lower_fc(params["fc"]["w"], params["fc"]["b"], options,
                          tracer=tracer, reorder=fc_reorder,
                          in_order=orders[prev])
        program = CompiledNetwork(
            config=cfg, convs=convs, fc=fc, block=options.block,
            tile=options.tile, precision=options.precision,
            cell_bits=options.cell_bits,
        )
        # the channel gathers the forward will trace
        compile_span.args.update(
            gathers=program.gathers(), dw_bricks=dw_bricks,
            dw_bricks_channel_order=dw_channel_bricks,
        )
    if verify is not None:
        from repro.analysis.ranges import analyze_network
        from repro.analysis.verify import verify_network

        with tracer.span("verify", cat="compile"):
            report = verify_network(program)
        # the range certification pass only runs over structurally sound
        # programs (its interval math assumes the verifier's contracts);
        # V5xx diagnostics land in the same report, the certificate rides
        # on the program (priced by hardware_report, saved in the manifest)
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
