"""Executor: run a ``CompiledNetwork`` through the Pallas/XLA spmm kernels.

``make_forward`` returns a jitted batched forward: per conv layer it
extracts im2col patches in the layer's ``patch_order`` (conv-as-spmm;
tap-major where K spans more than one block), dispatches through
``kernels/ops.pattern_spmm_raw`` (the Pallas TPU kernel on the chip; the
XLA path, or the Pallas interpreter when asked for, on CPU), then the
layer's epilogue: the real output columns, bias, the shared
``channel_norm`` where the layer keeps one, the residual add, the
activation (ReLU or ReLU6, or none) and the pool, matching ``cnn_apply``
(``resnet_apply`` for a ResNet, ``mobilenet_apply`` for a MobileNetV2) on
the pruned weights to numerical tolerance.  A grouped or depthwise conv
takes the same path: im2col and the spmm of its block-diagonal matrix.
The program is a graph in execution order: the forward keeps each
layer's output by name, for a later layer's ``src`` or ``residual``.

Each kept tensor stays in the channel order its producer's kernel wrote
it in (the reordered columns of ``bp.new_order``): the next layer's
weight rows were lowered in that order, and bias, ``channel_norm``, ReLU
and the pools act per channel.  The Output Indexing Unit's gather runs
only where ``CompiledNetwork.layout`` finds two orders that differ — at
the logits, where a layer's rows were lowered in another order than its
input carries, and across a residual add whose addends disagree.

With ``collect_stats=True`` the forward additionally counts, per layer
and per OU row-group (= (input channel, pattern) pair), how many input
selections were entirely zero — the quantity the paper's Input
Preprocessing Unit skips on.  The counters are plain masked reductions
over the very patches the spmm consumes, so they are jit-compatible and
backend-agnostic: they ride alongside both the Pallas and the XLA spmm
dispatch unchanged.  ``engine/stats.py`` aggregates them and
``CompiledNetwork.hardware_report`` prices energy/cycles from them.

``channel_norm`` is strictly per-sample (spatial axes only), so every
batch row is computed independently of its neighbours: the same image
produces bit-identical logits alone, co-batched, or surrounded by
zero-padded dead slots.  The serving scheduler exploits that by always
executing one fixed ``batch_slots`` shape — the forward traces exactly
once — and passing a row-validity mask that excludes dead slots from the
skip counters and window totals, keeping the measured statistics exact.

Quantized programs (``precision='int8'`` at compile time) run through the
same dispatch unchanged: ``pattern_spmm`` sees the int8 bricks +
row-group scales on the ``BlockPatternWeight`` and switches to the
int8-input/int32-accumulate kernel variant, quantizing activations
per im2col row on the fly (``core/quantize.quantize_rows``).  One caveat:
sharded-vs-unsharded agreement for quantized programs is bounded by the
*quantization* error, not fp32 noise — an ulp-level reassociation
difference in one layer can flip an int8 rounding in the next layer's
dynamic activation quantization.

With ``mesh=`` the same program executes *sharded* across a device mesh
(``engine/partition.py``): each spmm runs tile-parallel under
``shard_map`` — every ``model``-axis device computes the output columns
of its contiguous slab of (zero-padded) tiles, scatters them into full
width, and a ``psum`` combines the partial outputs in the stored column
order — while batch rows and the skip counters split over
the ``data`` axis (counters ``psum``-reduced back to the global count).
Padding tiles multiply zeros, so sharded and unsharded execution agree to
fp32 tolerance and the measured statistics agree exactly.
"""

from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.engine.partition import pad_bp_tiles, partition_from_mesh
from repro.engine.program import (
    CompiledConv,
    CompiledFC,
    CompiledNetwork,
    OpLayout,
)
from repro.engine.stats import (
    ActivationStats,
    skip_patterns_and_masks,
    stats_from_counts,
)
from repro.kernels.ops import default_backend, pattern_spmm_raw, spmm_grid
from repro.kernels.ops import _pad_to as _pad_axis_to_mult
from repro.models.cnn import (
    ACTIVATIONS,
    POOLS,
    channel_norm,
    conv_out_hw,
    out_sizes,
)
from repro.parallel.sharding import shard_block_pattern

__all__ = [
    "STAGES", "extract_patches", "make_forward", "warmup_forward", "execute",
]

# the stages of a layer, as the forward's ``jax.named_scope``s name them
STAGES = ("patches", "spmm", "permute", "epilogue", "residual", "stats")


def _tap(xp: jax.Array, dy: int, dx: int, ho: int, wo: int, stride: int,
         axes: tuple[int, int]) -> jax.Array:
    """The ``(dy, dx)`` tap of the padded ``xp``: ``ho x wo`` positions
    ``stride`` apart on the spatial ``axes``."""
    start = [0] * xp.ndim
    limit = list(xp.shape)
    strides = [1] * xp.ndim
    for ax, d, n in ((axes[0], dy, ho), (axes[1], dx, wo)):
        start[ax], limit[ax], strides[ax] = d, d + stride * (n - 1) + 1, stride
    return jax.lax.slice(xp, start, limit, strides)


def extract_patches(
    x: jax.Array, k: int, order: str = "channel", width: int | None = None,
    stride: int = 1,
) -> jax.Array:
    """im2col for convs padded by ``k // 2`` on every side: [B, C, H, W]
    -> [B*Ho*Wo, F], ``Ho = (H - 1) // stride + 1`` (likewise ``Wo``).

    The feature order matches ``lowering.conv_matrix(w, order)``:

    * ``'channel'`` — feature ``c * k*k + (dy*k + dx)``: the ``k*k`` taps
      of one channel sit side by side, built as a ``[B, C, Ho, Wo, k*k]``
      stack transposed into rows;
    * ``'tap'`` — feature ``(dy*k + dx) * C + c``: the ``k*k`` shifted
      (and strided) slices of the padded NHWC activation, each ``C`` lanes
      wide, concatenated along the feature axis (a plain copy).  A 1x1
      conv's patches are its input's strided NHWC view.

    ``F`` is ``C*k*k``, or ``width`` when given: the features are then
    zero-padded up to the spmm's padded K (``bp.k_in``) — tap-major
    patches as one more block of the same concatenation.
    """
    b, c, h, w = x.shape
    pad = k // 2
    ho, wo = conv_out_hw(h, k, stride), conv_out_hw(w, k, stride)
    if order == "channel":
        xp = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
        taps = [
            _tap(xp, dy, dx, ho, wo, stride, (2, 3))
            for dy in range(k)
            for dx in range(k)
        ]
        patches = jnp.stack(taps, axis=-1)  # [B, C, Ho, Wo, k*k]
        patches = patches.transpose(0, 2, 3, 1, 4).reshape(b * ho * wo, -1)
        return patches if width is None else _pad_features(patches, width)
    if order != "tap":
        raise ValueError(f"unknown patch order {order!r}")
    xp = jnp.pad(
        x.transpose(0, 2, 3, 1), ((0, 0), (pad, pad), (pad, pad), (0, 0))
    )
    parts = [
        _tap(xp, dy, dx, ho, wo, stride, (1, 2))
        for dy in range(k)
        for dx in range(k)
    ]
    if width is not None and width > c * k * k:
        parts.append(jnp.zeros((b, ho, wo, width - c * k * k), x.dtype))
    return jnp.concatenate(parts, axis=-1).reshape(b * ho * wo, -1)


def _pad_features(x: jax.Array, to: int) -> jax.Array:
    """Zero-pad the feature axis up to ``to`` (the bp's padded K).

    The feature count never exceeds ``to``, so padding to a multiple of
    ``to`` via the shared kernels helper lands exactly on ``to``.
    """
    assert x.shape[-1] <= to
    return _pad_axis_to_mult(x, x.ndim - 1, to)


def zero_selection_counts(
    patches: jax.Array,
    c_in: int,
    kk: int,
    masks: np.ndarray,
    row_valid: jax.Array | None = None,
    order: str = "channel",
) -> jax.Array:
    """Count all-zero input selections per OU row-group.

    patches: [M, c_in*kk] unpadded im2col windows, features in ``order``
    (:func:`extract_patches`; the counts are the same in either order),
    input channels in the order of the tensor they were built from (entry
    ``c`` of the result counts its ``c``-th channel);
    masks: [P, kk] bool, the layer's pattern position masks
    (``skip_patterns_and_masks``).
    Returns int32 [c_in, P]: entry (c, i) is the number of windows whose
    channel-c activations at ``masks[i]``'s positions are all zero — the
    selections the Input Preprocessing Unit would skip.  The all-zero
    pattern selects nothing and counts every window (vacuous all()).

    row_valid: optional bool [M]; ``False`` rows are excluded from every
    count.  The serving scheduler marks zero-padded dead batch slots this
    way — an all-zero padded row would otherwise count as 100%-skippable
    traffic and silently inflate the measured energy win.
    """
    m = patches.shape[0]
    if order == "tap":
        patches = patches.reshape(m, kk, c_in).transpose(0, 2, 1)
    z = patches.reshape(m, c_in, 1, kk) == 0.0
    keep = jnp.asarray(masks)[None, None]  # [1, 1, P, kk]
    all_zero = jnp.all(z | ~keep, axis=-1)  # [M, C, P]
    if row_valid is not None:
        all_zero = all_zero & row_valid[:, None, None]
    return all_zero.sum(axis=0, dtype=jnp.int32)


class _Dispatch:
    """Single-device spmm + stat-counter dispatch (the historical path)."""

    def __init__(self, backend, interpret, bm):
        self.backend = backend
        self.interpret = interpret
        self.bm = bm
        # layer -> the Pallas kernel's grid at the latest trace
        self.grid: dict[str, dict] = {}

    def prepare(self, bp):
        """Per-layer operand prep (identity here; padding when sharded)."""
        return bp

    def _raw(self, name, x2d, w_comp, block_ids, block, w_scales):
        """``pattern_spmm_raw`` on one device's operands, recording the
        kernel's ``(M, bm, grid steps)`` under ``name`` at trace time."""
        backend = self.backend or default_backend()
        if backend == "pallas":
            dtype = x2d.dtype if w_scales is None else jnp.int8
            m = x2d.shape[0]
            bm, steps = spmm_grid(m, w_comp, dtype, self.bm)
            self.grid[name] = {"m": m, "bm": bm, "steps": steps}
        return pattern_spmm_raw(
            x2d, w_comp, block_ids, block, backend=backend,
            interpret=self.interpret, bm=self.bm, w_scales=w_scales,
        )

    def spmm(self, x2d: jax.Array, bp, prepared, name: str) -> jax.Array:
        """``x2d @ W`` in ``bp``'s stored column order: [M, tiles*tile]."""
        with jax.named_scope("spmm"):
            return self._raw(
                name, x2d, bp.w_comp, bp.block_ids, bp.block, bp.w_scales
            ).astype(x2d.dtype)

    def counts(
        self, patches, c_in, kk, masks, row_valid=None, order="channel"
    ) -> jax.Array:
        return zero_selection_counts(
            patches, c_in, kk, masks, row_valid, order
        )


class _ShardedDispatch(_Dispatch):
    """Mesh execution: tile-parallel spmm (scatter + psum over the model
    axis), batch rows and skip counters split over the data axis."""

    def __init__(self, backend, interpret, bm, mesh, part):
        super().__init__(backend, interpret, bm)
        self.mesh = mesh
        self.part = part

    def prepare(self, bp):
        """Pad the tile axis for the model shards and place the slabs."""
        return shard_block_pattern(
            pad_bp_tiles(bp, self.part.model), self.mesh,
            model_axis=self.part.model_axis,
        )

    def _data_spec(self, m: int) -> str | None:
        """Shard batch rows over 'data' when they divide; else replicate.

        The divisibility decision is made on static shapes at trace time,
        so partial service generations keep exact single-device numerics.
        """
        part = self.part
        return (
            part.data_axis if part.data > 1 and m % part.data == 0 else None
        )

    def spmm(self, x2d: jax.Array, bp, prepared, name: str) -> jax.Array:
        part = self.part
        model, maxis = part.model, part.model_axis
        width = (prepared.n_tiles // model) * bp.tile
        full_width = prepared.n_tiles * bp.tile
        dspec = self._data_spec(x2d.shape[0])
        mspec = maxis if model > 1 else None
        quantized = prepared.w_scales is not None

        def local(xl, w_comp, block_ids, *scales):
            # Quantized operands ride the same slab split: each device
            # holds its tiles' int8 bricks + row-group scales and
            # quantizes its (replicated-along-model) activation rows
            # identically, so the psum still combines disjoint column
            # slabs of already-dequantized fp32 partials.
            yl = self._raw(
                name, xl, w_comp, block_ids, bp.block,
                scales[0] if quantized else None,
            )
            # The slabs are disjoint, so a tiled all_gather would also
            # reassemble them with less traffic; the scatter + psum form
            # is kept because it stays correct for any tile->device
            # assignment, not just the contiguous one.
            yf = jnp.zeros((xl.shape[0], full_width), yl.dtype)
            if model > 1:
                off = jax.lax.axis_index(maxis) * width
                yf = jax.lax.dynamic_update_slice(yf, yl, (0, off))
                yf = jax.lax.psum(yf, maxis)
            else:
                yf = jax.lax.dynamic_update_slice(yf, yl, (0, 0))
            return yf

        args = (x2d, prepared.w_comp, prepared.block_ids)
        in_specs = (P(dspec, None), P(mspec), P(mspec))
        if quantized:
            args += (prepared.w_scales,)
            in_specs += (P(mspec),)
        # the stored column order, padded tiles last (past every real
        # column and every inv_order entry)
        with jax.named_scope("spmm"):
            return jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=P(dspec, None),
                check_vma=False,
            )(*args).astype(x2d.dtype)

    def counts(
        self, patches, c_in, kk, masks, row_valid=None, order="channel"
    ) -> jax.Array:
        part = self.part
        dspec = self._data_spec(patches.shape[0])
        if dspec is None:
            return zero_selection_counts(
                patches, c_in, kk, masks, row_valid, order
            )

        def local(pl, *rv):
            return jax.lax.psum(
                zero_selection_counts(
                    pl, c_in, kk, masks, rv[0] if rv else None, order
                ),
                part.data_axis,
            )

        args = (patches,)
        in_specs: tuple = (P(dspec, None),)
        if row_valid is not None:
            # the per-sample validity rows shard with their patch rows
            args += (row_valid,)
            in_specs += (P(dspec),)
        return jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=P(None, None),
            check_vma=False,
        )(*args)


def _gather(x: jax.Array, idx, axis: int) -> jax.Array:
    """The Output Indexing Unit: ``x``'s entries ``idx`` along ``axis``."""
    with jax.named_scope("permute"):
        return jnp.take(x, jnp.asarray(idx), axis=axis)


def _outputs(y: jax.Array, bp, n: int, lay: OpLayout) -> jax.Array:
    """The ``n`` real outputs of a kernel's stored columns ``y``, in
    ``lay.out_order``: the leading columns, or gathered by
    ``bp.inv_order`` into channel order."""
    if lay.gather_out:
        return _gather(y, np.asarray(bp.inv_order)[:n], 1)
    with jax.named_scope("epilogue"):
        return y[:, :n]


def _run_conv(
    op: CompiledConv,
    x: jax.Array,
    disp: _Dispatch,
    prepared,
    lay: OpLayout,
    stat_masks: np.ndarray | None = None,
    valid: jax.Array | None = None,
    residual: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array | None]:
    b, c, h, w = x.shape
    h, w = conv_out_hw(h, op.kernel, op.stride), conv_out_hw(
        w, op.kernel, op.stride
    )
    if lay.gather_in is not None:
        x = _gather(x, lay.gather_in, 1)
    with jax.named_scope("patches"):
        # [B*Ho*Wo, bp.k_in], features in the order the weight rows are
        patches = extract_patches(
            x, op.kernel, op.patch_order, op.bp.k_in, op.stride
        )
    counts = None
    if stat_masks is not None:
        with jax.named_scope("stats"):
            # every patch row belongs to one sample; dead-slot samples
            # are excluded from the skip counters
            row_valid = None if valid is None else jnp.repeat(valid, h * w)
            counts = disp.counts(
                patches[:, : op.k_unpadded], op.c_in, op.kernel * op.kernel,
                stat_masks, row_valid, op.patch_order,
            )
    y = _outputs(
        disp.spmm(patches, op.bp, prepared, op.name), op.bp, op.c_out, lay
    )
    with jax.named_scope("epilogue"):
        # bias, and every op after it, in the output's channel order
        y = y + jnp.asarray(op.bias[lay.out_order])
        y = y.reshape(b, h, w, op.c_out).transpose(0, 3, 1, 2)
        if op.norm == "channel":
            y = channel_norm(y)
    if residual is not None:
        if lay.gather_residual is not None:
            residual = _gather(residual, lay.gather_residual, 1)
        with jax.named_scope("residual"):
            y = y + residual
    with jax.named_scope("epilogue"):
        if op.activation is not None:
            y = ACTIVATIONS[op.activation](y)
        if op.pool is not None:
            y = POOLS[op.pool](y)
    return y, counts


def _run_fc(
    op: CompiledFC,
    x: jax.Array,
    disp: _Dispatch,
    prepared,
    lay: OpLayout,
) -> jax.Array:
    if lay.gather_in is not None:
        x = _gather(x, lay.gather_in, 1)
    with jax.named_scope("patches"):
        xf = _pad_features(x, op.bp.k_in)
    y = _outputs(disp.spmm(xf, op.bp, prepared, "fc"), op.bp, op.d_out, lay)
    with jax.named_scope("epilogue"):
        return y + jnp.asarray(op.bias[lay.out_order])


def _layer_windows(
    program: CompiledNetwork, x_shape, live_rows: int | None = None
) -> dict[str, int]:
    """Windows (output positions, ``batch * Ho * Wo``) each conv layer
    sees for this input.

    ``live_rows`` overrides the batch size when some rows are dead slots
    (serving validity mask): only live samples contribute windows, so the
    measured skip fractions divide by exactly the traffic observed.
    """
    b, _, h, w = x_shape
    if live_rows is not None:
        b = live_rows
    rows, cols = out_sizes(program.convs, h), out_sizes(program.convs, w)
    return {op.name: b * rows[op.name][0] * cols[op.name][0]
            for op in program.convs}


def make_forward(
    program: CompiledNetwork,
    backend: str | None = None,
    interpret: bool | None = None,
    bm: int | None = None,
    collect_stats: bool = False,
    mesh=None,
    partition=None,
):
    """Build the jitted batched forward for ``program``.

    Args:
      backend: 'pallas' | 'xla' | None (auto: Pallas on TPU, XLA elsewhere).
      interpret: run the Pallas kernels in the interpreter.  Only an
        explicit ``True`` does; ``backend='pallas'`` off the TPU without
        it raises.
      bm: spmm row tile; None picks each call's from its row count
        (``kernels/ops._pick_bm``).
      collect_stats: also measure per-layer all-zero-selection counts
        (full convs only: a program with a grouped conv raises
        ``NotImplementedError``).
      mesh: a ``jax.sharding.Mesh`` to execute on.  Tiles split over the
        mesh's model axis (psum-combined partial outputs), batch rows and
        stat counters over the data axis; without a mesh the historical
        single-device path runs, and the two agree to fp32 tolerance.
      partition: explicit :class:`~repro.engine.partition.NetworkPartition`
        (defaults to ``program.partition``, else derived from the mesh);
        validated against the mesh's axis sizes.

    Returns: fn(x: [B, C, H, W], valid=None) -> logits [B, num_classes],
    or, with ``collect_stats``, fn(x, valid=None) ->
    (logits, :class:`ActivationStats`).  ``valid`` is an optional bool
    [B] row-validity mask: the serving scheduler zero-pads dead batch
    slots and marks them ``False`` so the fixed batch shape traces once
    while the skip statistics (counters *and* window totals) cover only
    live traffic.  ``channel_norm`` is per-sample, so dead rows never
    influence live logits; their own outputs are meaningless and must be
    dropped by the caller.  The returned callable exposes
    ``fn.trace_count()``, the number of times the forward has been traced
    (a retrace means a new batch shape hit the jit cache),
    ``fn.kernel_grid()``, each layer's Pallas spmm call at the latest
    trace as ``{"m": rows, "bm": row tile, "steps": grid steps}`` (per
    shard under a mesh; empty until traced and on the XLA path), and
    ``fn.lower(x, valid)``, the jitted forward's ``jax.jit(...).lower``
    (inspect the compiled program; after a call at the same shapes its
    ``compile()`` is a cache hit).

    Names on the device: every operation the forward traces sits under
    the ``jax.named_scope`` pair ``<layer>/<stage>``, so its ``op_name``
    metadata reads ``jit(forward)/<layer>/<stage>/...``.  ``<layer>`` is
    the conv's ``op.name`` (``conv1`` ..., or a ResNet's ``stem``,
    ``layer1.0.conv1`` ..., ``layer1.0.downsample``), then ``gap`` and
    ``fc``; ``<stage>`` is one of :data:`STAGES`:

    * ``patches`` — im2col in the layer's ``patch_order`` and the K
      padding: channel-major, the ``k // 2`` pad, (strided) tap slices,
      stack, the transpose into rows and the pad; tap-major, the
      NCHW->NHWC transpose, the pad, and one concatenation of the tap
      slices and the zero K padding;
    * ``spmm`` — the row padding, the ``pattern_spmm*`` kernel (or the
      XLA path) and the row slice; sharded, the scatter and ``psum``;
    * ``permute`` — a channel gather (the Output Indexing Unit), traced
      only where ``program.layout()`` finds two channel orders that
      differ: the layer's input against the order its weight rows
      assume, its output when its real columns do not lead the kernel's
      (taken back into channel order by ``bp.inv_order``), its residual
      against its output, and the logits against class order.  A program
      this code compiled gathers at most at the logits
      (``program.gathers()``; the ``compile_network`` span's ``gathers``
      arg);
    * ``epilogue`` — the column slice, bias (in the output's channel
      order), NHWC->NCHW transpose, ``channel_norm`` (where ``norm ==
      'channel'``), the activation (ReLU, or ReLU6's clip) and the pool;
      for ``gap``, the mean;
    * ``residual`` — the add of the layer's ``residual`` tensor, between
      the two halves of its epilogue (a ResNet block's conv3, a
      MobileNetV2 block's linear projection);
    * ``stats`` — the ``collect_stats`` skip counters.

    Profiles attribute device time by these names; they are a contract.
    Scopes change metadata only, never the compiled instructions.
    """
    if mesh is None:
        if partition is not None:
            raise ValueError("partition= requires mesh=")
        disp: _Dispatch = _Dispatch(backend, interpret, bm)
    else:
        part = partition_from_mesh(mesh, partition or program.partition)
        disp = _ShardedDispatch(backend, interpret, bm, mesh, part)

    prepared = {op.name: disp.prepare(op.bp) for op in program.convs}
    prepared["fc"] = disp.prepare(program.fc.bp)
    layout = program.layout()

    stat_masks = {}
    if collect_stats:
        grouped = [op.name for op in program.convs if op.groups > 1]
        if grouped:
            raise NotImplementedError(
                "collect_stats: the skip counters count (input channel, "
                "pattern) selections of full convs; grouped convs "
                f"{grouped[:3]} are not counted"
            )
        for op in program.convs:
            _, masks = skip_patterns_and_masks(
                op.pattern_bits, op.kernel * op.kernel
            )
            stat_masks[op.name] = masks

    traces = {"n": 0}

    def forward(x: jax.Array, valid: jax.Array | None = None):
        traces["n"] += 1  # python side effect: runs once per trace
        counts = {}
        tensors = {"input": x}  # each layer's output, for src / residual
        for op in program.convs:
            with jax.named_scope(op.name):
                x, cnt = _run_conv(
                    op, x if op.src is None else tensors[op.src], disp,
                    prepared[op.name], layout[op.name],
                    stat_masks.get(op.name), valid,
                    None if op.residual is None else tensors[op.residual],
                )
            tensors[op.name] = x
            if cnt is not None:
                counts[op.name] = cnt
        with jax.named_scope("gap"), jax.named_scope("epilogue"):
            x = x.mean(axis=(2, 3))  # global average pool
        with jax.named_scope("fc"):
            logits = _run_fc(program.fc, x, disp, prepared["fc"],
                             layout["fc"])
        return (logits, counts) if collect_stats else logits

    jitted = jax.jit(forward)

    def _as_valid(valid):
        return None if valid is None else jnp.asarray(valid, bool)

    if not collect_stats:
        def fn(x: jax.Array, valid=None) -> jax.Array:
            return jitted(x, _as_valid(valid))
    else:
        def fn(
            x: jax.Array, valid=None
        ) -> tuple[jax.Array, ActivationStats]:
            logits, counts = jitted(x, _as_valid(valid))
            live = None if valid is None else int(np.asarray(valid).sum())
            stats = stats_from_counts(
                program.convs,
                {k: np.asarray(v) for k, v in counts.items()},
                _layer_windows(program, x.shape, live_rows=live),
            )
            return logits, stats

    fn.trace_count = lambda: traces["n"]
    fn.kernel_grid = lambda: {k: dict(v) for k, v in disp.grid.items()}
    fn.lower = jitted.lower
    return fn


def warmup_forward(fn, program: CompiledNetwork, batch_slots: int):
    """Trace ``fn`` at the fixed serving batch shape, before traffic.

    Runs one all-dead batch — zeros with an all-``False`` validity mask,
    exactly the shape/dtype signature the serving scheduler executes —
    and blocks until ready, so a front end pays jit tracing (and
    compilation) at boot instead of on its first request, without
    pushing a synthetic request through the scheduler (boot leaves the
    served-traffic metrics untouched).  Returns ``fn``.
    """
    cfg = program.config
    x = jnp.zeros(
        (batch_slots, cfg.in_channels, cfg.input_hw, cfg.input_hw),
        jnp.float32,
    )
    valid = np.zeros(batch_slots, bool)
    out = fn(x, valid)
    jax.block_until_ready(out[0] if isinstance(out, tuple) else out)
    return fn


# `execute`'s per-program forward cache would otherwise retain every mesh
# ever passed (device buffers included) for the program's lifetime.
_FORWARD_CACHE_MAX = 8


def _dispatch_key(backend, interpret, bm, mesh, partition):
    """Stable, value-based cache key for a dispatch configuration.

    Meshes are fingerprinted by axis names/shape and device ids rather
    than object identity, so two equal meshes share one cache entry and a
    dropped mesh object is not pinned alive by the key.  ``partition`` is
    a frozen dataclass and hashes by value already.
    """
    mesh_key = None
    if mesh is not None:
        devices = np.asarray(mesh.devices)
        mesh_key = (
            tuple(mesh.axis_names),
            devices.shape,
            tuple(int(d.id) for d in devices.ravel()),
        )
    return (backend, interpret, bm, mesh_key, partition)


def execute(
    program: CompiledNetwork,
    x: jax.Array,
    backend: str | None = None,
    interpret: bool | None = None,
    bm: int | None = None,
    mesh=None,
    partition=None,
) -> jax.Array:
    """One-shot convenience wrapper around :func:`make_forward`.

    The jitted forward is LRU-cached on the program per dispatch
    configuration (mesh fingerprint, not identity), capped at
    ``_FORWARD_CACHE_MAX`` entries so long-lived programs don't pin every
    mesh/partition they ever executed on.
    """
    cache = program.__dict__.get("_forward_cache")
    if not isinstance(cache, OrderedDict):
        cache = program.__dict__["_forward_cache"] = OrderedDict()
    key = _dispatch_key(backend, interpret, bm, mesh, partition)
    fwd = cache.get(key)
    if fwd is None:
        fwd = make_forward(
            program, backend, interpret, bm, mesh=mesh, partition=partition
        )
        cache[key] = fwd
        while len(cache) > _FORWARD_CACHE_MAX:
            cache.popitem(last=False)
    else:
        cache.move_to_end(key)
    return fwd(x)
