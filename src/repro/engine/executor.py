"""Executor: run a ``CompiledNetwork`` through the Pallas/XLA spmm kernels.

``make_forward`` returns a jitted batched forward: per conv layer it
extracts im2col patches (conv-as-spmm), dispatches through
``kernels/ops.pattern_spmm`` (the Pallas TPU kernel on the chip; the XLA
path, or the Pallas interpreter when asked for, on CPU) — which applies
the stored inverse output permutation (the Output Indexing Unit) — then
bias + shared ``channel_norm``/ReLU and the 2x2 maxpool where the
schedule says so, matching ``cnn_apply`` on the pruned weights to
numerical tolerance.

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
width, and a ``psum`` combines the partial outputs before the global
inverse permutation — while batch rows and the skip counters split over
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
from repro.engine.program import CompiledConv, CompiledFC, CompiledNetwork
from repro.engine.stats import (
    ActivationStats,
    skip_patterns_and_masks,
    stats_from_counts,
)
from repro.kernels.ops import pattern_spmm, pattern_spmm_raw
from repro.kernels.ops import _pad_to as _pad_axis_to_mult
from repro.models.cnn import channel_norm, max_pool_2x2
from repro.parallel.sharding import shard_block_pattern

__all__ = [
    "STAGES", "extract_patches", "make_forward", "warmup_forward", "execute",
]

# the stages of a layer, as the forward's ``jax.named_scope``s name them
STAGES = ("patches", "spmm", "permute", "epilogue", "stats")


def extract_patches(x: jax.Array, k: int) -> jax.Array:
    """im2col for stride-1 'same' convs: [B, C, H, W] -> [B, H, W, C*k*k].

    Patch layout matches ``lowering.conv_matrix``: feature index is
    ``c * k*k + (dy*k + dx)``.
    """
    b, c, h, w = x.shape
    pad = k // 2
    xp = jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    taps = [
        xp[:, :, dy : dy + h, dx : dx + w]
        for dy in range(k)
        for dx in range(k)
    ]
    patches = jnp.stack(taps, axis=-1)  # [B, C, H, W, k*k]
    return patches.transpose(0, 2, 3, 1, 4).reshape(b, h, w, c * k * k)


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
) -> jax.Array:
    """Count all-zero input selections per OU row-group.

    patches: [M, c_in*kk] unpadded im2col windows; masks: [P, kk] bool,
    the layer's pattern position masks (``skip_patterns_and_masks``).
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

    def prepare(self, bp):
        """Per-layer operand prep (identity here; padding when sharded)."""
        return bp

    def spmm(self, x2d: jax.Array, bp, prepared) -> jax.Array:
        return pattern_spmm(
            x2d, bp, backend=self.backend, interpret=self.interpret,
            bm=self.bm,
        )

    def counts(self, patches, c_in, kk, masks, row_valid=None) -> jax.Array:
        return zero_selection_counts(patches, c_in, kk, masks, row_valid)


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

    def spmm(self, x2d: jax.Array, bp, prepared) -> jax.Array:
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
            yl = pattern_spmm_raw(
                xl, w_comp, block_ids, bp.block,
                backend=self.backend, interpret=self.interpret, bm=self.bm,
                w_scales=scales[0] if quantized else None,
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
        with jax.named_scope("spmm"):
            y = jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=P(dspec, None),
                check_vma=False,
            )(*args)
        # Output Indexing Unit: global inverse permutation after the psum
        # (padded columns sit past every inv_order entry and are dropped)
        with jax.named_scope("permute"):
            y = jnp.take(y, jnp.asarray(bp.inv_order), axis=1)
            return y.astype(x2d.dtype)

    def counts(self, patches, c_in, kk, masks, row_valid=None) -> jax.Array:
        part = self.part
        dspec = self._data_spec(patches.shape[0])
        if dspec is None:
            return zero_selection_counts(patches, c_in, kk, masks, row_valid)

        def local(pl, *rv):
            return jax.lax.psum(
                zero_selection_counts(
                    pl, c_in, kk, masks, rv[0] if rv else None
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


def _run_conv(
    op: CompiledConv,
    x: jax.Array,
    disp: _Dispatch,
    prepared,
    stat_masks: np.ndarray | None = None,
    valid: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array | None]:
    b, c, h, w = x.shape
    with jax.named_scope("patches"):
        patches = extract_patches(x, op.kernel)  # [B, H, W, C*k*k]
        patches = patches.reshape(b * h * w, -1)
    counts = None
    if stat_masks is not None:
        with jax.named_scope("stats"):
            # every patch row belongs to one sample; dead-slot samples
            # are excluded from the skip counters
            row_valid = None if valid is None else jnp.repeat(valid, h * w)
            counts = disp.counts(
                patches, op.c_in, op.kernel * op.kernel, stat_masks,
                row_valid,
            )
    with jax.named_scope("patches"):
        patches = _pad_features(patches, op.bp.k_in)
    y = disp.spmm(patches, op.bp, prepared)  # scopes spmm, permute
    with jax.named_scope("epilogue"):
        y = y[:, : op.c_out] + jnp.asarray(op.bias)
        y = y.reshape(b, h, w, op.c_out).transpose(0, 3, 1, 2)
        y = jax.nn.relu(channel_norm(y))
        if op.pool_after:
            y = max_pool_2x2(y)
    return y, counts


def _run_fc(
    op: CompiledFC,
    x: jax.Array,
    disp: _Dispatch,
    prepared,
) -> jax.Array:
    with jax.named_scope("patches"):
        xf = _pad_features(x, op.bp.k_in)
    y = disp.spmm(xf, op.bp, prepared)  # scopes spmm, permute
    with jax.named_scope("epilogue"):
        return y[:, : op.d_out] + jnp.asarray(op.bias)


def _layer_windows(
    program: CompiledNetwork, x_shape, live_rows: int | None = None
) -> dict[str, int]:
    """Windows (input positions) each conv layer sees for this input.

    ``live_rows`` overrides the batch size when some rows are dead slots
    (serving validity mask): only live samples contribute windows, so the
    measured skip fractions divide by exactly the traffic observed.
    """
    b, _, h, w = x_shape
    if live_rows is not None:
        b = live_rows
    windows = {}
    for op in program.convs:
        windows[op.name] = b * h * w
        if op.pool_after:
            h, w = h // 2, w // 2
    return windows


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
      bm: spmm row tile; None autotunes from the batch size.
      collect_stats: also measure per-layer all-zero-selection counts.
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
    (a retrace means a new batch shape hit the jit cache), and
    ``fn.lower(x, valid)``, the jitted forward's ``jax.jit(...).lower``
    (inspect the compiled program; after a call at the same shapes its
    ``compile()`` is a cache hit).

    Names on the device: every operation the forward traces sits under
    the ``jax.named_scope`` pair ``<layer>/<stage>``, so its ``op_name``
    metadata reads ``jit(forward)/<layer>/<stage>/...``.  ``<layer>`` is
    the conv's ``op.name`` (``conv1`` ...), then ``gap`` and ``fc``;
    ``<stage>`` is one of :data:`STAGES`:

    * ``patches`` — the SAME pad, tap slices and stack, the NCHW->NHWC
      transpose and reshape (im2col), and the K padding;
    * ``spmm`` — the row padding, the ``pattern_spmm*`` kernel (or the
      XLA path) and the row slice; sharded, the scatter and ``psum``;
    * ``permute`` — the inverse output permutation (Output Indexing
      Unit);
    * ``epilogue`` — the column slice, bias, NHWC->NCHW transpose,
      ``channel_norm``, ReLU and max-pool; for ``gap``, the mean;
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

    stat_masks = {}
    if collect_stats:
        for op in program.convs:
            _, masks = skip_patterns_and_masks(
                op.pattern_bits, op.kernel * op.kernel
            )
            stat_masks[op.name] = masks

    traces = {"n": 0}

    def forward(x: jax.Array, valid: jax.Array | None = None):
        traces["n"] += 1  # python side effect: runs once per trace
        counts = {}
        for op in program.convs:
            with jax.named_scope(op.name):
                x, cnt = _run_conv(
                    op, x, disp, prepared[op.name], stat_masks.get(op.name),
                    valid,
                )
            if cnt is not None:
                counts[op.name] = cnt
        with jax.named_scope("gap"), jax.named_scope("epilogue"):
            x = x.mean(axis=(2, 3))  # global average pool
        with jax.named_scope("fc"):
            logits = _run_fc(program.fc, x, disp, prepared["fc"])
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
        (batch_slots, cfg.conv_channels[0][0], cfg.input_hw, cfg.input_hw),
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
