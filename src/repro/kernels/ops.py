"""Public jit'd wrappers around the Pallas kernels.

Each op dispatches between:
  * the Pallas TPU kernel (``backend='pallas'``), compiled for the TPU, or
    run by the Pallas interpreter only when the caller passes
    ``interpret=True`` (the CPU tests do), and
  * the XLA path (``backend='xla'``), which runs on any platform.

``backend=None`` picks Pallas on TPU devices and XLA elsewhere.  Asking
for ``backend='pallas'`` off the TPU without ``interpret=True`` raises:
nothing silently swaps the compiled kernel for the interpreter.  The row
tile of ``pattern_spmm`` is chosen here from the call's static shapes
(``_pick_bm``); the flash-attention wrapper pads to its tiles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantize import quantize_rows
from repro.core.sparse import (
    BlockPatternWeight,
    pattern_spmm_xla,
    pattern_spmm_xla_quant,
)
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ou_mvm import ou_mvm_pallas
from repro.kernels.pattern_spmm import (
    pattern_spmm_pallas,
    pattern_spmm_pallas_quant,
)

__all__ = [
    "default_backend",
    "pattern_spmm",
    "pattern_spmm_raw",
    "spmm_grid",
    "flash_attention",
    "ou_mvm",
]


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _interpret_flag(interpret: bool | None) -> bool:
    """Whether a Pallas call runs interpreted; refuses to guess off-TPU.

    ``interpret=None`` means the compiled kernel, which only the TPU can
    run.  Elsewhere the caller must ask for the interpreter explicitly.
    """
    if interpret:
        return True
    platform = jax.default_backend()
    if platform != "tpu":
        raise ValueError(
            f"backend='pallas' needs a TPU, but JAX runs on {platform!r}; "
            "pass interpret=True to run the Pallas interpreter, or use "
            "backend='xla'"
        )
    return False


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# Row tiles above 128 rows are chosen from M alone (``_pick_bm``).  A grid
# step of the Pallas spmm costs about 0.4 us on a v5e whatever its work at
# bm = 128, so the tile is as large as these allow: at most _BM_MAX rows,
# at least _BM_MIN where no ragged last block is needed, and its blocks
# within _VMEM_BUDGET (the scoped VMEM default is 16 MiB; the f32 dot at
# HIGHEST precision needs temporaries beyond the blocks).
_BM_MAX = 1024
_BM_MIN = 512
_VMEM_BUDGET = 8 * 2**20


def _sublane_floor(dtype) -> int:
    """The dtype's minimum TPU tile height: 8 rows of 4-byte, 16 of 2-byte,
    32 of 1-byte types."""
    return {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)


def _bm_cap(dtype, block: int, tile: int) -> int:
    """The most rows whose blocks fit ``_VMEM_BUDGET``: x ``[bm, block]`` and
    the fp32 output ``[bm, tile]`` double-buffered, the fp32 accumulator
    once, beside two ``[block, tile]`` bricks; a multiple of 128, at most
    ``_BM_MAX``."""
    item = jnp.dtype(dtype).itemsize
    per_row = 2 * block * item + 2 * tile * 4 + tile * 4
    rows = (_VMEM_BUDGET - 2 * block * tile * item) // per_row
    return max(128, min(_BM_MAX, rows // 128 * 128))


def _pick_bm(m: int, dtype, block: int = 128, tile: int = 128) -> int:
    """Row tile of the Pallas spmm for ``m`` rows, from static shapes alone.

    Up to 128 rows (serving batches, the FC heads): the smallest of 8, 32
    and 128 that covers M, at least the dtype's sublane floor; the caller
    pads M up to it.  Padding 1 row up to 128 would waste a 128x factor of
    MXU work.

    Above 128 rows: the largest multiple of 128 that divides M within
    :func:`_bm_cap`, when that is at least ``_BM_MIN`` rows or all of M.
    Otherwise M is split into ``ceil(M / cap)`` near-equal tiles rounded up
    to the sublane floor, and the last block is ragged: Pallas masks its
    out-of-bounds rows, and each output row reads only its own input row,
    so the rows past M change nothing and no padded copy of x is made (one
    tile taller than M is padded, as below 128 rows).
    """
    floor = _sublane_floor(dtype)
    for cand in (8, 32, 128):
        if m <= cand:
            return max(cand, floor)
    cap = _bm_cap(dtype, block, tile)
    div = max((c for c in range(128, cap + 1, 128) if m % c == 0), default=0)
    if div >= min(_BM_MIN, m):
        return div
    rows = -(-m // -(-m // cap))  # ceil(M / the number of tiles)
    return -(-rows // floor) * floor


def spmm_grid(
    m: int, w_comp, dtype, bm: int | None = None
) -> tuple[int, int]:
    """``(bm, grid steps)`` of the Pallas spmm on ``m`` rows of ``dtype``
    (int8 for a quantized program) against ``w_comp`` ``[T, k_max, block,
    tile]``: the tile :func:`pattern_spmm_raw` runs (``bm`` wins when
    given) and ``ceil(M / bm) * T * k_max``."""
    t, k_max, block, tile = w_comp.shape
    bm = bm or _pick_bm(m, dtype, block, tile)
    return bm, -(-m // bm) * t * k_max


def pattern_spmm_raw(
    xm: jax.Array,
    w_comp: jax.Array,
    block_ids: jax.Array,
    block: int,
    backend: str | None = None,
    interpret: bool | None = None,
    bm: int | None = None,
    w_scales: jax.Array | None = None,
) -> jax.Array:
    """Compressed spmm in *reordered* column order (no inverse permutation).

    xm: [M, K]; returns [M, T*tile] where T = w_comp.shape[0].  The
    executor runs it on every layer (per shard of tiles when sharded) and
    keeps the stored column order, which the next layer's weight rows
    were lowered in.  ``pattern_spmm`` is this plus the inverse
    permutation.

    With ``w_scales`` (int8 ``w_comp`` + per-brick row-group scales,
    ``core/quantize.py``) the activations are dynamically quantized per
    row and the int8-input/int32-accumulate kernel variant runs; the
    weight-scale dequant folds into the accumulator and the activation
    row scale multiplies in the output epilogue here.  Output is fp32.
    """
    backend = backend or default_backend()
    quant = w_scales is not None
    if quant:
        xq, x_scale = quantize_rows(xm)
    if backend == "pallas":
        interp = _interpret_flag(interpret)
        xin = xq if quant else xm
        m = xin.shape[0]
        bm, _ = spmm_grid(m, w_comp, xin.dtype, bm)
        # only a tile taller than M pads; a ragged last block is masked
        xp = _pad_to(xin, 0, bm) if m < bm else xin
        if quant:
            y = pattern_spmm_pallas_quant(
                xp, w_comp, block_ids, w_scales,
                block=block, bm=bm, interpret=interp,
            )[:m]
            return y * x_scale[:, None]
        return pattern_spmm_pallas(
            xp, w_comp, block_ids, block=block, bm=bm, interpret=interp
        )[:m]
    if backend == "xla":
        if quant:
            return pattern_spmm_xla_quant(
                xq, x_scale, w_comp, block_ids, w_scales, block
            )
        return pattern_spmm_xla(xm, w_comp, block_ids, block)
    raise ValueError(f"unknown backend {backend!r}")


def pattern_spmm(
    x: jax.Array,
    bp: BlockPatternWeight,
    backend: str | None = None,
    interpret: bool | None = None,
    bm: int | None = None,
) -> jax.Array:
    """y = x @ W for a block-pattern compressed weight.  x: [..., K].

    ``bm=None`` (default) picks the row tile from M (``_pick_bm``).
    Quantized weights (``bp.w_scales is not None``) dispatch the int8
    variant transparently; output dtype follows ``x`` either way.  The
    compressed matmul runs under ``jax.named_scope("spmm")`` and the
    inverse permutation under ``"permute"`` (the executor's stage names).
    """
    lead = x.shape[:-1]
    with jax.named_scope("spmm"):
        xm = x.reshape(-1, x.shape[-1])
        y = pattern_spmm_raw(
            xm, bp.w_comp, bp.block_ids, bp.block,
            backend=backend, interpret=interpret, bm=bm,
            w_scales=bp.w_scales,
        )
    with jax.named_scope("permute"):
        y = jnp.take(y, jnp.asarray(bp.inv_order), axis=1)
        return y.reshape(*lead, bp.n_out).astype(x.dtype)


def flash_attention(
    q: jax.Array,  # [B, Hq, Sq, D]
    k: jax.Array,  # [B, Hkv, Sk, D]
    v: jax.Array,  # [B, Hkv, Sk, D]
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    backend: str | None = None,
    interpret: bool | None = None,
    bq: int = 128,
    bk: int = 128,
) -> jax.Array:
    """GQA flash attention.  Returns [B, Hq, Sq, D]."""
    backend = backend or default_backend()
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    # fold GQA: repeat kv heads (logical; XLA keeps this as a broadcast
    # until the kernel boundary)
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hq, sk, d)
    vf = v.reshape(b * hq, sk, d)
    if backend == "pallas":
        interp = _interpret_flag(interpret)
        qp = _pad_to(qf, 1, bq)
        kp = _pad_to(kf, 1, bk)
        vp = _pad_to(vf, 1, bk)
        out = flash_attention_pallas(
            qp, kp, vp, scale=scale, causal=causal, window=window,
            kv_len=sk, bq=bq, bk=bk, interpret=interp,
        )[:, :sq]
    elif backend == "xla":
        out = ref.flash_attention_ref(
            qf, kf, vf, scale=scale, causal=causal, window=window
        )
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return out.reshape(b, hq, sq, d)


def ou_mvm(
    x: jax.Array,
    w: jax.Array,
    ou_rows: int = 9,
    ou_cols: int = 8,
    interpret: bool = True,
) -> jax.Array:
    """Paper-faithful OU-granular MVM with all-zero input skip."""
    return ou_mvm_pallas(x, w, ou_rows=ou_rows, ou_cols=ou_cols, interpret=interpret)
