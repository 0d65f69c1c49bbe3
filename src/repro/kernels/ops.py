"""Public jit'd wrappers around the Pallas kernels.

Each op dispatches between:
  * the Pallas TPU kernel (``backend='pallas'``), compiled for the TPU, or
    run by the Pallas interpreter only when the caller passes
    ``interpret=True`` (the CPU tests do), and
  * the XLA path (``backend='xla'``), which runs on any platform.

``backend=None`` picks Pallas on TPU devices and XLA elsewhere.  Asking
for ``backend='pallas'`` off the TPU without ``interpret=True`` raises:
nothing silently swaps the compiled kernel for the interpreter.  Shapes
are padded to tile multiples here so kernels only see aligned sizes.
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


def _pick_bm(m: int, dtype) -> int:
    """Row-tile for pattern_spmm, autotuned from the (static) batch M.

    Serving batches are often tiny; padding 1 row up to bm=128 wastes a
    128x factor of MXU work, so pick the smallest sublane-aligned tile that
    covers M.  The floor keeps the second-minor dimension at the dtype's
    minimum TPU tile (8 for 4-byte, 16 for 2-byte, 32 for 1-byte types).
    """
    floor = {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)
    for cand in (8, 32, 128):
        if m <= cand:
            return max(cand, floor)
    return 128


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
        if bm is None:
            bm = _pick_bm(m, xin.dtype)
        xp = _pad_to(xin, 0, bm)
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

    ``bm=None`` (default) autotunes the row tile from the batch size.
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
