"""Pallas TPU kernel: block-pattern sparse matmul (the paper's OU compute).

y[:, tile_t] = sum_k  x[:, block_ids[t,k]] @ w_comp[t, k]

This is the TPU-native form of the paper's mapping (DESIGN §3):

  * w_comp holds only the *nonzero* 128x128 bricks of each output tile
    (zero-row compression after kernel reordering);
  * ``block_ids`` is the weight-index buffer: it drives the x BlockSpec
    ``index_map`` so each grid step DMAs exactly the input block the brick
    needs — the Input Preprocessing Unit as an index map;
  * each grid step is one MXU-aligned [bm, block] @ [block, bn] — the OU;
  * the fp32 accumulator lives in VMEM scratch across the k dimension.

Grid: (m_tiles, n_tiles, k_max), k innermost so the accumulator stays
resident while bricks stream.  A grid step has a fixed cost (pipeline
bookkeeping, DMA issue, the MXU weight load) of about 0.25 us on a v5e
besides its dot and its DMAs, so the row tile bm is as tall as VMEM and
the row count allow: ``ops._pick_bm`` keeps 8/32/128 for M <= 128 and
above it takes the largest multiple of 128 that divides M, up to
``_BM_MAX`` (1024), when that is at least 512 rows or all of M, else
near-equal tiles whose last block is ragged (Pallas masks its rows past
M).  Each output row is the same sum of the same brick dots in the same
k order at any bm.  VMEM per step at bm = 1024, block = tile = 128, fp32:
x [bm, block] and the output [bm, tile] double-buffered, 2 x 512 KiB
each, the fp32 accumulator 512 KiB and two 64 KiB bricks: 2.6 MiB of
blocks, inside ``ops._VMEM_BUDGET`` (8 MiB) and the 16 MiB scoped VMEM
default with room for the HIGHEST-precision dot's bf16 splits.

Padded brick slots (k >= nnz[t]) carry zero weights and block id 0: they
cost a grid step but contribute zero.  Neither benchmark program has
one (``nnz == k_max`` in every tile).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pattern_spmm_pallas", "pattern_spmm_pallas_quant"]


def _kernel(ids_ref, x_ref, w_ref, o_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # fp32 operands get fp32 products: Mosaic's default for an f32 dot is
    # one bf16 pass, ~2.4e-3 relative error per product on a v5e
    precision = (
        jax.lax.Precision.HIGHEST if x_ref.dtype == jnp.float32 else None
    )
    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[0, 0], preferred_element_type=jnp.float32,
        precision=precision,
    )

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block", "bm", "interpret", "out_dtype")
)
def pattern_spmm_pallas(
    x: jax.Array,
    w_comp: jax.Array,
    block_ids: jax.Array,
    block: int = 128,
    bm: int = 128,
    interpret: bool = False,
    out_dtype=None,
):
    """x: [M, K]; w_comp: [T, k_max, block, tile]; block_ids: [T, k_max].

    Returns y: [M, T*tile] in the *reordered* column order (caller applies
    the inverse permutation — the Output Indexing Unit).
    """
    m, k_in = x.shape
    t, k_max, blk, tile = w_comp.shape
    assert blk == block and k_in % block == 0
    out_dtype = out_dtype or x.dtype

    grid = (pl.cdiv(m, bm), t, k_max)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # x block selected by the prefetched index table
            pl.BlockSpec((bm, block), lambda i, j, k, ids: (i, ids[j, k])),
            # the (j, k) brick
            pl.BlockSpec((1, 1, block, tile), lambda i, j, k, ids: (j, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, tile), lambda i, j, k, ids: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, tile), jnp.float32)],
    )
    fn = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, t * tile), out_dtype),
        interpret=interpret,
        name="pattern_spmm",
    )
    return fn(block_ids, x, w_comp)


def _kernel_quant(ids_ref, wscale_ref, x_ref, w_ref, o_ref, acc_ref):
    j = pl.program_id(1)
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 on the MXU; the brick's row-group dequant scale
    # (prefetched to SMEM alongside the index table) folds into the fp32
    # accumulator, so accumulation across bricks stays exact in fp32
    part = jnp.dot(
        x_ref[...], w_ref[0, 0], preferred_element_type=jnp.int32
    )
    acc_ref[...] += wscale_ref[j, k] * part.astype(jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block", "bm", "interpret", "out_dtype")
)
def pattern_spmm_pallas_quant(
    xq: jax.Array,
    w_comp: jax.Array,
    block_ids: jax.Array,
    w_scales: jax.Array,
    block: int = 128,
    bm: int = 128,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    """Int-quantized variant: xq int8 [M, K]; w_comp int8 bricks with
    per-brick row-group scales ``w_scales`` [T, k_max].

    Returns fp32 partial output [M, T*tile] in reordered column order,
    already dequantized on the weight side; the caller multiplies the
    per-row activation scale in its epilogue (ops.pattern_spmm_raw) and
    applies the inverse permutation.  Grid and specs mirror
    :func:`pattern_spmm_pallas`; ``w_scales`` is the second scalar-prefetch
    operand so each grid step reads its brick scale from SMEM.
    """
    m, k_in = xq.shape
    t, k_max, blk, tile = w_comp.shape
    assert blk == block and k_in % block == 0

    grid = (pl.cdiv(m, bm), t, k_max)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (bm, block), lambda i, j, k, ids, ws: (i, ids[j, k])
            ),
            pl.BlockSpec(
                (1, 1, block, tile), lambda i, j, k, ids, ws: (j, k, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((bm, tile), lambda i, j, k, ids, ws: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, tile), jnp.float32)],
    )
    fn = pl.pallas_call(
        _kernel_quant,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, t * tile), out_dtype),
        interpret=interpret,
        name="pattern_spmm_quant",
    )
    return fn(block_ids, w_scales, xq, w_comp)
