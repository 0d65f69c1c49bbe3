"""TPU-native block-pattern sparse matmul layer (hardware adaptation, DESIGN §3).

The paper's pipeline — pattern dictionary -> kernel reordering -> zero
compression -> OU-granular dense compute -> index-driven select/reorder —
re-expressed at MXU granularity:

  * the contraction dimension K is split into 128-row *blocks*;
  * every output column gets a *block mask* (which blocks are nonzero),
    constrained to a small per-layer dictionary (pattern pruning);
  * output columns are permuted so equal-mask columns are adjacent
    (kernel reordering) and grouped into 128-column *tiles*;
  * weights are stored compressed: only the nonzero blocks of each tile,
    as dense [block, tile] bricks (zero-row compression);
  * compute walks, per output tile, only its nonzero blocks via a
    prefetched ``block_ids`` table — the Input Preprocessing Unit becomes
    an index map, the OU becomes the MXU tile (kernels/pattern_spmm.py);
  * results are un-permuted by the stored inverse permutation
    (Output Indexing Unit).

FLOPs and weight bytes drop by exactly the block density.  This module
holds the layout builder, the XLA reference execution path (used by the
distributed dry-run — Pallas TPU kernels don't lower on the CPU backend),
and the projection ("pattern pruning") of dense weights.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import NULL_TRACER

__all__ = [
    "REORDERS",
    "BlockPatternWeight",
    "build_block_pattern",
    "nonzero_block_masks",
    "reorder_columns",
    "predicted_tile_nnz",
    "pattern_spmm_xla",
    "pattern_spmm_xla_quant",
    "block_density",
]

# column-reorder strategies build_block_pattern understands; the mapping
# optimizer (core/mapsearch.py) searches over them, V205 validates tags
REORDERS = ("pattern", "similarity", "hybrid")


@dataclasses.dataclass
class BlockPatternWeight:
    """Compressed block-pattern weight for y = x @ W, W: [K, N].

    Attributes:
      w_comp:     [n_tiles, k_max, block, tile] — dense bricks, zero padded.
                  fp32, or int8 when quantized (``core/quantize.py``).
      block_ids:  [n_tiles, k_max] int32 — which K-block each brick is;
                  padded entries point at block 0 with zero weights.
      nnz:        [n_tiles] int32 — valid bricks per tile.
      new_order:  [N] int32 — column permutation (new position -> original).
      inv_order:  [N] int32 — inverse permutation (original -> new).
      k_in, n_out, block, tile: geometry.
      dict_masks: [P, n_blocks] bool — the layer's pattern dictionary.
      w_scales:   [n_tiles, k_max] fp32 per-row-group dequant scales, or
                  None for fp32 weights.  ``w ≈ w_scales[t, k] * w_comp``.
    """

    w_comp: jax.Array
    block_ids: jax.Array
    nnz: np.ndarray
    new_order: np.ndarray
    inv_order: np.ndarray
    k_in: int
    n_out: int
    block: int
    tile: int
    dict_masks: np.ndarray
    w_scales: jax.Array | None = None

    @property
    def n_tiles(self) -> int:
        return self.w_comp.shape[0]

    @property
    def k_max(self) -> int:
        return self.w_comp.shape[1]

    @property
    def precision(self) -> str:
        """Stored weight precision: 'fp32', or 'int8' when quantized."""
        return "int8" if self.w_scales is not None else "fp32"

    def dense(self) -> jax.Array:
        """Reconstruct the dense [K, N] weight (testing oracle).

        Quantized weights dequantize through their row-group scales, so
        the result approximates the original to the quantization bound.
        """
        nb = self.k_in // self.block
        w = np.zeros((nb, self.block, self.n_out), np.float64)
        wc = np.asarray(self.w_comp, np.float64)
        if self.w_scales is not None:
            wc = wc * np.asarray(self.w_scales, np.float64)[:, :, None, None]
        ids = np.asarray(self.block_ids)
        for t in range(self.n_tiles):
            for k in range(int(self.nnz[t])):
                cols = slice(t * self.tile, (t + 1) * self.tile)
                w[ids[t, k], :, cols] += wc[t, k]
        w = w.reshape(self.k_in, self.n_out)
        # undo the column permutation
        out = np.zeros_like(w)
        out[:, self.new_order] = w
        return jnp.asarray(out)


def block_density(bp: BlockPatternWeight) -> float:
    """Fraction of K-blocks kept (= FLOP / weight-byte ratio vs dense)."""
    n_blocks = bp.k_in // bp.block
    return float(np.sum(bp.nnz)) / (bp.n_tiles * n_blocks)


def _project_masks_to_dictionary(
    masks: np.ndarray, energies: np.ndarray, num_patterns: int
) -> np.ndarray:
    """Pattern pruning of block masks.

    masks: [N, nB] bool (desired per-column block masks),
    energies: [N, nB] block L2^2 (for energy-weighted projection).

    Returns projected masks [N, nB], each row one of <= num_patterns
    dictionary masks (plus the all-zero mask).
    """
    n, nb = masks.shape
    # PDF over observed masks
    keys = [m.tobytes() for m in masks]
    uniq: dict[bytes, int] = {}
    for k in keys:
        uniq[k] = uniq.get(k, 0) + 1
    ranked = sorted(uniq.items(), key=lambda kv: -kv[1])[:num_patterns]
    cand = np.stack(
        [np.frombuffer(k, dtype=bool).copy() for k, _ in ranked]
    )  # [P, nB]
    # project every column to the candidate keeping the most energy,
    # breaking ties toward the smaller pattern
    kept = energies @ cand.T.astype(np.float64)  # [N, P]
    sizes = cand.sum(-1)  # [P]
    score = kept - 1e-12 * sizes[None, :]
    choice = np.argmax(score, axis=1)
    return cand[choice]


def _mask_similarity_rank(uniq: np.ndarray) -> np.ndarray:
    """Greedy nearest-neighbour chain over unique block masks.

    ``uniq``: [U, nB] bool, lexicographically sorted (``np.unique`` rows).
    Starts from the heaviest mask (ties: first in lexicographic order)
    and repeatedly appends the unvisited mask with the greatest overlap
    with the current one (ties: smaller symmetric difference, then
    lexicographic position).  Adjacent-similar masks shrink each tile's
    block-mask union, i.e. the number of stored bricks.  Returns the
    chain rank per unique mask; deterministic for a given input.
    """
    u = np.asarray(uniq, bool)
    n = u.shape[0]
    rank = np.zeros(n, np.int64)
    if n == 0:
        return rank
    remaining = list(range(n))
    cur = int(np.argmax(u.sum(1)))  # argmax -> first max: deterministic
    for step in range(n):
        rank[cur] = step
        remaining.remove(cur)
        if not remaining:
            break
        inter = (u[remaining] & u[cur]).sum(1)
        xor = (u[remaining] ^ u[cur]).sum(1)
        # lexicographically smallest (-overlap, distance, position)
        best = min(range(len(remaining)),
                   key=lambda j: (-int(inter[j]), int(xor[j]), remaining[j]))
        cur = remaining[best]
    return rank


def reorder_columns(masks: np.ndarray, strategy: str = "pattern") -> np.ndarray:
    """Column permutation grouping equal block masks (kernel reordering).

    Returns ``new_order`` (int32 [N], new position -> original column).
    Every strategy groups equal-mask columns adjacently — only the order
    of the *groups* differs, so the compressed operand stays exact and
    the inverse permutation restores the original semantics.  In every
    strategy the all-zero columns (outputs pruned away, and the padding
    of a layer up to whole tiles) go last, in index order, so a layer's
    ``n`` real outputs are its first ``n`` stored columns:

      'pattern'    — groups in lexicographic mask order (the paper's
                     kernel reordering; the historical default).
      'similarity' — groups along a greedy bit-overlap chain
                     (``_mask_similarity_rank``): neighbouring tiles share
                     blocks, minimizing each tile's mask union.
      'hybrid'     — mask weight (set-bit count) descending first,
                     similarity-chain rank within equal weights.
    """
    masks = np.asarray(masks, bool)
    if masks.ndim != 2:
        raise ValueError(f"masks must be [N, n_blocks], got {masks.shape}")
    if strategy not in REORDERS:
        raise ValueError(f"unknown reorder strategy {strategy!r}")
    if masks.shape[0] == 0:
        return np.zeros(0, np.int32)
    if strategy == "pattern":
        mask_keys = np.array([m.tobytes() for m in masks])
        order = np.argsort(mask_keys, kind="stable")
    else:
        uniq, inverse = np.unique(masks, axis=0, return_inverse=True)
        chain = _mask_similarity_rank(uniq)
        if strategy == "similarity":
            rank = chain
        else:  # hybrid
            order_u = np.lexsort((chain, -uniq.sum(1)))
            rank = np.empty(len(uniq), np.int64)
            rank[order_u] = np.arange(len(uniq))
        order = np.argsort(rank[inverse.reshape(-1)], kind="stable")
    # stable within each part, so the all-zero columns keep index order
    zero = ~masks[order].any(axis=1)
    return np.concatenate([order[~zero], order[zero]]).astype(np.int32)


def predicted_tile_nnz(
    masks: np.ndarray, new_order: np.ndarray, tile: int
) -> np.ndarray:
    """Per-tile stored-brick counts a reorder would realize, without
    building the operand: exactly the ``nnz`` ``build_block_pattern``
    computes for the same ``masks``/``new_order`` (the cost model's
    brick predictor — property-tested to be drift-free)."""
    ms = np.asarray(masks, bool)[np.asarray(new_order)]
    n, nb = ms.shape
    if n % tile:
        raise ValueError(f"N={n} not divisible by tile={tile}")
    return ms.reshape(n // tile, tile, nb).any(axis=1).sum(-1).astype(
        np.int32
    )


def nonzero_block_masks(w: np.ndarray, block: int) -> np.ndarray:
    """Exact per-column block masks from the nonzero structure of ``w``.

    w: [K, N] with K divisible by ``block``.  Returns bool [N, K//block];
    a block is kept iff it holds at least one nonzero weight, so compressing
    with these masks is lossless — the path the inference engine uses on
    already-pruned weights.
    """
    w = np.asarray(w)
    k_in, n_out = w.shape
    if k_in % block:
        raise ValueError(f"K={k_in} not divisible by block={block}")
    return (w.reshape(k_in // block, block, n_out) != 0).any(axis=1).T


def build_block_pattern(
    w: np.ndarray,
    num_patterns: int = 8,
    density: float = 0.25,
    block: int = 128,
    tile: int = 128,
    masks: np.ndarray | None = None,
    tracer=None,
    reorder: str = "pattern",
) -> BlockPatternWeight:
    """Pattern-prune + reorder + compress a dense [K, N] weight.

    Steps mirror the paper's flowchart (Fig 3) at block granularity:
    magnitude-driven block masks -> mask PDF -> top-P dictionary ->
    projection -> column reordering -> zero compression.

    When ``masks`` ([N, K//block] bool) is given, the magnitude/projection
    step is skipped and the supplied per-column block masks are used
    verbatim (``num_patterns`` and ``density`` are ignored).  With
    ``nonzero_block_masks(w, block)`` this makes the build an exact
    re-layout of an already-pruned weight.

    ``tracer`` (``obs/trace.py``) records the build's phases as spans —
    ``prune`` (mask projection), ``reorder`` (column permutation),
    ``pack`` (zero compression into bricks) — under the ``compile``
    category; ``None`` records nothing.

    ``reorder`` selects the column-permutation strategy
    (:func:`reorder_columns`).  All strategies produce the same
    ``BlockPatternWeight`` contract and identical semantics (the stored
    inverse permutation undoes the layout); they differ only in how many
    bricks the tiles need.
    """
    tracer = tracer or NULL_TRACER
    w = np.asarray(w, np.float32)
    k_in, n_out = w.shape
    if k_in % block or n_out % tile:
        raise ValueError(f"weight {w.shape} not divisible by ({block},{tile})")
    nb = k_in // block

    if masks is None:
        with tracer.span("prune", cat="compile", n_out=n_out, n_blocks=nb):
            keep = max(1, int(np.ceil(density * nb)))
            energies = (w.reshape(nb, block, n_out) ** 2).sum(1).T  # [N, nB]
            order = np.argsort(-energies, axis=1)
            masks = np.zeros((n_out, nb), bool)
            np.put_along_axis(masks, order[:, :keep], True, axis=1)
            masks = _project_masks_to_dictionary(masks, energies, num_patterns)
    else:
        masks = np.asarray(masks, bool)
        if masks.shape != (n_out, nb):
            raise ValueError(
                f"masks shape {masks.shape} != (N={n_out}, K/block={nb})"
            )

    # kernel reordering: group equal masks; the strategy orders the groups
    with tracer.span("reorder", cat="compile", n_out=n_out,
                     strategy=reorder):
        new_order = reorder_columns(masks, reorder)
        inv_order = np.argsort(new_order).astype(np.int32)
        masks_sorted = masks[new_order]
        w_sorted = w[:, new_order]

    with tracer.span("pack", cat="compile", n_out=n_out) as pack_span:
        n_tiles = n_out // tile
        tile_masks = masks_sorted.reshape(n_tiles, tile, nb).any(axis=1)
        nnz = tile_masks.sum(-1).astype(np.int32)
        k_max = max(int(nnz.max()), 1)
        pack_span.args.update(n_tiles=n_tiles, k_max=k_max)

        w_blocks = w_sorted.reshape(nb, block, n_tiles, tile)
        w_comp = np.zeros((n_tiles, k_max, block, tile), np.float32)
        block_ids = np.zeros((n_tiles, k_max), np.int32)
        for t in range(n_tiles):
            ids = np.nonzero(tile_masks[t])[0]
            for j, bid in enumerate(ids):
                # zero out the entries this tile's columns masked off
                colmask = masks_sorted[t * tile : (t + 1) * tile, bid]
                w_comp[t, j] = w_blocks[bid, :, t, :] * colmask[None, :]
                block_ids[t, j] = bid

    dict_masks = np.unique(masks, axis=0)
    return BlockPatternWeight(
        w_comp=jnp.asarray(w_comp),
        block_ids=jnp.asarray(block_ids),
        nnz=nnz,
        new_order=new_order,
        inv_order=inv_order,
        k_in=k_in,
        n_out=n_out,
        block=block,
        tile=tile,
        dict_masks=dict_masks,
    )


def pattern_spmm_xla(
    x: jax.Array,
    w_comp: jax.Array,
    block_ids: jax.Array,
    block: int,
    unpermute: jax.Array | None = None,
    out_dtype=None,
) -> jax.Array:
    """XLA execution of the compressed matmul: y = x @ W_compressed.

    x: [..., K]; w_comp: [T, k_max, block, tile]; block_ids: [T, k_max].
    Walks the k_max brick slots with a scan; each step gathers the needed
    x-block per tile (the 'input preprocessing unit') and does a dense
    [M, block] @ [block, tile] per tile.  Padded slots have zero weights.
    """
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    k_in = x.shape[-1]
    m = int(np.prod(lead)) if lead else 1
    xb = x.reshape(m, k_in // block, block)
    t, k_max, _, tile = w_comp.shape

    def step(acc, slot):
        ids, w_slot = slot  # ids: [T], w_slot: [T, block, tile]
        xg = jnp.take(xb, ids, axis=1)  # [M, T, block]
        acc = acc + jnp.einsum(
            "mtb,tbn->mtn", xg, w_slot, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,  # fp32, not one bf16 pass
        )
        return acc, None

    acc0 = jnp.zeros((m, t, tile), jnp.float32)
    acc, _ = jax.lax.scan(
        step, acc0, (block_ids.T, jnp.swapaxes(w_comp, 0, 1))
    )
    y = acc.reshape(m, t * tile)
    if unpermute is not None:
        y = jnp.take(y, unpermute, axis=1)
    return y.reshape(*lead, t * tile).astype(out_dtype)


def pattern_spmm_xla_quant(
    xq: jax.Array,
    x_scale: jax.Array,
    w_comp: jax.Array,
    block_ids: jax.Array,
    w_scales: jax.Array,
    block: int,
    out_dtype=jnp.float32,
) -> jax.Array:
    """XLA execution of the *int-quantized* compressed matmul.

    xq: int8 [M, K] (per-row quantized activations, scales ``x_scale``
    [M]); w_comp: int8 [T, k_max, block, tile] with per-brick row-group
    scales ``w_scales`` [T, k_max].  Each scan step is an int8 x int8 ->
    int32 contraction (the MXU-native path on TPU); the brick's row-group
    scale folds into the fp32 accumulator, and the activation row scale
    multiplies once in the output epilogue:

        y = x_scale[:, None] * sum_k w_scales[t, k] * (xq_k @ wq_{t,k})
    """
    m, k_in = xq.shape
    xb = xq.reshape(m, k_in // block, block)
    t, k_max, _, tile = w_comp.shape

    def step(acc, slot):
        ids, w_slot, s_slot = slot  # [T], [T, block, tile], [T]
        xg = jnp.take(xb, ids, axis=1)  # [M, T, block] int8
        part = jnp.einsum(
            "mtb,tbn->mtn", xg, w_slot, preferred_element_type=jnp.int32
        )
        acc = acc + s_slot[None, :, None] * part.astype(jnp.float32)
        return acc, None

    acc0 = jnp.zeros((m, t, tile), jnp.float32)
    acc, _ = jax.lax.scan(
        step,
        acc0,
        (block_ids.T, jnp.swapaxes(w_comp, 0, 1), w_scales.T),
    )
    y = acc * x_scale[:, None, None]
    return y.reshape(m, t * tile).astype(out_dtype)
