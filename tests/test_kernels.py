"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparse import (
    BlockPatternWeight,
    build_block_pattern,
    pattern_spmm_xla,
)
from repro.kernels import ref
from repro.kernels.ops import flash_attention, ou_mvm, pattern_spmm


def _tolerance(dtype):
    # bf16 inputs with fp32 accumulators: reduction-order noise across the
    # pallas/ref paths is a few ULPs of bf16 (~8e-3 relative) per element
    return dict(rtol=8e-2, atol=4e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "m,k,n,block,tile",
    [
        (32, 256, 256, 128, 128),
        (130, 256, 384, 128, 128),  # m not tile-aligned
        (16, 512, 256, 64, 64),
        (8, 128, 128, 128, 128),  # single block
    ],
)
def test_pattern_spmm_sweep(rng, m, k, n, block, tile, dtype):
    w = rng.normal(size=(k, n)).astype(np.float32)
    bp = build_block_pattern(w, num_patterns=4, density=0.4, block=block,
                             tile=tile)
    x = (rng.normal(size=(m, k)) * 0.3).astype(np.float32)
    xj = jnp.asarray(x, dtype)

    y_pallas = pattern_spmm(xj, bp, backend="pallas", interpret=True)
    y_ref = ref.pattern_spmm_ref(
        jnp.asarray(x), bp.w_comp, bp.block_ids, block
    )
    y_ref = jnp.take(y_ref, jnp.asarray(bp.inv_order), axis=1)
    np.testing.assert_allclose(
        np.asarray(y_pallas, np.float32), np.asarray(y_ref, np.float32),
        **_tolerance(dtype),
    )
    # XLA path agrees too
    y_xla = pattern_spmm(jnp.asarray(x), bp, backend="xla")
    np.testing.assert_allclose(
        np.asarray(y_xla), np.asarray(y_ref), rtol=1e-5, atol=1e-5
    )


# the spmm calls' row counts in both benchmark cells (VGG16 at batch 32,
# ResNet-50 at batch 128, 224x224): batch x Ho x Wo
CELL_ROWS = (1_605_632, 401_408, 100_352, 25_088, 6_272)


@pytest.mark.parametrize(
    "m,dtype,want",
    [
        (1, jnp.float32, 8),
        (8, jnp.float32, 8),
        (20, jnp.float32, 32),
        (200, jnp.float32, 200),  # one tile of all 200 rows, no padding
        (1, jnp.bfloat16, 16),  # bf16 min sublane tile is 16
        (100, jnp.bfloat16, 128),
        (32, jnp.float32, 32),  # VGG16's FC at batch 32
        (128, jnp.float32, 128),  # ResNet-50's FC at batch 128
        (130, jnp.float32, 136),  # rounded up to the sublane floor, padded
        (2500, jnp.float32, 840),  # three near-equal tiles, the last ragged
        (6_272, jnp.float32, 896),  # 7 x 128 divides it
        (25_088, jnp.float32, 896),
        (1_605_632, jnp.float32, 1024),
    ],
)
def test_pattern_spmm_bm_autotune(m, dtype, want):
    """bm=None picks a sublane-aligned row tile from M alone."""
    from repro.kernels.ops import _pick_bm

    assert _pick_bm(m, dtype) == want


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
@pytest.mark.parametrize("m", CELL_ROWS)
def test_pick_bm_cell_rows(m, dtype):
    """Every row count of the benchmark cells gets a tile of at least 512
    rows that divides it (no padded copy, no ragged block) and whose blocks
    fit the VMEM budget."""
    from repro.kernels import ops

    bm = ops._pick_bm(m, dtype)
    assert m % bm == 0 and bm % 128 == 0
    assert bm >= 512
    item = jnp.dtype(dtype).itemsize
    vmem = 2 * bm * 128 * item + 3 * bm * 128 * 4 + 2 * 128 * 128 * item
    assert vmem <= ops._VMEM_BUDGET


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("m", [129, 777, 2500, 4_100, 10_007, 123_457])
def test_pick_bm_ragged_rows(m, dtype):
    """A row count no large tile divides: near-equal tiles of at most the
    cap, on the sublane floor, whose last block is ragged by fewer rows
    than one tile (or, under one tile, padded); never a 128-row grid."""
    from repro.kernels import ops

    bm = ops._pick_bm(m, dtype)
    floor = ops._sublane_floor(dtype)
    assert bm % floor == 0 and bm <= ops._bm_cap(dtype, 128, 128)
    tiles = -(-m // bm)
    assert tiles * bm - m < bm
    assert tiles == -(-m // ops._bm_cap(dtype, 128, 128))
    if m >= 4_096:
        assert bm >= 512


@pytest.mark.parametrize("m", [1, 3, 17, 130, 2500])
def test_pattern_spmm_auto_tile_matches_xla(rng, m):
    """The automatic tile (padded, whole or ragged) gives the XLA path's
    result."""
    k, n = 256, 256
    w = rng.normal(size=(k, n)).astype(np.float32)
    bp = build_block_pattern(w, num_patterns=4, density=0.4)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    y_auto = pattern_spmm(x, bp, backend="pallas", interpret=True)
    y_ref = pattern_spmm(x, bp, backend="xla")
    np.testing.assert_allclose(
        np.asarray(y_auto), np.asarray(y_ref), rtol=2e-5, atol=2e-5
    )


def test_pattern_spmm_matches_dense_oracle(rng):
    """Compressed compute == dense matmul with the projected weight —
    the paper's central correctness claim at the kernel level."""
    k, n = 512, 512
    w = rng.normal(size=(k, n)).astype(np.float32)
    bp = build_block_pattern(w, num_patterns=4, density=0.3)
    wd = np.asarray(bp.dense())
    x = rng.normal(size=(17, k)).astype(np.float32)
    y = pattern_spmm(jnp.asarray(x), bp, backend="xla")
    np.testing.assert_allclose(np.asarray(y), x @ wd, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 33])
@pytest.mark.parametrize(
    "b,hq,hkv,sq,sk,d",
    [
        (1, 2, 1, 64, 64, 32),
        (2, 4, 2, 100, 100, 64),  # unaligned seq
        (1, 3, 3, 128, 256, 32),  # cross-length
    ],
)
def test_flash_attention_sweep(rng, b, hq, hkv, sq, sk, d, causal, window,
                               dtype):
    if sq != sk and causal:
        pytest.skip("causal with sq != sk is not used by the models")
    q = (rng.normal(size=(b, hq, sq, d)) * 0.5).astype(np.float32)
    k = (rng.normal(size=(b, hkv, sk, d)) * 0.5).astype(np.float32)
    v = (rng.normal(size=(b, hkv, sk, d)) * 0.5).astype(np.float32)
    args = [jnp.asarray(a, dtype) for a in (q, k, v)]
    o_pal = flash_attention(*args, causal=causal, window=window,
                            backend="pallas", interpret=True, bq=64, bk=64)
    o_ref = flash_attention(*map(jnp.asarray, (q, k, v)), causal=causal,
                            window=window, backend="xla")
    np.testing.assert_allclose(
        np.asarray(o_pal, np.float32), np.asarray(o_ref, np.float32),
        **_tolerance(dtype),
    )


@pytest.mark.parametrize("r,c,ou_r,ou_c", [(100, 52, 9, 8), (64, 64, 16, 8),
                                           (27, 8, 9, 8)])
def test_ou_mvm_sweep(rng, r, c, ou_r, ou_c):
    w = rng.normal(size=(r, c)).astype(np.float32)
    x = rng.normal(size=(r,)).astype(np.float32)
    # carve all-zero bands to exercise the skip path
    x[: ou_r] = 0.0
    y = ou_mvm(jnp.asarray(x), jnp.asarray(w), ou_rows=ou_r, ou_cols=ou_c)
    y_ref = ref.ou_mvm_ref(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5,
                               atol=1e-5)


def test_ou_skip_lossless(rng):
    """The all-zero-input skip (paper §IV-A) must be numerically lossless."""
    w = rng.normal(size=(45, 16)).astype(np.float32)
    x = rng.normal(size=(45,)).astype(np.float32)
    x[9:27] = 0.0
    y_skip = ou_mvm(jnp.asarray(x), jnp.asarray(w))
    dense = x @ w
    np.testing.assert_allclose(np.asarray(y_skip), dense, rtol=1e-5, atol=1e-5)
