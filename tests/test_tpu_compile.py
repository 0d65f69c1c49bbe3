"""The spmm kernels and the im2col patch build compile for a TPU v5e at
VGG16-224 shapes.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology compiles what the chip would compile, and refuses
what it would refuse (misaligned blocks, too much VMEM).  Nothing runs.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, so under
several test workers only the worker given this file loads it.  Keep
every such compile in this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine.executor import extract_patches
from repro.kernels.ops import _pick_bm
from repro.kernels.pattern_spmm import (
    pattern_spmm_pallas,
    pattern_spmm_pallas_quant,
)

BLOCK = TILE = 128

# (M, K, T, k_max) of the VGG16-224 engine at batch 8: conv2 (M = 8*224^2,
# K = 64*9 padded to 640), conv11-13 (M = 8*14^2, K = 512*9), and the FC
# head at the int8 row floor; then the benchmark cells' largest call
# (VGG16's conv2 at batch 32, ResNet-50's stem rows at batch 128) and
# their deepest (VGG16's conv11-13 at batch 32, ResNet-50's layer4 at
# batch 128), each at the row tile ``_pick_bm`` chooses; and a row count
# no large tile divides, whose last row block is ragged
SHAPES = {
    "conv2": (8 * 224 * 224, 640, 1, 5),
    "conv13": (8 * 14 * 14, 4608, 4, 36),
    "fc": (32, 512, 8, 4),
    "cell_largest": (32 * 224 * 224, 640, 1, 5),
    "cell_deepest": (32 * 14 * 14, 4608, 4, 36),
    "ragged": (4_100, 640, 1, 5),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("precision", ["fp32", "int8"])
@pytest.mark.parametrize("layer", sorted(SHAPES))
def test_spmm_kernel_compiles_for_v5e(one_chip, layer, precision):
    m, k, t, k_max = SHAPES[layer]
    dtype = jnp.int8 if precision == "int8" else jnp.float32

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x = sds((m, k), dtype)
    w = sds((t, k_max, BLOCK, TILE), dtype)
    ids = sds((t, k_max), jnp.int32)
    static = {"block": BLOCK, "bm": _pick_bm(m, dtype)}
    if precision == "int8":
        scales = sds((t, k_max), jnp.float32)
        lowered = pattern_spmm_pallas_quant.lower(x, w, ids, scales, **static)
    else:
        lowered = pattern_spmm_pallas.lower(x, w, ids, **static)
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("order,loops", [("tap", 0), ("channel", 1)])
def test_conv2_patches_compile_without_relayout_loop(one_chip, order, loops):
    """conv2's im2col at the benchmark's batch of 32 (64 channels at 224,
    K padded to 640): tap-major is one concatenation and compiles with no
    ``while`` loop; the channel-major build compiles into the relayout
    loop that tap-major replaces (at batch 8 it does not, so batch 8
    would not tell the two apart)."""
    x = jax.ShapeDtypeStruct((32, 64, 224, 224), jnp.float32,
                             sharding=one_chip)
    build = jax.jit(lambda x: extract_patches(x, 3, order, 640))
    text = build.lower(x).compile().as_text()
    assert len(re.findall(r"\swhile\(", text)) == loops
