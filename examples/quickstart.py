"""Quickstart: the paper's pipeline on one layer, in ~60 lines.

  PYTHONPATH=src python examples/quickstart.py

1. synthesize a pattern-pruned conv layer (Table-II-like statistics),
2. map it onto 512x512 RRAM crossbars with the kernel-reordering scheme,
3. price area / energy / cycles vs the naive mapping (paper Figs 7-8),
4. show the same idea at MXU granularity: block-pattern SpMM (DESIGN §3).
"""

import numpy as np
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core.indexing import build_index_stream, index_overhead_bits
from repro.core.mapping import map_layer, map_layer_naive
from repro.core.simulator import simulate_layer
from repro.core.synthetic import LayerSpec, synthesize_layer
from repro.core.sparse import block_density, build_block_pattern
from repro.kernels.ops import default_backend, pattern_spmm

enable_compile_cache()
rng = np.random.default_rng(0)

# -- 1. a pattern-pruned layer: 128 -> 256 channels, 3x3 kernels ----------
spec = LayerSpec("demo", c_in=128, c_out=256, out_hw=16)
layer = synthesize_layer(
    spec, n_patterns=6, zero_ratio=0.4, target_sparsity=0.85, rng=rng
)
print(f"layer: {spec.c_in}->{spec.c_out}, "
      f"{layer.pdict.num_nonzero_patterns} nonzero patterns, "
      f"{(layer.weights == 0).mean():.1%} sparse")

# -- 2. kernel-reordering mapping -----------------------------------------
mapping = map_layer(layer.pattern_bits)
naive = map_layer_naive(spec.c_out, spec.c_in)
print(f"crossbars: ours={mapping.num_crossbars}  naive={naive.num_crossbars}"
      f"  (area efficiency {naive.num_crossbars/mapping.num_crossbars:.2f}x,"
      f" utilization {mapping.utilization:.0%})")

idx = index_overhead_bits(build_index_stream(mapping))
print(f"index overhead: {idx['total_bits']/8/1024:.1f} KB "
      f"({idx['bits_per_kernel_index']} bits/kernel)")

# -- 3. energy / cycles -----------------------------------------------------
res = simulate_layer(layer, zero_ind=None)
print(f"energy: {res.naive_energy_pj/res.ours_energy_pj:.2f}x  "
      f"speedup: {res.naive_cycles/max(res.ours_cycles,1):.2f}x "
      f"(without input-sparsity skips; the full benchmark adds them)")

# -- 4. the TPU-native form: block-pattern SpMM -----------------------------
w = rng.normal(size=(1024, 1024)).astype(np.float32)
bp = build_block_pattern(w, num_patterns=8, density=0.25)
x = jnp.asarray(rng.normal(size=(8, 1024)).astype(np.float32))
y = pattern_spmm(x, bp)  # the Pallas kernel on a TPU, the XLA path elsewhere
print(f"pattern_spmm ({default_backend()}): block density "
      f"{block_density(bp):.2f} -> {1/block_density(bp):.1f}x fewer "
      f"FLOPs/weight-bytes, output {y.shape}")
