"""Functional CNNs in JAX: the paper's (modified) VGG16 and a miniature CNN.

The paper's benchmark is VGG16 with all 13 conv layers kept and the FC
stack reduced to a single layer (§V-A) so the evaluation is dominated by
the convolutions the mapping scheme targets.  Params are plain pytrees
(dict of arrays); conv weights use layout [C_out, C_in, Kh, Kw] to line up
with ``repro.core`` mapping code.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.synthetic import TABLE_II, VGG16_CONV_CHANNELS, synthesize_network

__all__ = [
    "CNNConfig",
    "vgg16_config",
    "synthetic_vgg16",
    "mini_cnn_config",
    "init_cnn",
    "cnn_apply",
    "channel_norm",
    "max_pool_2x2",
    "conv_weight_names",
]


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    conv_channels: tuple[tuple[int, int], ...]  # (c_in, c_out) per conv
    pool_after: frozenset[int]  # 1-based conv indices followed by 2x2 maxpool
    num_classes: int
    input_hw: int
    kernel: int = 3

    @property
    def num_convs(self) -> int:
        return len(self.conv_channels)


def vgg16_config(num_classes: int = 10, input_hw: int = 32) -> CNNConfig:
    return CNNConfig(
        conv_channels=tuple(VGG16_CONV_CHANNELS),
        pool_after=frozenset({2, 4, 7, 10, 13}),
        num_classes=num_classes,
        input_hw=input_hw,
    )


def synthetic_vgg16(
    dataset: str, seed: int = 0, num_classes: int = 1000
) -> tuple[CNNConfig, dict, dict]:
    """Pattern-pruned VGG16-D at ``dataset``'s input size, from a seed.

    The conv weights and pattern bits are ``core/synthetic``'s Table II
    match for ``dataset``; the FC head is seeded Gaussian (``init_cnn``'s
    scale), so logits and their argmax carry information.  Returns
    ``(cfg, params, pattern_bits)`` for ``compile_network``.
    """
    _, layers = synthesize_network(dataset, seed=seed)
    cfg = vgg16_config(num_classes, TABLE_II[dataset].input_hw)
    params, bits = {}, {}
    for i, layer in enumerate(layers, start=1):
        spec = layer.spec
        params[f"conv{i}"] = {
            "w": jnp.asarray(layer.weights.reshape(spec.c_out, spec.c_in, 3, 3)),
            "b": jnp.zeros((spec.c_out,), jnp.float32),
        }
        bits[f"conv{i}"] = layer.pattern_bits
    feat = cfg.conv_channels[-1][1]
    rng = np.random.default_rng([seed, 1])
    params["fc"] = {
        "w": jnp.asarray(
            rng.normal(0.0, np.sqrt(1.0 / feat), (feat, num_classes)),
            jnp.float32,
        ),
        "b": jnp.zeros((num_classes,), jnp.float32),
    }
    return cfg, params, bits


def mini_cnn_config(
    num_classes: int = 4, input_hw: int = 12, widths: Sequence[int] = (8, 16, 16)
) -> CNNConfig:
    chans, c = [], 1
    for w in widths:
        chans.append((c, w))
        c = w
    return CNNConfig(
        conv_channels=tuple(chans),
        pool_after=frozenset({len(widths) - 1}),
        num_classes=num_classes,
        input_hw=input_hw,
    )


def init_cnn(cfg: CNNConfig, key: jax.Array) -> dict:
    params: dict = {}
    k = cfg.kernel
    keys = jax.random.split(key, cfg.num_convs + 1)
    hw = cfg.input_hw
    for i, (ci, co) in enumerate(cfg.conv_channels, start=1):
        fan_in = ci * k * k
        params[f"conv{i}"] = {
            "w": jax.random.normal(keys[i - 1], (co, ci, k, k), jnp.float32)
            * jnp.sqrt(2.0 / fan_in),
            "b": jnp.zeros((co,), jnp.float32),
        }
        if i in cfg.pool_after:
            hw //= 2
    c_last = cfg.conv_channels[-1][1]
    feat = c_last  # global average pool
    params["fc"] = {
        "w": jax.random.normal(keys[-1], (feat, cfg.num_classes), jnp.float32)
        * jnp.sqrt(1.0 / feat),
        "b": jnp.zeros((cfg.num_classes,), jnp.float32),
    }
    return params


def _conv2d(x: jax.Array, w: jax.Array) -> jax.Array:
    """x: [B, C, H, W], w: [C_out, C_in, Kh, Kw], stride 1, 'same'."""
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(1, 1),
        padding="SAME",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )


def channel_norm(x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Per-sample, per-channel scale normalisation (BN stand-in, stateless).

    Shared by ``cnn_apply`` and the compiled-engine executor so both paths
    apply bit-identical normalisation.  x: [B, C, H, W].

    The reduction runs over the spatial axes ``(2, 3)`` only — never the
    batch axis — so a sample's activations (and therefore its logits) do
    not depend on which other samples share the batch.  That invariance is
    what lets the serving layer zero-pad dead batch slots: an all-zero row
    normalises against its own statistics and stays numerically inert for
    every live row.
    """
    return x / (jnp.std(x, axis=(2, 3), keepdims=True) + eps)


def max_pool_2x2(x: jax.Array) -> jax.Array:
    """2x2 stride-2 max pool.  x: [B, C, H, W]."""
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 2, 2), (1, 1, 2, 2), "VALID"
    )


def cnn_apply(cfg: CNNConfig, params: dict, x: jax.Array) -> jax.Array:
    """Forward pass -> logits [B, num_classes].  x: [B, C, H, W]."""
    for i in range(1, cfg.num_convs + 1):
        p = params[f"conv{i}"]
        x = _conv2d(x, p["w"]) + p["b"][None, :, None, None]
        x = jax.nn.relu(channel_norm(x))
        if i in cfg.pool_after:
            x = max_pool_2x2(x)
    x = x.mean(axis=(2, 3))  # global average pool
    return x @ params["fc"]["w"] + params["fc"]["b"]


def conv_weight_names(cfg: CNNConfig) -> list[str]:
    return [f"conv{i}" for i in range(1, cfg.num_convs + 1)]
