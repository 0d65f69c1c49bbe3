"""MobileNetV2 and its plain float32 reference.

The network is Sandler et al., arXiv:1801.04381, Table 2, at width
multiplier 1.0 (:func:`mobilenet_v2_config`): a 3x3/2 stem conv with 32
channels, seventeen inverted-residual blocks, a 1x1 conv to 1280
channels, a global average pool and one FC.  A block of expansion ``t``,
``c`` output channels and stride ``s`` is

* a 1x1 expansion to ``t * C_in`` channels, batch norm, ReLU6 (absent
  where ``t == 1``);
* a depthwise 3x3 conv with stride ``s``, batch norm, ReLU6;
* a linear 1x1 projection to ``c`` channels and batch norm, with no
  activation;
* the add of the block's input where ``s == 1`` and ``C_in == c``, with
  no activation after it.

Departures from the paper, all as torchvision's ``mobilenet_v2`` has
them:

* padding is explicit and symmetric, ``k // 2`` on each side (PyTorch's
  rule; JAX's ``"SAME"`` pads stride-2 convs on even inputs
  asymmetrically);
* batch norm is in inference form: running mean and variance, eps 1e-5;
* the classifier's dropout is the identity at inference, so the head is
  the pool and one FC;
* the channel counts are Table 2's at width 1.0, which torchvision's
  rounding to multiples of 8 leaves unchanged.

:func:`mobilenet_apply` applies batch norm explicitly from gamma, beta and
the running statistics, with ``lax.conv_general_dilated``'s
``feature_group_count`` for the depthwise convs: the compiler folds batch
norm into each conv's weight and bias and runs a depthwise conv as the
spmm of its block-diagonal matrix, and this forward is what both are
tested against.  Parameters are ``{<layer>: {w: [C_out, C_in / groups,
K, K], bn: {gamma, beta, mean, var}}, fc: {w: [1280, classes], b}}``
with torchvision's layer names: ``features.0`` (the stem),
``features.<i>.conv.<j>`` for block ``i`` (1 to 17; its convs in order,
``j`` counting them), ``features.18`` (the last 1x1 conv).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.cnn import ConvSpec, relu6
from repro.models.resnet import batch_norm

__all__ = [
    "MobileNetV2Config",
    "mobilenet_v2_config",
    "mobilenet_small_config",
    "init_mobilenet",
    "mobilenet_apply",
]

# Table 2 of arXiv:1801.04381: (expansion t, channels c, repeats n, stride s)
TABLE_2 = ((1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
           (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1))


@dataclasses.dataclass(frozen=True)
class MobileNetV2Config:
    """A MobileNetV2: a 3x3/2 stem of ``stem_width`` channels, then per
    ``(t, c, n, s)`` of ``blocks`` ``n`` inverted-residual blocks (stride
    ``s`` on the first), a 1x1 conv to ``head_width`` and the FC."""

    blocks: tuple[tuple[int, int, int, int], ...]
    stem_width: int
    head_width: int
    num_classes: int
    input_hw: int
    in_channels: int = 3
    bn_eps: float = 1e-5

    def block_list(self) -> list[tuple[int, int, int, int]]:
        """``(t, c_in, c_out, stride)`` of each block, in order."""
        out, c = [], self.stem_width
        for t, c_out, n, s in self.blocks:
            for i in range(n):
                out.append((t, c, c_out, s if i == 0 else 1))
                c = c_out
        return out

    def layers(self) -> list[ConvSpec]:
        """Every conv in execution order.  Each block reads the previous
        block's output; its projection adds that input back where the
        block keeps the side and the channels."""
        out = [ConvSpec("features.0", self.in_channels, self.stem_width, 3,
                        2, activation="relu6", norm="none")]
        prev = "features.0"
        for i, (t, c_in, c_out, s) in enumerate(self.block_list(), start=1):
            name, hidden, j = f"features.{i}.conv", t * c_in, 0
            if t != 1:
                out.append(ConvSpec(f"{name}.0", c_in, hidden, 1,
                                    activation="relu6", norm="none"))
                j = 1
            out += [
                ConvSpec(f"{name}.{j}", hidden, hidden, 3, s,
                         activation="relu6", norm="none", groups=hidden),
                ConvSpec(f"{name}.{j + 1}", hidden, c_out, 1,
                         residual=prev if s == 1 and c_in == c_out else None,
                         activation=None, norm="none"),
            ]
            prev = f"{name}.{j + 1}"
        n = len(self.block_list()) + 1
        c_last = self.block_list()[-1][2]
        out.append(ConvSpec(f"features.{n}", c_last, self.head_width, 1,
                            activation="relu6", norm="none"))
        return out

    @property
    def feature_width(self) -> int:
        return self.head_width


def mobilenet_v2_config(num_classes: int = 1000,
                        input_hw: int = 224) -> MobileNetV2Config:
    """MobileNetV2 at width 1.0 (arXiv:1801.04381 Table 2)."""
    return MobileNetV2Config(
        blocks=TABLE_2, stem_width=32, head_width=1280,
        num_classes=num_classes, input_hw=input_hw,
    )


def mobilenet_small_config(num_classes: int = 10,
                           input_hw: int = 32) -> MobileNetV2Config:
    """A narrow MobileNetV2 for tests: the 3x3/2 stem, a ``t = 1`` block,
    a stride-2 block, a block with the residual add, and the 1x1 head."""
    return MobileNetV2Config(
        blocks=((1, 8, 1, 1), (6, 12, 2, 2)), stem_width=16, head_width=32,
        num_classes=num_classes, input_hw=input_hw,
    )


def init_mobilenet(cfg: MobileNetV2Config, key: jax.Array) -> dict:
    """Seeded parameters: He-normal convs (std ``sqrt(2 / fan_in)``, fan in
    ``c_in / groups * k * k``), batch norm with gamma in [0.5, 1.5) (away
    from 0, so folding keeps every zero weight zero), beta and running
    mean ``N(0, 0.1)``, running variance in [0.5, 1.5); a Gaussian FC
    (std ``sqrt(1 / feat)``) with zero bias."""
    params: dict = {}
    layers = cfg.layers()
    keys = jax.random.split(key, len(layers) + 1)
    for spec, k in zip(layers, keys):
        kw, kg, kb, km, kv = jax.random.split(k, 5)
        c, c_in = spec.c_out, spec.c_in // spec.groups
        fan_in = c_in * spec.kernel * spec.kernel
        params[spec.name] = {
            "w": jax.random.normal(
                kw, (c, c_in, spec.kernel, spec.kernel), jnp.float32
            ) * jnp.sqrt(2.0 / fan_in),
            "bn": {
                "gamma": jax.random.uniform(kg, (c,), jnp.float32, 0.5, 1.5),
                "beta": 0.1 * jax.random.normal(kb, (c,), jnp.float32),
                "mean": 0.1 * jax.random.normal(km, (c,), jnp.float32),
                "var": jax.random.uniform(kv, (c,), jnp.float32, 0.5, 1.5),
            },
        }
    feat = cfg.feature_width
    params["fc"] = {
        "w": jax.random.normal(keys[-1], (feat, cfg.num_classes), jnp.float32)
        * jnp.sqrt(1.0 / feat),
        "b": jnp.zeros((cfg.num_classes,), jnp.float32),
    }
    return params


def _conv_bn(x: jax.Array, p: dict, stride: int, eps: float,
             groups: int = 1) -> jax.Array:
    w = jnp.asarray(p["w"])
    pad = w.shape[-1] // 2
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    )
    return batch_norm(y, p["bn"], eps)


def mobilenet_apply(cfg: MobileNetV2Config, params: dict,
                    x: jax.Array) -> jax.Array:
    """Forward pass -> logits [B, num_classes].  x: [B, C, H, W]; every
    conv and matmul at ``"highest"`` precision."""
    eps = cfg.bn_eps
    with jax.default_matmul_precision("highest"):
        x = relu6(_conv_bn(x, params["features.0"], 2, eps))
        blocks = cfg.block_list()
        for i, (t, c_in, c_out, s) in enumerate(blocks, start=1):
            name, y = f"features.{i}.conv", x
            convs = [f"{name}.{j}" for j in range(3 if t != 1 else 2)]
            if t != 1:
                y = relu6(_conv_bn(y, params[convs.pop(0)], 1, eps))
            y = relu6(_conv_bn(y, params[convs[0]], s, eps,
                               groups=t * c_in))
            y = _conv_bn(y, params[convs[1]], 1, eps)
            x = x + y if s == 1 and c_in == c_out else y
        x = relu6(_conv_bn(x, params[f"features.{len(blocks) + 1}"], 1, eps))
        x = x.mean(axis=(2, 3))  # global average pool
        return x @ jnp.asarray(params["fc"]["w"]) + jnp.asarray(
            params["fc"]["b"]
        )
