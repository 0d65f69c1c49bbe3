"""ResNet v1.5 with bottleneck blocks, and its plain float32 reference.

The network is He et al., arXiv:1512.03385, Table 1 (the 50-layer column
for :func:`resnet50_v15_config`): a 7x7/2 stem conv with 64 channels, a
3x3/2 max pool, stages of bottleneck blocks (1x1 -> 3x3 -> 1x1,
expansion 4), a global average pool and one FC.  Every conv is followed
by batch norm; a block ends in ``relu(bn3(conv3) + shortcut)``.

Departures from the paper, all as torchvision's ``resnet50`` and the
MLPerf Inference reference model have them:

* v1.5: the stride 2 of a stage's first block sits on its 3x3 conv, not
  on its first 1x1;
* the shortcut of each stage's first block is a 1x1 conv with the
  block's stride plus batch norm (projection, option B), identity
  elsewhere;
* padding is explicit and symmetric, ``k // 2`` on each side (PyTorch's
  rule; JAX's ``"SAME"`` pads stride-2 convs on even inputs
  asymmetrically), and the max pool pads by 1 with ``-inf``;
* batch norm is in inference form: running mean and variance, eps 1e-5.

:func:`resnet_apply` applies batch norm explicitly from gamma, beta and
the running statistics: the compiler folds it into each conv's weight and
bias (``engine/lowering.fold_bn``), and this forward is what the folding
is tested against.  Parameters are ``{<layer>: {w: [C_out, C_in, K, K],
bn: {gamma, beta, mean, var}}, fc: {w: [feat, classes], b}}`` with
torchvision's layer names (``stem``, ``layer1.0.conv1``,
``layer1.0.downsample``, ...).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.cnn import ConvSpec, max_pool_3x3s2

__all__ = [
    "ResNetConfig",
    "resnet50_v15_config",
    "resnet_small_config",
    "init_resnet",
    "resnet_apply",
    "batch_norm",
]


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    """A bottleneck ResNet: ``stage_blocks[i]`` blocks of width
    ``stage_widths[i]`` (output ``width * expansion``), stride 2 in every
    stage but the first."""

    stage_blocks: tuple[int, ...]
    stage_widths: tuple[int, ...]
    stem_width: int
    num_classes: int
    input_hw: int
    in_channels: int = 3
    expansion: int = 4
    stem_kernel: int = 7
    bn_eps: float = 1e-5

    def layers(self) -> list[ConvSpec]:
        """Every conv in execution order.  A stage's first block runs its
        projection shortcut (``downsample``) first; the block's conv1 and
        the shortcut both read the block's input, and conv3 adds the
        shortcut before its ReLU."""
        out = [ConvSpec("stem", self.in_channels, self.stem_width,
                        self.stem_kernel, 2, norm="none", pool="max3s2")]
        c, prev = self.stem_width, "stem"
        for s, (n, width) in enumerate(
            zip(self.stage_blocks, self.stage_widths), start=1
        ):
            c_out = width * self.expansion
            for b in range(n):
                name = f"layer{s}.{b}"
                stride = 2 if b == 0 and s > 1 else 1
                shortcut, src = prev, None
                if stride != 1 or c != c_out:
                    shortcut, src = f"{name}.downsample", prev
                    out.append(ConvSpec(
                        f"{name}.downsample", c, c_out, 1, stride, src=prev,
                        activation=None, norm="none",
                    ))
                out += [
                    ConvSpec(f"{name}.conv1", c, width, 1, src=src,
                             norm="none"),
                    ConvSpec(f"{name}.conv2", width, width, 3, stride,
                             norm="none"),
                    ConvSpec(f"{name}.conv3", width, c_out, 1,
                             residual=shortcut, norm="none"),
                ]
                c, prev = c_out, f"{name}.conv3"
        return out

    @property
    def feature_width(self) -> int:
        return self.stage_widths[-1] * self.expansion


def resnet50_v15_config(num_classes: int = 1000,
                        input_hw: int = 224) -> ResNetConfig:
    """ResNet-50 v1.5 (arXiv:1512.03385 Table 1, 50-layer column)."""
    return ResNetConfig(
        stage_blocks=(3, 4, 6, 3), stage_widths=(64, 128, 256, 512),
        stem_width=64, num_classes=num_classes, input_hw=input_hw,
    )


def resnet_small_config(num_classes: int = 10,
                        input_hw: int = 32) -> ResNetConfig:
    """A narrow two-stage ResNet for tests: the 7x7 stem and 3x3/2 pool,
    one block in stage 1 (projection shortcut, stride 1) and two in stage
    2 (projection with stride 2, then an identity shortcut)."""
    return ResNetConfig(
        stage_blocks=(1, 2), stage_widths=(4, 8), stem_width=8,
        num_classes=num_classes, input_hw=input_hw,
    )


def init_resnet(cfg: ResNetConfig, key: jax.Array) -> dict:
    """Seeded parameters: He-normal convs (std ``sqrt(2 / fan_in)``),
    batch norm with gamma in [0.5, 1.5) (away from 0, so folding keeps
    every zero weight zero), beta and running mean ``N(0, 0.1)``, running
    variance in [0.5, 1.5); a Gaussian FC (std ``sqrt(1 / feat)``) with
    zero bias."""
    params: dict = {}
    layers = cfg.layers()
    keys = jax.random.split(key, len(layers) + 1)
    for spec, k in zip(layers, keys):
        kw, kg, kb, km, kv = jax.random.split(k, 5)
        c = spec.c_out
        fan_in = spec.c_in * spec.kernel * spec.kernel
        params[spec.name] = {
            "w": jax.random.normal(
                kw, (c, spec.c_in, spec.kernel, spec.kernel), jnp.float32
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


def batch_norm(x: jax.Array, bn: dict, eps: float) -> jax.Array:
    """Inference batch norm of ``x [B, C, H, W]`` from running statistics."""
    def ch(v):
        return jnp.asarray(v)[None, :, None, None]

    scale = jnp.asarray(bn["gamma"]) / jnp.sqrt(jnp.asarray(bn["var"]) + eps)
    return (x - ch(bn["mean"])) * ch(scale) + ch(bn["beta"])


def _conv_bn(x: jax.Array, p: dict, stride: int, eps: float) -> jax.Array:
    w = jnp.asarray(p["w"])
    pad = w.shape[-1] // 2
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    return batch_norm(y, p["bn"], eps)


def resnet_apply(cfg: ResNetConfig, params: dict, x: jax.Array) -> jax.Array:
    """Forward pass -> logits [B, num_classes].  x: [B, C, H, W]; every
    conv and matmul at ``"highest"`` precision."""
    eps = cfg.bn_eps
    with jax.default_matmul_precision("highest"):
        x = jax.nn.relu(_conv_bn(x, params["stem"], 2, eps))
        x = max_pool_3x3s2(x)
        for s, (n, _) in enumerate(
            zip(cfg.stage_blocks, cfg.stage_widths), start=1
        ):
            for b in range(n):
                name = f"layer{s}.{b}"
                stride = 2 if b == 0 and s > 1 else 1
                shortcut = x
                if f"{name}.downsample" in params:
                    shortcut = _conv_bn(
                        x, params[f"{name}.downsample"], stride, eps
                    )
                y = jax.nn.relu(_conv_bn(x, params[f"{name}.conv1"], 1, eps))
                y = jax.nn.relu(
                    _conv_bn(y, params[f"{name}.conv2"], stride, eps)
                )
                y = _conv_bn(y, params[f"{name}.conv3"], 1, eps)
                x = jax.nn.relu(y + shortcut)
        x = x.mean(axis=(2, 3))  # global average pool
        return x @ jnp.asarray(params["fc"]["w"]) + jnp.asarray(
            params["fc"]["b"]
        )
