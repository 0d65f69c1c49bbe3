"""A bottleneck ResNet v1.5 with batch norm in inference form.

The configuration file states the stages (blocks and widths), the stem,
the input size, the classes and the pattern-pruning statistics of the 3x3
convs (arXiv:2010.06156 Table II). From it and the file's ``weight_seed``
this module draws the weights and runs the plain float32 forward that
decides ``correct``:

    stem: conv7x7/2 -> BN -> ReLU -> maxpool 3x3/2 (pad 1, -inf)
    block: conv1x1 -> BN -> ReLU -> conv3x3/s -> BN -> ReLU -> conv1x1 -> BN
           + shortcut (identity, or conv1x1/s -> BN on a stage's first
           block) -> ReLU
    ... -> global average pool -> FC

Every conv pads ``k // 2`` on each side and has no bias; the stride 2 of a
stage's first block sits on its 3x3 conv (v1.5). Batch norm is applied
here from gamma, beta and the running statistics; the program under test
(``repro.engine.compile_network`` of ``repro.models.resnet.ResNetConfig``)
folds it into the weights.

Weights (``make_weights``): the 3x3 convs are ``model.pruned_conv``'s
pattern-pruned draw, every other conv Gaussian at He scale
(``sqrt(2 / fan_in)``), the FC Gaussian at ``sqrt(1 / features)``; gamma
is drawn in [0.5, 1), beta from ``N(0, 0.1)``. The running statistics are
those of the seeded images themselves: :data:`CALIBRATION_IMAGES` images
drawn as the traffic draws them, from ``weight_seed``, go through this
forward with batch norm in training form (each conv's output normalised
by its own per-channel mean and variance over the batch and positions),
and those means and variances become the running ones. So every batch
norm sees inputs of about its running statistics, and activations stay
O(1) through all 16 blocks. The pass runs on JAX's default device in one
jitted call; like any float32 result, its statistics may differ in their
last bits between devices.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

import model
import traffic
import work

CALIBRATION_IMAGES = 8  # images whose statistics are the running ones


@dataclasses.dataclass(frozen=True)
class Layer:
    """One conv: it reads tensor ``src`` (``None``: the previous conv's
    output), adds tensor ``residual`` before its ReLU, and pools."""

    name: str
    c_in: int
    c_out: int
    kernel: int
    stride: int
    src: str | None = None
    residual: str | None = None
    relu: bool = True
    pool: bool = False  # the stem's 3x3/2 max pool


@dataclasses.dataclass(frozen=True)
class Config:
    """A configuration file, as it is run."""

    name: str
    in_channels: int
    input_hw: int
    num_classes: int
    stage_blocks: tuple[int, ...]
    stage_widths: tuple[int, ...]
    stem_width: int
    stem_kernel: int
    expansion: int
    bn_eps: float
    sparsity: float
    zero_pattern_ratio: float
    patterns_per_layer: tuple[int, ...]  # per 3x3 conv, in order
    weight_seed: int
    precision: str
    logit_rel_err_limit: float
    raw: dict

    @classmethod
    def from_dict(cls, raw: dict) -> "Config":
        pr = raw["pruning"]
        return cls(
            name=raw["name"],
            in_channels=int(raw["in_channels"]),
            input_hw=int(raw["input_hw"]),
            num_classes=int(raw["num_classes"]),
            stage_blocks=tuple(int(n) for n in raw["stage_blocks"]),
            stage_widths=tuple(int(n) for n in raw["stage_widths"]),
            stem_width=int(raw["stem_width"]),
            stem_kernel=int(raw["stem_kernel"]),
            expansion=int(raw["expansion"]),
            bn_eps=float(raw["bn_eps"]),
            sparsity=float(pr["sparsity"]),
            zero_pattern_ratio=float(pr["zero_pattern_ratio"]),
            patterns_per_layer=tuple(pr["patterns_per_layer"]),
            weight_seed=int(raw["weight_seed"]),
            precision=raw["precision"],
            logit_rel_err_limit=float(raw["correct"]["logit_rel_err_limit"]),
            raw=raw,
        )

    def layers(self) -> list[Layer]:
        """Every conv in execution order, torchvision's names; a stage's
        first block runs its projection (``downsample``) first."""
        out = [Layer("stem", self.in_channels, self.stem_width,
                     self.stem_kernel, 2, pool=True)]
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
                    out.append(Layer(shortcut, c, c_out, 1, stride, src=prev,
                                     relu=False))
                out += [
                    Layer(f"{name}.conv1", c, width, 1, 1, src=src),
                    Layer(f"{name}.conv2", width, width, 3, stride),
                    Layer(f"{name}.conv3", width, c_out, 1, 1,
                          residual=shortcut),
                ]
                c, prev = c_out, f"{name}.conv3"
        return out

    def sides(self) -> dict[str, tuple[int, int]]:
        """``{name: (input side, output side before the pool)}``."""
        side, prev, out = {"input": self.input_hw}, self.input_hw, {}
        for layer in self.layers():
            h_in = side[layer.src] if layer.src is not None else prev
            h = (h_in + 2 * (layer.kernel // 2) - layer.kernel) \
                // layer.stride + 1
            out[layer.name] = (h_in, h)
            # the stem's max pool: 3x3, stride 2, padding 1
            prev = side[layer.name] = (h - 1) // 2 + 1 if layer.pool else h
        return out


# ------------------------------------------------------------------ weights


def make_weights(cfg: Config) -> tuple[dict, dict]:
    """``(params, pattern_bits)`` drawn from ``cfg.weight_seed``.

    ``params`` is ``{<layer>: {w: [c_out, c_in, k, k], bn: {gamma, beta,
    mean, var}}, fc: {w: [features, classes], b}}`` as numpy float32;
    ``pattern_bits`` covers the 3x3 convs."""
    rng = np.random.default_rng(cfg.weight_seed)
    bn_rng = np.random.default_rng([cfg.weight_seed, 2])
    layers = cfg.layers()
    n3 = sum(layer.kernel == 3 for layer in layers)
    if len(cfg.patterns_per_layer) != n3:
        raise ValueError(f"{cfg.name}: {len(cfg.patterns_per_layer)} pattern "
                         f"counts for {n3} 3x3 convs")
    params, bits, i3 = {}, {}, 0
    for layer in layers:
        k = layer.kernel
        if k == 3:
            w, b = model.pruned_conv(
                layer.c_in, layer.c_out, cfg.patterns_per_layer[i3],
                cfg.zero_pattern_ratio, cfg.sparsity, rng, k * k,
            )
            w = w.reshape(layer.c_out, layer.c_in, k, k)
            bits[layer.name] = b
            i3 += 1
        else:
            fan_in = layer.c_in * k * k
            w = rng.normal(0.0, math.sqrt(2.0 / fan_in),
                           (layer.c_out, layer.c_in, k, k)).astype(np.float32)
        params[layer.name] = {"w": w, "bn": {
            "gamma": bn_rng.uniform(0.5, 1.0, layer.c_out).astype(np.float32),
            "beta": bn_rng.normal(0.0, 0.1, layer.c_out).astype(np.float32),
        }}
    feat = cfg.stage_widths[-1] * cfg.expansion
    fc_rng = np.random.default_rng([cfg.weight_seed, 1])
    params["fc"] = {
        "w": fc_rng.normal(0.0, math.sqrt(1.0 / feat), (feat, cfg.num_classes))
        .astype(np.float32),
        "b": np.zeros((cfg.num_classes,), np.float32),
    }
    _calibrate(cfg, params)
    return params, bits


def _calibrate(cfg: Config, params: dict) -> None:
    """Set every running mean and variance to the batch statistics of the
    seeded calibration images (module docstring)."""
    import jax
    import jax.numpy as jnp

    images = traffic.make_images(cfg.weight_seed, CALIBRATION_IMAGES,
                                 cfg.in_channels, cfg.input_hw)
    stats = jax.jit(
        lambda p, x: _run(cfg, p, x, "highest", calibrate=True)[1]
    )(params, jnp.asarray(images))
    for name, (mean, var) in stats.items():
        params[name]["bn"]["mean"] = np.asarray(mean, np.float32)
        params[name]["bn"]["var"] = np.asarray(var, np.float32)


def build_program(cfg: Config, params: dict, pattern_bits: dict, options):
    """The program under test: ``compile_network`` of this ResNet with the
    harness's weights, under ``options`` (a ``CompileOptions``)."""
    from repro.engine import compile_network
    from repro.models.resnet import ResNetConfig

    net = ResNetConfig(
        stage_blocks=cfg.stage_blocks, stage_widths=cfg.stage_widths,
        stem_width=cfg.stem_width, num_classes=cfg.num_classes,
        input_hw=cfg.input_hw, in_channels=cfg.in_channels,
        expansion=cfg.expansion, stem_kernel=cfg.stem_kernel,
        bn_eps=cfg.bn_eps,
    )
    return compile_network(net, params, pattern_bits, options=options)


def forward(cfg: Config, params: dict, x, precision: str = "highest"):
    """Logits ``[B, classes]`` of images ``x [B, C, H, W]``.

    ``precision``: ``"highest"`` (the reference) or ``"three_pass"`` (the
    control)."""
    return _run(cfg, params, x, precision)[0]


def _run(cfg: Config, params: dict, x, precision: str,
         calibrate: bool = False):
    """``(logits, stats)``: batch norm from the running statistics or, with
    ``calibrate``, from each conv output's own per-channel mean and
    variance, which ``stats`` returns by layer."""
    import jax
    import jax.numpy as jnp

    def conv_at(stride, pad):
        def conv(a, w):
            return jax.lax.conv_general_dilated(
                a, w, (stride, stride), ((pad, pad), (pad, pad)),
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
        return model.at_precision(conv, precision)

    def matmul(a, w):
        return jnp.matmul(
            a, w, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    matmul = model.at_precision(matmul, precision)

    def ch(v):
        return v[None, :, None, None]

    tensors, prev, stats = {"input": x}, x, {}
    for layer in cfg.layers():
        p = params[layer.name]
        a = prev if layer.src is None else tensors[layer.src]
        y = conv_at(layer.stride, layer.kernel // 2)(a, p["w"])
        bn = p["bn"]
        if calibrate:
            mean, var = y.mean(axis=(0, 2, 3)), y.var(axis=(0, 2, 3))
            stats[layer.name] = (mean, var)
        else:
            mean, var = bn["mean"], bn["var"]
        scale = bn["gamma"] / jnp.sqrt(var + cfg.bn_eps)
        y = (y - ch(mean)) * ch(scale) + ch(bn["beta"])
        if layer.residual is not None:
            y = y + tensors[layer.residual]
        if layer.relu:
            y = jax.nn.relu(y)
        if layer.pool:
            y = jax.lax.reduce_window(
                y, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                ((0, 0), (0, 0), (1, 1), (1, 1)),
            )
        prev = tensors[layer.name] = y
    x = prev.mean(axis=(2, 3))
    return matmul(x, params["fc"]["w"]) + params["fc"]["b"], stats


def network_work(cfg: Config, params: dict,
                 precision: str = "fp32") -> list[work.LayerWork]:
    """One :class:`work.LayerWork` per spmm layer (every conv, then the FC)
    of the pruned weights ``params``: a conv's FLOPs are 2 x its own
    (strided) output positions x its nonzero weights; its bytes read its
    input at the input's side and its residual, and write its output."""
    wbytes = work.WEIGHT_BYTES[precision]
    sides = cfg.sides()
    out = []
    for layer in cfg.layers():
        h_in, h = sides[layer.name]
        nnz = int(np.count_nonzero(np.asarray(params[layer.name]["w"])))
        acts = layer.c_in * h_in * h_in + layer.c_out * h * h
        if layer.residual is not None:
            acts += layer.c_out * h * h
        out.append(work.LayerWork(
            layer.name,
            flops_per_image=2.0 * h * h * nnz,
            act_bytes_per_image=float(acts * work.ACT_BYTES),
            weight_bytes=float(nnz * wbytes),
        ))
    feat, classes = np.asarray(params["fc"]["w"]).shape
    out.append(work.LayerWork(
        "fc",
        flops_per_image=2.0 * feat * classes,
        act_bytes_per_image=float((feat + classes) * work.ACT_BYTES),
        weight_bytes=float(feat * classes * wbytes),
    ))
    return out
