"""MobileNetV2: inverted-residual blocks with batch norm in inference form.

The configuration file states the stem, the blocks as Table 2 of
arXiv:1801.04381 lists them (expansion t, channels c, repeats n, stride s),
the last 1x1 conv, the input size, the classes and the pattern set of the
depthwise convs. From it and the file's ``weight_seed`` this module draws
the weights and runs the plain float32 forward that decides ``correct``:

    stem: conv3x3/2 -> BN -> ReLU6
    block: [conv1x1 to t*C_in -> BN -> ReLU6, absent where t = 1]
           -> depthwise conv3x3/s -> BN -> ReLU6 -> conv1x1 to c -> BN
           (+ the block's input where s = 1 and C_in = c; no activation)
    ... -> conv1x1 to 1280 -> BN -> ReLU6 -> global average pool -> FC

Every conv pads ``k // 2`` on each side and has no bias; a depthwise conv
is ``conv_general_dilated`` with ``feature_group_count`` equal to its
channels. Batch norm is applied here from gamma, beta and the running
statistics; the program under test (``repro.engine.compile_network`` of
``repro.models.mobilenet.MobileNetV2Config``) folds it into the weights.

Weights (``make_weights``): each depthwise conv draws its own set of
``patterns`` distinct patterns of ``pattern_taps`` taps, every one holding
the centre tap (PatDNN's pattern shape, arXiv:2001.00138), and each of
its kernels takes one of them uniformly; no kernel is pruned away, and
the kept taps are Gaussian at He scale (``sqrt(2 / taps)``). The stem and
every 1x1 conv are dense, Gaussian at He scale (``sqrt(2 / fan_in)``); the
FC is Gaussian at ``sqrt(1 / features)``; gamma is drawn in [0.5, 1),
beta from ``N(0, 0.1)``. The running statistics are those of the seeded
images themselves, as ``bottleneck_resnet`` draws them:
:data:`CALIBRATION_IMAGES` images drawn as the traffic draws them, from
``weight_seed``, go through this forward with batch norm in training form,
and each conv output's per-channel mean and variance become its running
ones. The pass runs on JAX's default device in one jitted call.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

import model
import traffic
import work

CALIBRATION_IMAGES = 8  # images whose statistics are the running ones
CENTRE = 4  # the centre tap of a 3x3 kernel, row-major


@dataclasses.dataclass(frozen=True)
class Layer:
    """One conv: it reads the previous conv's output, adds tensor
    ``residual`` after its batch norm, then clips to [0, 6] where
    ``relu6``."""

    name: str
    c_in: int
    c_out: int
    kernel: int
    stride: int
    groups: int = 1
    residual: str | None = None
    relu6: bool = True


@dataclasses.dataclass(frozen=True)
class Config:
    """A configuration file, as it is run."""

    name: str
    in_channels: int
    input_hw: int
    num_classes: int
    stem_width: int
    head_width: int
    blocks: tuple[tuple[int, int, int, int], ...]  # (t, c, n, s)
    bn_eps: float
    patterns: int  # per depthwise conv
    pattern_taps: int
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
            stem_width=int(raw["stem_width"]),
            head_width=int(raw["head_width"]),
            blocks=tuple(tuple(int(v) for v in b) for b in raw["blocks"]),
            bn_eps=float(raw["bn_eps"]),
            patterns=int(pr["patterns_per_layer"]),
            pattern_taps=int(pr["pattern_taps"]),
            weight_seed=int(raw["weight_seed"]),
            precision=raw["precision"],
            logit_rel_err_limit=float(raw["correct"]["logit_rel_err_limit"]),
            raw=raw,
        )

    def layers(self) -> list[Layer]:
        """Every conv in execution order, torchvision's names."""
        out = [Layer("features.0", self.in_channels, self.stem_width, 3, 2)]
        c, prev, i = self.stem_width, "features.0", 0
        for t, c_out, n, s in self.blocks:
            for r in range(n):
                i += 1
                stride, hidden = (s if r == 0 else 1), t * c
                name, j = f"features.{i}.conv", 0
                if t != 1:
                    out.append(Layer(f"{name}.0", c, hidden, 1, 1))
                    j = 1
                out += [
                    Layer(f"{name}.{j}", hidden, hidden, 3, stride,
                          groups=hidden),
                    Layer(f"{name}.{j + 1}", hidden, c_out, 1, 1,
                          residual=prev if stride == 1 and c == c_out
                          else None, relu6=False),
                ]
                c, prev = c_out, f"{name}.{j + 1}"
        out.append(Layer(f"features.{i + 1}", c, self.head_width, 1, 1))
        return out

    def sides(self) -> dict[str, tuple[int, int]]:
        """``{name: (input side, output side)}``."""
        prev, out = self.input_hw, {}
        for layer in self.layers():
            h = (prev + 2 * (layer.kernel // 2) - layer.kernel) \
                // layer.stride + 1
            out[layer.name] = (prev, h)
            prev = h
        return out


# ------------------------------------------------------------------ weights


def _depthwise(c: int, n_patterns: int, taps: int, rng):
    """``(w [c, 1, 3, 3], bits [c, 1])`` of one depthwise conv."""
    others = [t for t in range(9) if t != CENTRE]
    chosen: list[int] = []
    while len(chosen) < n_patterns:
        pick = rng.choice(others, size=taps - 1, replace=False)
        bits = (1 << CENTRE) | int(np.sum(1 << pick.astype(np.int64)))
        if bits not in chosen:
            chosen.append(bits)
    bits = np.asarray(chosen, np.int64)[rng.integers(0, n_patterns, c)]
    masks = ((bits[:, None] >> np.arange(9)) & 1).astype(np.float32)
    w = rng.normal(0.0, math.sqrt(2.0 / taps), (c, 9)).astype(np.float32)
    return (w * masks).reshape(c, 1, 3, 3), bits[:, None]


def make_weights(cfg: Config) -> tuple[dict, dict]:
    """``(params, pattern_bits)`` drawn from ``cfg.weight_seed``.

    ``params`` is ``{<layer>: {w: [c_out, c_in / groups, k, k], bn:
    {gamma, beta, mean, var}}, fc: {w: [features, classes], b}}`` as numpy
    float32; ``pattern_bits`` (``[c, 1]``) covers the depthwise convs."""
    rng = np.random.default_rng(cfg.weight_seed)
    bn_rng = np.random.default_rng([cfg.weight_seed, 2])
    params, bits = {}, {}
    for layer in cfg.layers():
        k = layer.kernel
        if layer.groups > 1:
            w, bits[layer.name] = _depthwise(
                layer.c_out, cfg.patterns, cfg.pattern_taps, rng
            )
        else:
            fan_in = layer.c_in * k * k
            w = rng.normal(0.0, math.sqrt(2.0 / fan_in),
                           (layer.c_out, layer.c_in, k, k)).astype(np.float32)
        params[layer.name] = {"w": w, "bn": {
            "gamma": bn_rng.uniform(0.5, 1.0, layer.c_out).astype(np.float32),
            "beta": bn_rng.normal(0.0, 0.1, layer.c_out).astype(np.float32),
        }}
    fc_rng = np.random.default_rng([cfg.weight_seed, 1])
    feat = cfg.head_width
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
    """The program under test: ``compile_network`` of this MobileNetV2
    with the harness's weights, under ``options`` (a ``CompileOptions``)."""
    from repro.engine import compile_network
    from repro.models.mobilenet import MobileNetV2Config

    net = MobileNetV2Config(
        blocks=cfg.blocks, stem_width=cfg.stem_width,
        head_width=cfg.head_width, num_classes=cfg.num_classes,
        input_hw=cfg.input_hw, in_channels=cfg.in_channels,
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

    def conv_at(stride, pad, groups):
        def conv(a, w):
            return jax.lax.conv_general_dilated(
                a, w, (stride, stride), ((pad, pad), (pad, pad)),
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                feature_group_count=groups,
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

    tensors, prev, stats = {}, x, {}
    for layer in cfg.layers():
        p = params[layer.name]
        y = conv_at(layer.stride, layer.kernel // 2, layer.groups)(prev, p["w"])
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
        if layer.relu6:
            y = jnp.clip(y, 0.0, 6.0)
        prev = tensors[layer.name] = y
    x = prev.mean(axis=(2, 3))
    return matmul(x, params["fc"]["w"]) + params["fc"]["b"], stats


def network_work(cfg: Config, params: dict,
                 precision: str = "fp32") -> list[work.LayerWork]:
    """One :class:`work.LayerWork` per spmm layer (every conv, then the FC)
    of the pruned weights ``params``: a conv's FLOPs are 2 x its own
    (strided) output positions x its nonzero weights; its bytes read its
    whole input at the input's side and its residual, and write its
    output."""
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
