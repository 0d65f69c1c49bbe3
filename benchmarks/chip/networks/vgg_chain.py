"""The VGG chain: 3x3 stride-1 SAME convs, 2x2 max pools, a global average
pool and one FC.

The configuration file states the conv widths, the pools, the input size,
the classes and the pattern-pruning statistics of arXiv:2010.06156
Table II. From it and the file's ``weight_seed`` this module draws the
pruned weights and runs the plain float32 forward that decides
``correct``:

    conv3x3 SAME -> + bias -> channel_norm -> ReLU [-> maxpool 2x2]
    ... -> global average pool -> FC

``channel_norm`` divides each sample's channel by its spatial standard
deviation (a stateless stand-in for batch norm). The program under test
is the same network compiled by ``repro.engine.compile_network``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import model
import work


@dataclasses.dataclass(frozen=True)
class Config:
    """A configuration file, as it is run."""

    name: str
    in_channels: int
    conv_channels: tuple[tuple[int, int], ...]
    pool_after: frozenset[int]  # 1-based conv indices followed by a 2x2 pool
    input_hw: int
    num_classes: int
    kernel: int
    sparsity: float
    zero_pattern_ratio: float
    patterns_per_layer: tuple[int, ...]
    weight_seed: int
    precision: str
    logit_rel_err_limit: float
    raw: dict

    @classmethod
    def from_dict(cls, raw: dict) -> "Config":
        pr = raw["pruning"]
        conv_channels = tuple(tuple(c) for c in raw["conv_channels"])
        return cls(
            name=raw["name"],
            in_channels=int(conv_channels[0][0]),
            conv_channels=conv_channels,
            pool_after=frozenset(raw["pool_after"]),
            input_hw=int(raw["input_hw"]),
            num_classes=int(raw["num_classes"]),
            kernel=int(raw["kernel"]),
            sparsity=float(pr["sparsity"]),
            zero_pattern_ratio=float(pr["zero_pattern_ratio"]),
            patterns_per_layer=tuple(pr["patterns_per_layer"]),
            weight_seed=int(raw["weight_seed"]),
            precision=raw["precision"],
            logit_rel_err_limit=float(raw["correct"]["logit_rel_err_limit"]),
            raw=raw,
        )

    def conv_out_hw(self) -> list[int]:
        """Output side of each conv (stride-1 SAME convs; pools halve)."""
        out, hw = [], self.input_hw
        for i in range(1, len(self.conv_channels) + 1):
            out.append(hw)
            if i in self.pool_after:
                hw //= 2
        return out


def make_weights(cfg: Config) -> tuple[dict, dict]:
    """``(params, pattern_bits)`` drawn from ``cfg.weight_seed``.

    ``params`` is ``{convN: {w: [c_out, c_in, 3, 3], b}, fc: {w: [feat,
    classes], b}}`` as numpy float32; biases are zero.
    """
    rng = np.random.default_rng(cfg.weight_seed)
    k = cfg.kernel * cfg.kernel
    params, bits = {}, {}
    for i, (c_in, c_out) in enumerate(cfg.conv_channels, start=1):
        w, b = model.pruned_conv(
            c_in, c_out, cfg.patterns_per_layer[i - 1],
            cfg.zero_pattern_ratio, cfg.sparsity, rng, k,
        )
        params[f"conv{i}"] = {
            "w": w.reshape(c_out, c_in, cfg.kernel, cfg.kernel),
            "b": np.zeros((c_out,), np.float32),
        }
        bits[f"conv{i}"] = b
    feat = cfg.conv_channels[-1][1]
    fc_rng = np.random.default_rng([cfg.weight_seed, 1])
    params["fc"] = {
        "w": fc_rng.normal(0.0, np.sqrt(1.0 / feat), (feat, cfg.num_classes))
        .astype(np.float32),
        "b": np.zeros((cfg.num_classes,), np.float32),
    }
    return params, bits


def build_program(cfg: Config, params: dict, pattern_bits: dict, options):
    """The program under test: ``compile_network`` of this chain with the
    harness's weights, under ``options`` (a ``CompileOptions``)."""
    from repro.engine import compile_network
    from repro.models.cnn import CNNConfig

    net = CNNConfig(
        conv_channels=cfg.conv_channels, pool_after=cfg.pool_after,
        num_classes=cfg.num_classes, input_hw=cfg.input_hw, kernel=cfg.kernel,
    )
    return compile_network(net, params, pattern_bits, options=options)


def forward(cfg: Config, params: dict, x, precision: str = "highest"):
    """Logits ``[B, classes]`` of images ``x [B, C, H, W]``.

    ``precision``: ``"highest"`` (the reference) or ``"three_pass"`` (the
    control).
    """
    import jax
    import jax.numpy as jnp

    def conv(a, w):
        return jax.lax.conv_general_dilated(
            a, w, (1, 1), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    def matmul(a, w):
        return jnp.matmul(
            a, w, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    conv = model.at_precision(conv, precision)
    matmul = model.at_precision(matmul, precision)
    for i in range(1, len(cfg.conv_channels) + 1):
        p = params[f"conv{i}"]
        x = conv(x, p["w"]) + p["b"][None, :, None, None]
        x = x / (jnp.std(x, axis=(2, 3), keepdims=True) + 1e-5)
        x = jax.nn.relu(x)
        if i in cfg.pool_after:
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 1, 2, 2), (1, 1, 2, 2), "VALID"
            )
    x = x.mean(axis=(2, 3))
    return matmul(x, params["fc"]["w"]) + params["fc"]["b"]


def network_work(cfg: Config, params: dict,
                 precision: str = "fp32") -> list[work.LayerWork]:
    """One :class:`work.LayerWork` per spmm layer (every conv, then the FC)
    of the pruned weights ``params`` (``{convN: {w}, fc: {w}}``)."""
    wbytes = work.WEIGHT_BYTES[precision]
    out = []
    for i, ((c_in, c_out), hw) in enumerate(
        zip(cfg.conv_channels, cfg.conv_out_hw()), start=1
    ):
        nnz = int(np.count_nonzero(np.asarray(params[f"conv{i}"]["w"])))
        out.append(work.LayerWork(
            f"conv{i}",
            flops_per_image=2.0 * hw * hw * nnz,
            act_bytes_per_image=float((c_in + c_out) * hw * hw * work.ACT_BYTES),
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
