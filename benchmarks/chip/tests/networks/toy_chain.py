"""A family that lives only in the tests: one pattern-pruned 3x3 conv of
``width`` channels, channel_norm, ReLU, global average pool, FC.

``test_networks.py`` copies it into a directory of its own and points the
family lookup there, to show that a family enters the harness as a file.
Its configuration keys (``width`` among them) are its own.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import model
import work


@dataclasses.dataclass(frozen=True)
class Config:
    name: str
    in_channels: int
    width: int
    input_hw: int
    num_classes: int
    weight_seed: int
    precision: str
    logit_rel_err_limit: float
    raw: dict

    @classmethod
    def from_dict(cls, raw: dict) -> "Config":
        return cls(
            name=raw["name"], in_channels=int(raw["in_channels"]),
            width=int(raw["width"]), input_hw=int(raw["input_hw"]),
            num_classes=int(raw["num_classes"]),
            weight_seed=int(raw["weight_seed"]), precision=raw["precision"],
            logit_rel_err_limit=float(raw["correct"]["logit_rel_err_limit"]),
            raw=raw,
        )


def make_weights(cfg: Config):
    rng = np.random.default_rng(cfg.weight_seed)
    w, bits = model.pruned_conv(cfg.in_channels, cfg.width, 4, 0.25, 0.6, rng)
    params = {
        "conv1": {"w": w.reshape(cfg.width, cfg.in_channels, 3, 3),
                  "b": np.zeros((cfg.width,), np.float32)},
        "fc": {"w": rng.normal(0.0, 0.3, (cfg.width, cfg.num_classes))
               .astype(np.float32),
               "b": np.zeros((cfg.num_classes,), np.float32)},
    }
    return params, {"conv1": bits}


def build_program(cfg: Config, params, bits, options):
    from repro.engine import compile_network
    from repro.models.cnn import CNNConfig

    net = CNNConfig(conv_channels=((cfg.in_channels, cfg.width),),
                    pool_after=frozenset(), num_classes=cfg.num_classes,
                    input_hw=cfg.input_hw)
    return compile_network(net, params, bits, options=options)


def forward(cfg: Config, params, x, precision: str = "highest"):
    import jax
    import jax.numpy as jnp

    def conv(a, w):
        return jax.lax.conv_general_dilated(
            a, w, (1, 1), "SAME", dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    def matmul(a, w):
        return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    conv = model.at_precision(conv, precision)
    matmul = model.at_precision(matmul, precision)
    x = conv(x, params["conv1"]["w"]) + params["conv1"]["b"][None, :, None, None]
    x = jax.nn.relu(x / (jnp.std(x, axis=(2, 3), keepdims=True) + 1e-5))
    return matmul(x.mean(axis=(2, 3)), params["fc"]["w"]) + params["fc"]["b"]


def network_work(cfg: Config, params, precision: str = "fp32"):
    nnz = int(np.count_nonzero(params["conv1"]["w"]))
    hw = cfg.input_hw
    return [
        work.LayerWork("conv1", 2.0 * hw * hw * nnz,
                       float((cfg.in_channels + cfg.width) * hw * hw * work.ACT_BYTES),
                       float(nnz * work.WEIGHT_BYTES[precision])),
        work.LayerWork("fc", 2.0 * cfg.width * cfg.num_classes,
                       float((cfg.width + cfg.num_classes) * work.ACT_BYTES),
                       float(cfg.width * cfg.num_classes * work.WEIGHT_BYTES[precision])),
    ]
