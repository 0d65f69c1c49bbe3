"""The benchmark's own copy of the model: weights, images and the plain reference.

Nothing here imports the program under test. A configuration file
(``configs/<name>.json``) states the network: conv widths, pools, input
size, classes, and the pattern-pruning statistics of arXiv:2010.06156
Table II. From it and the file's ``weight_seed`` this module draws the
pruned weights (the same draw as the program's ``core/synthetic``, copied
here so the yardstick cannot move with the program), and runs the plain
float32 forward that decides ``correct``:

    conv3x3 SAME -> + bias -> channel_norm -> ReLU [-> maxpool 2x2]
    ... -> global average pool -> FC

``channel_norm`` divides each sample's channel by its spatial standard
deviation (a stateless stand-in for batch norm). The reference runs at
``Precision.HIGHEST``. The control runs the same forward with every
matmul as three bf16 passes (``hi*hi + hi*lo + lo*hi``), which is what
``Precision.HIGH`` does on a TPU, written out so that it computes the same
on any backend.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

ALL_ZERO = 0  # pattern bitmask of a pruned-away kernel


@dataclasses.dataclass(frozen=True)
class NetConfig:
    """A configuration file, as it is run."""

    name: str
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
    def load(cls, path: str | Path) -> "NetConfig":
        raw = json.loads(Path(path).read_text())
        return cls.from_dict(raw)

    @classmethod
    def from_dict(cls, raw: dict) -> "NetConfig":
        pr = raw["pruning"]
        return cls(
            name=raw["name"],
            conv_channels=tuple(tuple(c) for c in raw["conv_channels"]),
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


# ----------------------------------------------------------------- weights


def _sample_distinct_patterns(rng, sizes, k):
    chosen: set[int] = set()
    out = []
    for s in sizes:
        for _ in range(1000):
            pos = rng.choice(k, size=s, replace=False)
            bits = int(np.sum(1 << pos.astype(np.int64)))
            if bits not in chosen:
                chosen.add(bits)
                out.append(bits)
                break
        else:
            raise RuntimeError("could not sample distinct pattern")
    return out


def _allocate_fractions(sizes, nonzero_frac, target_mean_size):
    """f_i >= 0 with sum f = nonzero_frac and mean size target_mean_size,
    by exponential tilting f_i ~ exp(-lam * s_i)."""
    sizes = sizes.astype(np.float64)
    lo, hi = -50.0, 50.0
    for _ in range(200):
        lam = 0.5 * (lo + hi)
        w = np.exp(-lam * (sizes - sizes.mean()))
        mean = float((w * sizes).sum() / w.sum())
        if mean > target_mean_size:
            lo = lam
        else:
            hi = lam
    w = np.exp(-lam * (sizes - sizes.mean()))
    return nonzero_frac * w / w.sum()


def _pruned_layer(c_in, c_out, n_patterns, zero_ratio, sparsity, rng, k=9):
    """(weights [c_out, c_in, k], pattern bits [c_out, c_in]) of one conv
    whose kernels use ``n_patterns`` patterns (the all-zero one included)."""
    n_nonzero = max(1, n_patterns - 1)
    mean_size = k * (1.0 - sparsity) / max(1.0 - zero_ratio, 1e-9)
    mean_size = float(np.clip(mean_size, 1.0, k))
    lo = max(1, int(np.floor(mean_size)) - 1)
    hi = min(k, int(np.ceil(mean_size)) + 2)
    size_pool = list(range(lo, hi + 1))
    sizes = [size_pool[i % len(size_pool)] for i in range(n_nonzero)]
    if int(np.floor(mean_size)) not in sizes:
        sizes[0] = int(np.floor(mean_size))
    pats = _sample_distinct_patterns(rng, sizes, k)
    fracs = _allocate_fractions(
        np.array(sizes, np.float64), 1.0 - zero_ratio, mean_size
    )
    probs = np.concatenate([[zero_ratio], fracs])
    probs = probs / probs.sum()
    all_pats = np.array([ALL_ZERO] + pats, dtype=np.int64)
    choice = rng.choice(len(all_pats), size=c_out * c_in, p=probs)
    bits = all_pats[choice].reshape(c_out, c_in)
    masks = ((bits[..., None] >> np.arange(k)) & 1).astype(np.float64)
    w = rng.normal(0.0, 1.0 / np.sqrt(max(c_in * k, 1)), size=(c_out, c_in, k))
    return (w * masks).astype(np.float32), bits


def make_weights(cfg: NetConfig) -> tuple[dict, dict]:
    """``(params, pattern_bits)`` drawn from ``cfg.weight_seed``.

    ``params`` is ``{convN: {w: [c_out, c_in, 3, 3], b}, fc: {w: [feat,
    classes], b}}`` as numpy float32; biases are zero.
    """
    rng = np.random.default_rng(cfg.weight_seed)
    k = cfg.kernel * cfg.kernel
    params, bits = {}, {}
    for i, (c_in, c_out) in enumerate(cfg.conv_channels, start=1):
        w, b = _pruned_layer(
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


# ------------------------------------------------------------- reference


def _split_bf16(x):
    """``x`` as ``hi + lo``, both bfloat16. The rounding goes through
    ``reduce_precision``: a round trip through bf16 ``astype`` may be folded
    away by the compiler, leaving ``lo`` zero."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _three_pass(op, x, w):
    """``op(x, w)`` from three bf16 products accumulated in float32."""
    import jax.numpy as jnp

    xh, xl = _split_bf16(x)
    wh, wl = _split_bf16(w)
    return (op(xh, wh) + op(xh, wl) + op(xl, wh)).astype(jnp.float32)


def forward(cfg: NetConfig, params: dict, x, precision: str = "highest"):
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

    if precision == "three_pass":
        conv = _partial3(conv)
        matmul = _partial3(matmul)
    elif precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")

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


def _partial3(op):
    return lambda a, w: _three_pass(op, a, w)


def logits_in_blocks(cfg, params, images: np.ndarray, block: int,
                     precision: str = "highest") -> np.ndarray:
    """``forward`` over ``images`` in blocks of ``block`` rows (the last
    block zero-padded, so one shape compiles)."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda p, x: forward(cfg, p, x, precision))
    dev_params = jax.device_put(params)
    out = []
    for i in range(0, len(images), block):
        chunk = images[i:i + block]
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate(
                [chunk, np.zeros((pad, *chunk.shape[1:]), chunk.dtype)]
            )
        out.append(np.asarray(fn(dev_params, jnp.asarray(chunk)))[:block - pad])
    return np.concatenate(out)
