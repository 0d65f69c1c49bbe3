"""What every network family of the benchmark shares: the seeded draw of a
pattern-pruned conv, the control's arithmetic and the blocked reference run.

Nothing here imports the program under test. A family module
(``networks/<family>.py``, contract in ``spec.py``) draws its weights with
:func:`pruned_conv` where its convs are pattern-pruned (the same draw as
the program's ``core/synthetic``, copied here so the yardstick cannot move
with the program) and writes its plain float32 forward with every conv and
matmul at ``Precision.HIGHEST``, wrapped in :func:`at_precision`.

The reference runs at ``"highest"``. The control, ``"three_pass"``, runs
the same forward with every such op as three bf16 passes (``hi*hi + hi*lo
+ lo*hi``), which is what ``Precision.HIGH`` does on a TPU, written out so
that it computes the same on any backend.
"""

from __future__ import annotations

import numpy as np

ALL_ZERO = 0  # pattern bitmask of a pruned-away kernel


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


def pruned_conv(c_in, c_out, n_patterns, zero_ratio, sparsity, rng, k=9):
    """(weights [c_out, c_in, k], pattern bits [c_out, c_in]) of one conv
    whose kernels use ``n_patterns`` patterns (the all-zero one included),
    drawn from ``rng``."""
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


def at_precision(op, precision: str):
    """``op(activations, weights)``, a float32 op at ``Precision.HIGHEST``,
    as the reference (``"highest"``) or the control (``"three_pass"``)
    computes it."""
    if precision == "highest":
        return op
    if precision == "three_pass":
        return lambda a, w: _three_pass(op, a, w)
    raise ValueError(f"unknown precision {precision!r}")


def logits_in_blocks(net, cfg, params, images: np.ndarray, block: int,
                     precision: str = "highest") -> np.ndarray:
    """Family ``net``'s ``forward`` over ``images`` in blocks of ``block``
    rows (the last block zero-padded, so one shape compiles)."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda p, x: net.forward(cfg, p, x, precision))
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
