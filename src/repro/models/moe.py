"""Mixture-of-Experts FFN with shared experts and capacity-based dispatch.

Two execution paths:

  * **shard_map EP** (distributed default): expert parallelism shares the
    'model' mesh axis.  Each (data, model) shard sorts only its *local*
    tokens (65k, not 1M-global) and runs only its *local* experts; the
    weighted combine is a local scatter-add followed by a psum over
    'model'.  This keeps the GSPMD partitioner away from distributed-sort
    (which otherwise dominates compile time at 160-256 experts x 512
    devices) and is the production EP design: the only collective is the
    final all-reduce, which XLA fuses with the layer's existing reduction.

  * **single-device path** (smoke tests, no mesh context): same dispatch
    logic with global tokens and all experts.

Dispatch is sort-based (dropless up to the capacity factor): (token, k)
pairs sort by expert id, each expert takes up to C tokens, overflow drops
(capacity semantics; the drop rate at cf=1.25 is <1% for balanced routers
— reported by the MoE bench).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.layers import linear, linear_init, mlp_apply, mlp_init

__all__ = ["MoEConfig", "moe_init", "moe_apply"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    d_ff_shared: int | None = None  # defaults to n_shared * d_ff_expert
    capacity_factor: float = 1.25
    act: str = "swiglu"
    model_shards: int = 16
    router_scale: bool = True  # normalise top-k weights to sum 1


def moe_init(key, cfg: MoEConfig, param_dtype=jnp.float32):
    k_r, k_e, k_s = jax.random.split(key, 3)
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    params, specs, static = {}, {}, {}

    params["router"], specs["router"] = linear_init(
        k_r, d, e, "embed", "unsharded", param_dtype=param_dtype
    )

    ke = jax.random.split(k_e, 3)
    scale_in, scale_out = d ** -0.5, f ** -0.5
    params["experts"] = {
        "gate": jax.random.normal(ke[0], (e, d, f), param_dtype) * scale_in,
        "up": jax.random.normal(ke[1], (e, d, f), param_dtype) * scale_in,
        "down": jax.random.normal(ke[2], (e, f, d), param_dtype) * scale_out,
    }
    specs["experts"] = {
        "gate": ("expert", None, None),
        "up": ("expert", None, None),
        "down": ("expert", None, None),
    }
    if cfg.n_shared:
        f_sh = cfg.d_ff_shared or cfg.n_shared * cfg.d_ff_expert
        params["shared"], specs["shared"], static["shared"] = mlp_init(
            k_s, d, f_sh, act=cfg.act, sparse=None,
            model_shards=cfg.model_shards, param_dtype=param_dtype,
        )
    return params, specs, static


def _dispatch_compute_combine(
    xf: jax.Array,  # [T, D] local tokens
    top_w: jax.Array,  # [T, k]
    top_e: jax.Array,  # [T, k] global expert ids
    experts: dict,  # local expert weights [E_loc, ...]
    cfg: MoEConfig,
    e0: jax.Array | int,  # first global expert id owned locally
) -> jax.Array:
    """Capacity-gather local tokens to local experts, run the FFNs, and
    scatter-add the weighted outputs back.  Returns the *partial* output
    (contributions of local experts only)."""
    t, d = xf.shape
    k = cfg.top_k
    e_loc = experts["up"].shape[0]
    cap = int(max(1, round(t * k / cfg.n_experts * cfg.capacity_factor)))

    flat_e = top_e.reshape(-1) - e0  # local expert index (may be OOB)
    flat_w = top_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    local = (flat_e >= 0) & (flat_e < e_loc)
    sort_key = jnp.where(local, flat_e, e_loc)  # foreign pairs sort last

    order = jnp.argsort(sort_key, stable=True)
    e_sorted = sort_key[order]
    tok_sorted = flat_tok[order]
    w_sorted = jnp.where(local[order], flat_w[order], 0.0)
    seg_pos = jnp.arange(e_sorted.shape[0])
    group_start = jnp.searchsorted(e_sorted, jnp.arange(e_loc + 1), side="left")
    pos_in_group = seg_pos - group_start[jnp.clip(e_sorted, 0, e_loc)]
    keep = (e_sorted < e_loc) & (pos_in_group < cap)

    slot = jnp.where(keep, e_sorted * cap + pos_in_group, e_loc * cap)
    gathered = jnp.zeros((e_loc * cap + 1, d), xf.dtype)
    gathered = gathered.at[slot].set(
        jnp.where(keep[:, None], xf[tok_sorted], 0).astype(xf.dtype)
    )
    xe = gathered[:-1].reshape(e_loc, cap, d)

    h = jnp.einsum("ecd,edf->ecf", xe, experts["up"].astype(xf.dtype))
    if cfg.act == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", xe, experts["gate"].astype(xf.dtype))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    ye = jnp.einsum("ecf,efd->ecd", h, experts["down"].astype(xf.dtype))
    ye = jnp.concatenate(
        [ye.reshape(e_loc * cap, d), jnp.zeros((1, d), ye.dtype)], axis=0
    )

    contrib = ye[slot] * jnp.where(keep, w_sorted, 0.0)[:, None].astype(xf.dtype)
    return jnp.zeros((t, d), xf.dtype).at[tok_sorted].add(contrib)


def _route(params, cfg: MoEConfig, xf: jax.Array):
    logits = linear(params["router"], xf).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)
    if cfg.router_scale:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-9)
    return top_w.astype(xf.dtype), top_e


def _moe_local(params, cfg: MoEConfig, x: jax.Array) -> jax.Array:
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    top_w, top_e = _route(params, cfg, xf)
    out = _dispatch_compute_combine(xf, top_w, top_e, params["experts"], cfg, 0)
    return out.reshape(b, s, d)


def _moe_shard_map(params, cfg: MoEConfig, x: jax.Array, mesh) -> jax.Array:
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    has_model = "model" in mesh.axis_names

    def body(router, experts, xl):
        bl, s, d = xl.shape
        xf = xl.reshape(bl * s, d)
        top_w, top_e = _route({"router": router}, cfg, xf)
        if has_model:
            j = jax.lax.axis_index("model")
            e_loc = experts["up"].shape[0]
            e0 = j * e_loc
        else:
            e0 = 0
        out = _dispatch_compute_combine(xf, top_w, top_e, experts, cfg, e0)
        if has_model:
            out = jax.lax.psum(out, "model")
        return out.reshape(bl, s, d)

    espec = P("model") if has_model else P()
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), {k: espec for k in params["experts"]}, P(dp)),
        out_specs=P(dp),
        check_vma=False,
    )(params["router"], params["experts"], x)


def moe_apply(params, static, cfg: MoEConfig, x: jax.Array) -> jax.Array:
    """x: [B, S, D] -> [B, S, D]."""
    from repro.parallel.activations import current_mesh

    mesh = current_mesh()
    b = x.shape[0]
    use_shard_map = (
        mesh is not None
        and b % int(np.prod([mesh.shape[a] for a in ("pod", "data")
                             if a in mesh.axis_names])) == 0
        and cfg.n_experts % mesh.shape.get("model", 1) == 0
    )
    if use_shard_map:
        out = _moe_shard_map(params, cfg, x, mesh)
    else:
        out = _moe_local(params, cfg, x)

    if "shared" in params:
        out = out + mlp_apply(params["shared"], static["shared"], x)
    return out
