"""GQA / MQA / full / sliding-window attention with KV caches.

Three execution regimes, all pure XLA (the Pallas flash kernel in
``repro.kernels`` is the TPU drop-in; the CPU dry-run lowers this path):

  * full     — einsum attention for short sequences (train_4k);
  * chunked  — lax.scan over KV chunks with online softmax for long
               sequences (prefill_32k): O(S * chunk) score memory;
  * decode   — single-token query against a (possibly sequence-sharded)
               KV cache, with optional sliding-window slicing so SWA decode
               reads O(window) not O(S).

Head padding: q heads are padded to a multiple of the TP degree
(``repro.parallel.sharding.padded_heads``); padded heads have zero in/out
projection weights, so they are numerically inert.  GQA grouping uses the
reshape path when padded_q %% kv == 0, otherwise a kv-repeat fallback
(phi3's 10 kv heads).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import apply_rope, linear, linear_init, rope_frequencies
from repro.parallel.sharding import padded_heads

__all__ = ["AttnConfig", "attention_init", "attention_apply", "init_kv_cache"]

_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    qkv_bias: bool = False
    causal: bool = True
    window: int | None = None  # sliding window (h2o-danube)
    rope_theta: float | None = 10000.0  # None -> no RoPE (whisper)
    model_shards: int = 16
    chunk: int = 1024  # kv chunk for the online-softmax path
    full_attn_max_seq: int = 8192  # einsum path below this
    # decode against a sequence-sharded KV cache:
    #  'gather' — GSPMD resolves (all-gathers cache chunks): baseline.
    #  'flash'  — shard_map flash-decode: each 'model' shard scores its
    #             local cache chunk, log-sum-exp combine via psum; wire
    #             bytes drop from O(cache) to O(B*H*D).  §Perf hillclimb.
    decode_strategy: str = "gather"

    @property
    def hq_pad(self) -> int:
        return padded_heads(self.n_heads, self.model_shards)

    @property
    def grouped(self) -> bool:
        return self.hq_pad % self.n_kv_heads == 0


def attention_init(key, cfg: AttnConfig, param_dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, dh = cfg.d_model, cfg.d_head
    hq, hkv = cfg.hq_pad, cfg.n_kv_heads
    params, specs = {}, {}
    params["wq"], specs["wq"] = linear_init(
        kq, d, hq * dh, "embed", "heads", bias=cfg.qkv_bias,
        param_dtype=param_dtype,
    )
    if cfg.hq_pad != cfg.n_heads:  # zero the padded head columns
        pad = (cfg.hq_pad - cfg.n_heads) * dh
        w = params["wq"]["w"][:, : cfg.n_heads * dh]
        params["wq"]["w"] = jnp.concatenate(
            [w, jnp.zeros((d, pad), param_dtype)], axis=1
        )
    kv_axis = "kv_heads" if (hkv * dh) % cfg.model_shards == 0 else None
    params["wk"], specs["wk"] = linear_init(
        kk, d, hkv * dh, "embed", kv_axis, bias=cfg.qkv_bias,
        param_dtype=param_dtype,
    )
    params["wv"], specs["wv"] = linear_init(
        kv, d, hkv * dh, "embed", kv_axis, bias=cfg.qkv_bias,
        param_dtype=param_dtype,
    )
    params["wo"], specs["wo"] = linear_init(
        ko, hq * dh, d, "heads", "embed", param_dtype=param_dtype,
        scale=(hq * dh) ** -0.5,
    )
    if cfg.hq_pad != cfg.n_heads:  # zero the padded head rows
        pad = (cfg.hq_pad - cfg.n_heads) * dh
        w = params["wo"]["w"][: cfg.n_heads * dh]
        params["wo"]["w"] = jnp.concatenate(
            [w, jnp.zeros((pad, d), param_dtype)], axis=0
        )
    return params, specs


def init_kv_cache(
    cfg: AttnConfig, batch: int, max_seq: int, dtype=jnp.bfloat16
):
    shape = (batch, max_seq, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _expand_kv(cfg: AttnConfig, q: jax.Array, k: jax.Array, v: jax.Array):
    """Align kv head count with q heads.  q: [B,S,Hq,D]; k/v: [B,T,Hkv,D].
    Returns q,k,v as [B,H,S,D] with H = hq_pad."""
    b, s, hq, dh = q.shape
    t = k.shape[1]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if cfg.grouped:
        rep = hq // cfg.n_kv_heads
    else:  # phi3-style: repeat kv to match q heads
        rep = -(-hq // cfg.n_kv_heads)
    kt = jnp.repeat(kt, rep, axis=1)[:, :hq]
    vt = jnp.repeat(vt, rep, axis=1)[:, :hq]
    return qt, kt, vt


def _mask(
    qpos: jax.Array, kpos: jax.Array, causal: bool, window: int | None,
    kv_len: jax.Array | None,
) -> jax.Array:
    qq = qpos[..., :, None]
    kk = kpos[..., None, :]
    m = jnp.ones(jnp.broadcast_shapes(qq.shape, kk.shape), bool)
    if causal:
        m &= qq >= kk
    if window is not None:
        m &= kk > qq - window
    if kv_len is not None:
        kv = (
            kv_len[..., None, None]
            if getattr(kv_len, "ndim", 0)
            else kv_len
        )
        m &= kk < kv
    return m


def _expand_mask(m: jax.Array) -> jax.Array:
    """Broadcast a mask to score rank 4: [S,T] -> [1,1,S,T] (shared across
    batch) or [B,S,T] -> [B,1,S,T] (per-row positions / cache lengths)."""
    return m[None, None] if m.ndim == 2 else m[:, None]


def _full_attention(q, k, v, qpos, kpos, causal, window, kv_len):
    """q,k,v: [B,H,S,D] / [B,H,T,D]."""
    dh = q.shape[-1]
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * (dh ** -0.5)
    m = _mask(qpos, kpos, causal, window, kv_len)  # [Sq, Tk] / [B, Sq, Tk]
    s = jnp.where(_expand_mask(m), s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _chunked_attention(q, k, v, qpos, kpos, causal, window, kv_len, chunk):
    """Online-softmax scan over KV chunks.  q/k: [B,H,S,D], v: [B,H,T,Dv]
    (Dv may differ — MLA has 192-dim keys and 128-dim values)."""
    b, h, sq, dh = q.shape
    t = k.shape[2]
    dv = v.shape[-1]
    n_chunks = -(-t // chunk)
    pad = n_chunks * chunk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kpos = jnp.pad(kpos, (0, pad), constant_values=2**30)
    kc = k.reshape(b, h, n_chunks, chunk, dh).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, h, n_chunks, chunk, dv).transpose(2, 0, 1, 3, 4)
    pc = kpos.reshape(n_chunks, chunk)
    qf = q.astype(jnp.float32)

    def step(carry, xs):
        m_prev, l_prev, acc = carry
        kb, vb, pb = xs
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb.astype(jnp.float32)) * (
            dh ** -0.5
        )
        msk = _mask(qpos, pb, causal, window, kv_len)
        s = jnp.where(_expand_mask(msk), s, _NEG)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + p.sum(-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32)
        )
        return (m_new, l_new, acc), None

    init = (
        jnp.full((b, h, sq, 1), _NEG, jnp.float32),
        jnp.zeros((b, h, sq, 1), jnp.float32),
        jnp.zeros((b, h, sq, dv), jnp.float32),
    )
    (m_f, l_f, acc), _ = jax.lax.scan(step, init, (kc, vc, pc))
    return (acc / jnp.maximum(l_f, 1e-30)).astype(q.dtype)


def _flash_decode_sharded(
    cfg: AttnConfig,
    q: jax.Array,  # [B, Hq, 1, D]
    k: jax.Array,  # [B, T, Hkv, D]  (T sequence-sharded over 'model')
    v: jax.Array,  # [B, T, Hkv, D]
    kv_len: jax.Array,  # scalar valid length
    mesh,
) -> jax.Array:
    """Flash-decode over a sequence-sharded cache (shard_map).

    Each 'model' shard scores all heads against its local cache chunk and
    the partial softmaxes merge with a log-sum-exp reduction: pmax of the
    running max, psum of the rescaled denominators and weighted values.
    Replaces the O(cache-bytes) all-gather the GSPMD baseline emits with
    O(B*H*D) combine traffic."""
    from jax.sharding import PartitionSpec as P

    b, hq, _, dh = q.shape
    t = k.shape[1]
    scale = dh ** -0.5
    n_shards = mesh.shape.get("model", 1)
    t_loc = t // n_shards
    # batch stays sharded over the DP axes; only heads are gathered (tiny)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    batch_ok = b % max(
        1, int(np.prod([mesh.shape[a] for a in dp]))
    ) == 0
    bspec = dp if (dp and batch_ok) else None

    def body(qb, kb, vb, kv_len_b):
        j = jax.lax.axis_index("model") if "model" in mesh.axis_names else 0
        kpos = j * t_loc + jnp.arange(t_loc)  # [T_loc]
        kh = kb.transpose(0, 2, 1, 3)  # [B, Hkv, T_loc, D]
        vh = vb.transpose(0, 2, 1, 3)
        rep = (hq // cfg.n_kv_heads) if cfg.grouped else -(-hq // cfg.n_kv_heads)
        kh = jnp.repeat(kh, rep, axis=1)[:, :hq]
        vh = jnp.repeat(vh, rep, axis=1)[:, :hq]
        s = jnp.einsum(
            "bhqd,bhtd->bhqt", qb.astype(jnp.float32),
            kh.astype(jnp.float32),
        ) * scale  # [B, Hq, 1, T_loc]
        mask = kpos[None, None, None, :] < kv_len_b
        s = jnp.where(mask, s, _NEG)
        m_loc = jnp.max(s, axis=-1, keepdims=True)
        m_glob = jax.lax.pmax(m_loc, "model")
        p = jnp.exp(s - m_glob)
        l_loc = p.sum(-1, keepdims=True)
        o_loc = jnp.einsum("bhqt,bhtd->bhqd", p, vh.astype(jnp.float32))
        l_glob = jax.lax.psum(l_loc, "model")
        o_glob = jax.lax.psum(o_loc, "model")
        return (o_glob / jnp.maximum(l_glob, 1e-30)).astype(qb.dtype)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(bspec), P(bspec, "model"), P(bspec, "model"), P()),
        out_specs=P(bspec),
        check_vma=False,
    )(q, k, v, kv_len)


def attention_apply(
    params,
    cfg: AttnConfig,
    x: jax.Array,  # [B, S, D]
    positions: jax.Array,  # [S] (shared) or [B, S] (per-row) positions
    memory: jax.Array | None = None,  # cross-attention source [B, T, D]
    cache: dict | None = None,  # kv cache to read/update
    cache_pos: jax.Array | None = None,  # scalar or [B] write offset
    cache_len: jax.Array | None = None,  # scalar or [B] valid length
) -> tuple[jax.Array, dict | None]:
    """Returns (output [B,S,D], updated cache).

    ``positions`` / ``cache_pos`` / ``cache_len`` accept either the shared
    (scalar / [S]) form — every batch row at the same decode position — or
    the per-row ([B,S] / [B]) form used by continuous batching, where each
    slot advances independently.  Per-row mode keeps the mask-based paths
    (the SWA slice and sharded flash-decode shortcuts need a shared scalar
    position and are skipped)."""
    b, s, d = x.shape
    dh, hq = cfg.d_head, cfg.hq_pad
    per_row = (
        cache_pos is not None and getattr(cache_pos, "ndim", 0) > 0
    ) or (cache_len is not None and getattr(cache_len, "ndim", 0) > 0)

    q = linear(params["wq"], x).reshape(b, s, hq, dh)
    src = memory if memory is not None else x
    t_src = src.shape[1]
    k = linear(params["wk"], src).reshape(b, t_src, cfg.n_kv_heads, dh)
    v = linear(params["wv"], src).reshape(b, t_src, cfg.n_kv_heads, dh)

    if cfg.rope_theta is not None and memory is None:
        freqs = rope_frequencies(dh, cfg.rope_theta)
        pos_b = positions if positions.ndim == 2 else positions[None, :]
        q = apply_rope(q, pos_b, freqs)
        k = apply_rope(k, pos_b, freqs)

    new_cache = cache
    if cache is not None and memory is None:
        pos0 = cache_pos if cache_pos is not None else jnp.int32(0)
        if getattr(pos0, "ndim", 0):
            rows = jnp.arange(b)[:, None]
            cols = pos0[:, None] + jnp.arange(s)[None, :]
            new_cache = {
                "k": cache["k"].at[rows, cols].set(
                    k.astype(cache["k"].dtype)
                ),
                "v": cache["v"].at[rows, cols].set(
                    v.astype(cache["v"].dtype)
                ),
            }
        else:
            new_cache = {
                "k": jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, pos0, 0, 0)
                ),
                "v": jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, pos0, 0, 0)
                ),
            }
        k_all, v_all = new_cache["k"], new_cache["v"]
        t = k_all.shape[1]
        kpos = jnp.arange(t)
        kv_len = cache_len
        # SWA decode: only the last `window` positions can score — slice
        # them out so decode work is O(window), not O(max_seq)
        if cfg.window is not None and s == 1 and t > cfg.window and not per_row:
            w = cfg.window
            start = jnp.clip(
                (cache_len if cache_len is not None else t) - w, 0, t - w
            )
            k_all = jax.lax.dynamic_slice(k_all, (0, start, 0, 0),
                                          (b, w, cfg.n_kv_heads, dh))
            v_all = jax.lax.dynamic_slice(v_all, (0, start, 0, 0),
                                          (b, w, cfg.n_kv_heads, dh))
            kpos = start + jnp.arange(w)
        k, v = k_all, v_all
    else:
        kpos = jnp.arange(t_src) if memory is not None else positions
        kv_len = None

    # flash-decode fast path: sequence-sharded cache, shard_map combine
    if (
        cfg.decode_strategy == "flash"
        and s == 1
        and cache is not None
        and memory is None
        and cfg.window is None
        and not per_row
    ):
        from repro.parallel.activations import current_mesh

        mesh = current_mesh()
        if mesh is not None and k.shape[1] % mesh.shape.get("model", 1) == 0:
            qh = q.transpose(0, 2, 1, 3)  # [B, Hq, 1, D]
            kv_len_c = kv_len if kv_len is not None else jnp.int32(k.shape[1])
            out = _flash_decode_sharded(cfg, qh, k, v, kv_len_c, mesh)
            out = out.transpose(0, 2, 1, 3).reshape(b, s, hq * dh)
            return linear(params["wo"], out.astype(x.dtype)), new_cache

    qh, kh, vh = _expand_kv(cfg, q, k, v)
    causal = cfg.causal and memory is None
    t = kh.shape[2]
    if max(s, t) <= cfg.full_attn_max_seq:
        out = _full_attention(qh, kh, vh, positions, kpos, causal,
                              cfg.window, kv_len)
    else:
        out = _chunked_attention(qh, kh, vh, positions, kpos, causal,
                                 cfg.window, kv_len, cfg.chunk)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, hq * dh)
    return linear(params["wo"], out.astype(x.dtype)), new_cache
