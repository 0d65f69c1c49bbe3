"""GPipe-style pipeline parallelism over a homogeneous layer stack.

Opt-in (DESIGN §6): the assigned production mesh uses DP x TP, but at
1000+-node scale a pipeline axis bounds the TP collective diameter.  This
module implements the classic shard_map pipeline: each 'stage' shard holds
a contiguous slice of the stacked layer params; microbatches flow through
a rotating buffer moved by ``collective_permute``; the schedule runs
``n_micro + n_stages - 1`` ticks (GPipe fill/drain bubble, whose cost the
caller amortises by choosing n_micro >> n_stages).

``pipeline_apply(layer_fn, stacked_params, x_micro, mesh, axis)`` is
numerically identical to folding ``layer_fn`` over the full stack (tested
in tests/test_pipeline.py on a fake 4-device mesh).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(
    layer_fn,
    stacked_params,
    x_micro: jax.Array,  # [n_micro, B_micro, ...] microbatched input
    mesh: Mesh,
    axis: str = "stage",
):
    """Run ``layer_fn`` over a stage-sharded layer stack.

    Args:
      layer_fn: (params_slice, x) -> x, applied per layer.
      stacked_params: pytree with leading layer dim L (L %% n_stages == 0).
      x_micro: microbatched inputs; n_micro >= 1.
      mesh: mesh containing ``axis``.
      axis: pipeline axis name.

    Returns [n_micro, B_micro, ...] outputs after all L layers.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]

    def stage_fn(params_local, x):
        # apply this stage's layers (L/n_stages of them) sequentially
        def body(carry, p):
            return layer_fn(p, carry), None
        y, _ = jax.lax.scan(body, x, params_local)
        return y

    def pipe(params_local, xs):
        # params_local: [L/n_stages, ...]; xs: [n_micro_local...] — the
        # microbatch stream is fed entirely on stage 0 and read on the
        # last stage; all stages execute the same program.
        stage = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs[0])  # inter-stage rotating buffer
        outs = jnp.zeros_like(xs)

        def tick(state, t):
            buf, outs = state
            # stage 0 ingests microbatch t (when valid); others take buf
            fresh = xs[jnp.clip(t, 0, n_micro - 1)]
            inp = jnp.where(stage == 0, fresh, buf)
            out = stage_fn(params_local, inp)
            # last stage records its result for microbatch t - (S-1)
            slot = t - (n_stages - 1)
            valid = (stage == n_stages - 1) & (slot >= 0)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.maximum(slot, 0), 0
                ),
                lambda o: o,
                outs,
            )
            # rotate: stage s -> stage s+1 (ring; the wraparound value
            # into stage 0 is ignored — stage 0 always takes `fresh`)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(out, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(
            tick, (buf, outs), jnp.arange(n_ticks)
        )
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis,
        )
        return outs

    in_specs = (P(axis), P())
    return jax.shard_map(
        pipe, mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False,
    )(stacked_params, x_micro)
