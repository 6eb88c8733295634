"""GPipe-style pipeline parallelism over the `pipe` mesh axis.

No reference analogue (SURVEY.md §2.10: pipeline parallelism absent in the
2018 codebase); TPU-first per the task charter. Stage parameters are stacked
on a leading [n_stages, ...] axis and sharded over `pipe`; microbatch
activations flow stage-to-stage via `lax.ppermute` over ICI in a
(M + n - 1)-tick schedule (the classic GPipe fill/drain bubble). Everything
runs inside one shard_map, so XLA overlaps each tick's send with the next
tick's compute.
"""

import functools

import numpy as np

__all__ = ["pipeline_apply", "pipeline_sharded"]


def pipeline_apply(stage_fn, stage_params, microbatches, axis_name):
    """Per-shard body (inside shard_map over `axis_name` of size n).

    stage_fn(params, x) -> y: one pipeline stage; activations keep shape.
    stage_params: this device's stage parameters (leading [1, ...] shard of
      the stacked [n, ...] pytree) — squeezed before use.
    microbatches: [M, mb, ...] all microbatch inputs (replicated).
    Returns [M, mb, ...] outputs (valid on every device after the final
    broadcast from the last stage).
    """
    import jax
    import jax.numpy as jnp

    n = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    params = jax.tree_util.tree_map(lambda p: p[0], stage_params)
    M = microbatches.shape[0]
    ticks = M + n - 1
    fwd_perm = [(i, i + 1) for i in range(n - 1)]

    x_shape = microbatches.shape[1:]

    def tick(carry, t):
        buf, outs = carry
        # stage 0 ingests microbatch t (zeros past the fill phase)
        mb_idx = jnp.minimum(t, M - 1)
        fresh = jax.lax.dynamic_index_in_dim(microbatches, mb_idx, axis=0,
                                             keepdims=False)
        inp = jnp.where(rank == 0, fresh, buf)
        y = stage_fn(params, inp)
        # last stage emits microbatch t - (n - 1) at tick t
        out_idx = t - (n - 1)
        valid = (rank == n - 1) & (out_idx >= 0)
        upd = jax.lax.dynamic_update_index_in_dim(
            outs, y, jnp.maximum(out_idx, 0), axis=0)
        outs = jnp.where(valid, upd, outs)
        # send activations downstream (device i -> i+1)
        buf_next = jax.lax.ppermute(y, axis_name, fwd_perm)
        return (buf_next, outs), None

    buf0 = jnp.zeros(x_shape, microbatches.dtype)
    outs0 = jnp.zeros((M,) + x_shape, microbatches.dtype)
    # carries become device-varying after the first tick (ppermute/rank
    # branches); mark the initial values as varying so scan types match
    buf0 = jax.lax.pcast(buf0, axis_name, to="varying")
    outs0 = jax.lax.pcast(outs0, axis_name, to="varying")
    (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(ticks))
    # broadcast results from the last stage to every device so the caller
    # sees a replicated output (psum of the masked buffer = broadcast)
    outs = jax.lax.psum(
        jnp.where(rank == n - 1, outs, jnp.zeros_like(outs)), axis_name)
    return outs


def pipeline_sharded(stage_fn, stacked_params, microbatches, mesh,
                     axis_name="pipe"):
    """stacked_params: pytree with leading [n_stages, ...] axis;
    microbatches [M, mb, ...] replicated. Returns [M, mb, ...]."""
    import jax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    param_spec = jax.tree_util.tree_map(
        lambda _: P(axis_name), stacked_params)
    fn = shard_map(
        functools.partial(pipeline_apply, stage_fn, axis_name=axis_name),
        mesh=mesh,
        in_specs=(param_spec, P()), out_specs=P())
    return fn(stacked_params, microbatches)
