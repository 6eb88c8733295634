"""Mesh-sharded embedding tables (model-parallel lookup).

Reference analogue: the distributed lookup table (SURVEY §2.10 row
"Model/embedding sharding") — rows hashed across pservers with
prefetch_op/split_ids/merge_ids (transpiler distribute_lookup_table
path). The parameter-server realization lives in ops/distributed_ops.py
(prefetch / sparse_table_push); THIS module is the collective (TPU-
native) realization: the table is row-sharded over a mesh axis with
jax.sharding, the lookup runs fully on-device, and XLA inserts the
all-reduce over ICI.

Design: shard rows round-robin-by-block over axis `model`
(NamedSharding P("model", None)); each device gathers its local rows
with out-of-range ids masked to zero contribution, and a psum over the
axis assembles full rows — the same math as the reference's
split_ids -> per-shard lookup -> merge_ids, but compiled into one
collective. Gradients reverse through the same path (scatter-add of the
psum cotangent back onto the owning shard), matching the sparse-grad
semantics of the distributed table.
"""

import numpy as np

__all__ = ["shard_table", "sharded_lookup"]


def shard_table(table, mesh, axis="model"):
    """Place a [V, D] table with rows sharded over `axis` (replicated on
    every other mesh axis). V must divide evenly; pad the vocab up like
    the reference's block-sliced tables otherwise."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.shape[axis]
    if table.shape[0] % n != 0:
        raise ValueError(
            "vocab %d not divisible by %s axis size %d — pad the table"
            % (table.shape[0], axis, n))
    return jax.device_put(table, NamedSharding(mesh, P(axis, None)))


def sharded_lookup(table, ids, mesh, axis="model"):
    """Gather rows of a sharded table: [*, D] rows for integer `ids`.

    Runs under shard_map on `axis`: each shard gathers its local rows
    (non-local ids clamp and zero out), then one psum assembles full
    rows. Differentiable — the vjp scatter-adds back onto the owning
    shard only."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    n = mesh.shape[axis]
    V = table.shape[0]
    rows_per = V // n

    def local(tbl, idv):
        # shard index along `axis` (block-sliced rows: shard s owns
        # [s*rows_per, (s+1)*rows_per))
        s = jax.lax.axis_index(axis)
        lo = s * rows_per
        local_idx = idv - lo
        mine = (local_idx >= 0) & (local_idx < rows_per)
        picked = jnp.take(tbl, jnp.clip(local_idx, 0, rows_per - 1),
                          axis=0)
        picked = picked * mine[..., None].astype(picked.dtype)
        return jax.lax.psum(picked, axis)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(*([None] * ids.ndim))),
        out_specs=P(*([None] * ids.ndim), None))(
            table, ids.astype(np.int32))
