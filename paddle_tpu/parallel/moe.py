"""Expert parallelism — Mixture-of-Experts dispatch over a mesh axis.

No reference analogue (SURVEY.md §2.10: expert parallelism absent in the
2018 codebase); TPU-first per the task charter, completing the
parallelism matrix alongside ring attention (cp), Ulysses (sp), pipeline
(pp), and the mesh-sharded ParallelExecutor (dp/tp).

Design (the standard TPU MoE recipe, scaling-book style): experts shard
one-per-group over the `expert` mesh axis. Tokens route top-1 by a
learned gate; dispatch is a capacity-bounded one-hot einsum to
[E, C, D] slots, an all_to_all moves each expert's slots onto its
device, the expert FFN runs as one batched matmul pair, and a second
all_to_all + combine einsum returns outputs to token order, scaled by
the gate probability. Static shapes throughout: overflow beyond
capacity drops (standard top-1 semantics), masked tokens contribute
zero.
"""

import numpy as np

__all__ = ["moe_ffn", "moe_ffn_sharded", "top1_dispatch"]


def top1_dispatch(gate_logits, num_experts, capacity):
    """Top-1 routing tensors from [T, E] gate logits.

    Returns (dispatch [T, E, C] one-hot, combine [T, E, C] prob-weighted,
    probs [T, E]). Position within an expert's capacity is the token's
    rank among that expert's tokens; tokens past capacity drop."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(gate_logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                 # [T]
    # rank bookkeeping in int32: a bf16 cumsum of ones saturates past 256
    # and collides capacity slots
    onehot_i = jax.nn.one_hot(expert, num_experts,
                              dtype=jnp.int32)          # [T, E]
    pos = jnp.cumsum(onehot_i, axis=0) * onehot_i - onehot_i
    keep = ((pos < capacity) & (onehot_i > 0))
    pos_oh = jax.nn.one_hot(pos, capacity, dtype=gate_logits.dtype)
    dispatch = keep[..., None].astype(gate_logits.dtype) * pos_oh
    onehot = onehot_i.astype(gate_logits.dtype)
    gate_p = jnp.sum(probs * onehot, axis=-1, keepdims=True)  # [T, 1]
    combine = dispatch * gate_p[..., None]
    return dispatch, combine, probs


def moe_ffn(x, gate_w, w_in, w_out, axis_name, capacity_factor=1.25):
    """Per-shard body (inside shard_map over the `expert` axis).

    x: token-sharded [T_loc, D]; gate_w [D, E] replicated;
    w_in [E_loc, D, F], w_out [E_loc, F, D] expert-sharded (E_loc =
    E / n). Returns [T_loc, D]."""
    import jax
    import jax.numpy as jnp

    n = jax.lax.psum(1, axis_name)
    T_loc, D = x.shape
    E_loc = w_in.shape[0]
    E = E_loc * n
    capacity = int(np.ceil(capacity_factor * T_loc / E)) or 1

    dispatch, combine, _ = top1_dispatch(x @ gate_w, E, capacity)
    # gather slots: [T, E, C] x [T, D] -> [E, C, D]
    slots = jnp.einsum("tec,td->ecd", dispatch, x)
    # all_to_all (tiled=False removes split_axis and inserts the
    # received-from axis at concat_axis): [n, E_loc, C, D] block-major
    # -> device d holds its experts' slots from every source shard as
    # [E_loc, n, C, D]
    slots = slots.reshape(n, E_loc, capacity, D)
    slots = jax.lax.all_to_all(slots, axis_name, split_axis=0,
                               concat_axis=1, tiled=False)
    slots = slots.reshape(E_loc, n * capacity, D)
    # expert FFN: batched matmuls on the MXU
    h = jax.nn.relu(jnp.einsum("ecd,edf->ecf", slots, w_in))
    y = jnp.einsum("ecf,efd->ecd", h, w_out)
    # return trip: [E_loc, n, C, D] -> send source-shard s its block ->
    # [n, E_loc, C, D] where axis 0 is the expert-block (device) index,
    # i.e. expert-major [E, C, D] after reshape
    y = y.reshape(E_loc, n, capacity, D)
    y = jax.lax.all_to_all(y, axis_name, split_axis=1, concat_axis=0,
                           tiled=False)
    y = y.reshape(E, capacity, D)
    return jnp.einsum("tec,ecd->td", combine, y)


def moe_ffn_sharded(x, gate_w, w_in, w_out, mesh, axis_name="expert",
                    capacity_factor=1.25):
    """Global entry: x [T, D] token-sharded over `axis_name`; w_in/w_out
    [E, D, F]/[E, F, D] expert-sharded; gate replicated. One shard_map
    over the mesh — XLA lowers the two all_to_alls onto ICI."""
    import jax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    fn = shard_map(
        lambda xs, gw, wi, wo: moe_ffn(xs, gw, wi, wo, axis_name,
                                       capacity_factor),
        mesh=mesh,
        in_specs=(P(axis_name), P(), P(axis_name), P(axis_name)),
        out_specs=P(axis_name))
    return fn(x, gate_w, w_in, w_out)
