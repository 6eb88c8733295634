"""Operations and bytes the kernels of a latent-attention stack NEED, from
their shapes alone (`mla_attention_roofline`, `held_experts_ffn_roofline`).
A file of its own beside costs.py, which a PR that adds a configuration may
not edit; `costs.roofline_seconds` turns a pair into the least possible
time."""

from benchmark import costs_moe


def latent_attention_cost(lengths, n_heads, row_lanes, value_lanes,
                          row_bytes=4, q_bytes=4, out_bytes=4):
    """One `latent_decode_attention` call over a slot table: every slot
    attends one query position, `n_heads` absorbed queries of `row_lanes`
    lanes, over its `lengths[i]` live latent rows, whose first `value_lanes`
    lanes are the values.

    FLOPs: q . row over `row_lanes` and p . row over `value_lanes`, a
    multiply-add each, per head and live position: 2 * (row_lanes +
    value_lanes) * n_heads (278,528 at 128 heads of 576 over 512; softmax's
    exp/max/sum are lower-order and not counted).  Bytes: the live rows read
    ONCE for all heads, at the table's byte width - a kernel that reads a
    row once a head, or its value lanes a second time, is charged for it by
    its time; q read and the output written, by the heads.  Positions past a
    slot's length, and the lanes a table pads its rows with, need not be
    touched and are not counted."""
    live = float(sum(int(n) for n in lengths))
    n_slots = len(lengths)
    flops = 2.0 * live * n_heads * (row_lanes + value_lanes)
    bytes_ = (live * row_lanes * row_bytes
              + n_slots * n_heads * (row_lanes * q_bytes
                                     + value_lanes * out_bytes))
    return flops, bytes_


def held_experts_ffn_cost(tokens, held_touched, d_model, expert_width,
                          n_experts, weight_bytes, router_bytes=4):
    """One routed layer's FFN on a member that HOLDS a run of the experts
    (`experts_held`), over `tokens` rows of which `held_touched` distinct
    held experts received at least one: `costs_moe.moe_ffn_cost` with the
    weights at `weight_bytes` a value, the router's matrix (all `n_experts`
    outputs, kept in float32 and read at "highest") at `router_bytes`, and
    the (token, expert) pairs counted as ONE a touched expert: the run keeps
    no count of the pairs that stayed here, each touched expert has at least
    one, and fewer FLOPs make the least time no larger (memory binds by far:
    94 MB of weights an expert against a few rows)."""
    per_token = held_touched / float(tokens) if tokens else 0.0
    flops, bytes_ = costs_moe.moe_ffn_cost(
        tokens, held_touched, d_model, expert_width, n_experts, per_token,
        weight_bytes=weight_bytes)
    return flops, bytes_ + d_model * n_experts * (router_bytes - weight_bytes)
