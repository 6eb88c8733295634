"""Operations and bytes the kernels of a stack with state-space mixers NEED,
from their shapes alone (`ssm_update_roofline`).  A file of its own beside
costs.py, which a PR that adds a configuration may not edit;
`costs.roofline_seconds` turns a pair into the least possible time.  The
attention of such a stack is grouped-query: `costs_hybrid.gqa_attention_cost`."""


def ssm_update_cost(n_slots, heads, head_dim, state, groups, conv_kernel,
                    state_bytes=4, act_bytes=4):
    """One decode step of ONE state-space (Mamba-2 / SSD) layer over
    `n_slots` live slots: S <- exp(dt A) S + dt x (outer) B; y = S . C + D x,
    behind a causal depthwise conv of `conv_kernel` taps whose window rolls.

    The count is of the WORK, the same whatever implements the update.
    FLOPs: a state value is decayed (1 multiply), added to (the outer
    product's multiply and the add: 2; dt x is shared by the row) and read
    out (C's multiply and the sum's add: 2): about 6 with exp(dt A)'s
    broadcast, `heads * head_dim * state` values a slot; the conv
    2 * taps a channel.  Bytes: every live slot's state read ONCE and
    written ONCE at the state's dtype (an update that reads it a second time
    to write it back is charged for that by its time); the conv window read
    and written ((taps - 1) rows of heads * head_dim + 2 * groups * state
    channels); the new input row in and y out.  Memory binds by far: 24 B
    against 6 FLOPs a state value."""
    values = heads * head_dim * state
    channels = heads * head_dim + 2 * groups * state
    flops = n_slots * (6.0 * values + 2.0 * conv_kernel * channels)
    bytes_ = n_slots * (2.0 * values * state_bytes
                        + 2.0 * (conv_kernel - 1) * channels * act_bytes
                        + (channels + heads * head_dim) * act_bytes)
    return flops, bytes_
