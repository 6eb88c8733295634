"""Operations and bytes the kernels of a stack of block-sparse and linear
attention layers NEED, from their shapes alone (`sparse_attention_roofline`,
`lightning_update_roofline`).  A file of its own beside costs.py, which a PR
that adds a configuration may not edit; `costs.roofline_seconds` turns a pair
into the least possible time."""


def selected_rows(seen, block, topk):
    """Positions a slot that attends under `seen` positions reads once its
    blocks are selected: all of them while no more than `topk` blocks are in
    sight, else `topk` blocks of which the slot's own last one (always
    selected: it lies in the forced window) is the only partial one."""
    seen = int(seen)
    in_sight = -(-seen // block)
    if in_sight <= topk:
        return seen
    return (topk - 1) * block + seen - (in_sight - 1) * block


def sparse_attention_cost(lengths, layers, n_heads, n_kv_heads, head_dim,
                          block, topk, kv_bytes=4, act_bytes=4):
    """One decode TRIP's stage 2 of `layers` sparse_attention layers, every
    live slot attending one query position under `lengths[i]` positions:
    the SELECTED blocks' K and V rows read once by the K/V heads (a slot's
    `selected_rows`), q in and the result out by the query heads; scores
    and values 2 FLOPs a product each.  The count is of the WORK, the same
    whatever implements the kernel: a kernel that stages a whole block for
    the slot's partial last one, or whole rows, is charged for that by its
    time.  Stage 1 (the compressed keys' scores, the top-k) is not in it: it
    has a reader of its own.  Memory binds."""
    rows = sum(selected_rows(n, block, topk) for n in lengths)
    n = len(lengths)
    flops = layers * 4.0 * n_heads * head_dim * rows
    bytes_ = layers * (2.0 * rows * n_kv_heads * head_dim * kv_bytes
                       + 2.0 * n * n_heads * head_dim * act_bytes)
    return flops, bytes_


def linear_update_cost(n_slots, heads, head_dim, state, state_bytes=4,
                       act_bytes=4):
    """One decode step of ONE linear_attention layer over `n_slots` live
    slots: S <- decay S + v (outer) k; o = S . q.  A state value is decayed,
    added to and read out (about 6 FLOPs, `costs_ssm.ssm_update_cost`'s
    count without a conv); every live slot's state read ONCE and written
    ONCE, q, k and v in and o out.  Memory binds by far."""
    values = heads * head_dim * state
    flops = n_slots * 6.0 * values
    bytes_ = n_slots * (2.0 * values * state_bytes
                        + 2.0 * heads * (head_dim + state) * act_bytes)
    return flops, bytes_
