"""Operations and bytes the kernels of a hybrid stack NEED, from their shapes
alone (`gqa_attention_roofline`).  A file of its own beside costs.py, which a
PR that adds a configuration may not edit; `costs.roofline_seconds` turns the
pair into the least possible time."""


def gqa_attention_cost(lengths, n_heads, n_kv_heads, head_dim, kv_bytes=4,
                       q_bytes=4, out_bytes=4):
    """One grouped-query `decode_attention` call over a slot table: every
    slot attends one query position, `n_heads` query heads, over its
    `lengths[i]` live cache positions of `n_kv_heads` K/V heads.

    FLOPs: q.k and p.v, 2 * H * Dh multiply-adds each per live position, by
    the QUERY heads (softmax's exp/max/sum are not counted: lower-order).
    Bytes: the live K and V rows read ONCE, by the K/V heads - a group's
    query heads share their K/V head's rows, and a kernel that reads them
    once a query head is charged for it by its time; q read, the output
    written, by the query heads.  Positions past a slot's length, and the
    lanes a table pads its rows with, need not be touched and are not
    counted."""
    live = float(sum(int(n) for n in lengths))
    n_slots = len(lengths)
    flops = 2.0 * 2.0 * live * n_heads * head_dim
    bytes_ = (2.0 * live * n_kv_heads * head_dim * kv_bytes
              + n_slots * n_heads * head_dim * (q_bytes + out_bytes))
    return flops, bytes_
