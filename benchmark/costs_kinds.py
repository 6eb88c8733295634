"""Operations and bytes the decode kernel NEEDS over a stack whose attending
layers are of two KINDS with their OWN geometries: K/V heads by kind, key
and value heads of different sizes (rows by leaf), a sink a query head
(`kinds_attention_roofline`).  `costs_window.mixed_attention_cost` knows ONE
geometry for both kinds and K as wide as V.  A file of its own beside
costs.py, costs_hybrid.py and costs_window.py, which a PR that adds a
configuration may not edit; `costs.roofline_seconds` turns the pair into the
least possible time."""


def kind_attention_cost(lengths, layers, n_heads, kv_heads, k_dim, v_dim,
                        sink=False, kv_bytes=4, q_bytes=4, out_bytes=4):
    """`layers` `decode_attention` calls of ONE kind over a slot table:
    every slot attends one query position, `n_heads` query heads (keys of
    `k_dim`, values of `v_dim`), over its `lengths[i]` live rows of
    `kv_heads` K/V heads.

    FLOPs: q.k is 2 * H * k_dim a live position, p.v 2 * H * v_dim, by the
    QUERY heads (softmax's exp/max/sum are not counted: lower-order, and a
    sink is one more term of the sum).  Bytes: the live K rows (kv_heads *
    k_dim lanes) and V rows (kv_heads * v_dim lanes) read ONCE, by the K/V
    heads, each at its OWN width; q read at k_dim and the result written at
    v_dim, by the query heads; `sink`: 4 * H bytes of sinks a call.  Rows
    past a slot's length need not be touched and are not counted."""
    live = float(sum(int(n) for n in lengths))
    n_slots = len(lengths)
    flops = 2.0 * live * n_heads * (k_dim + v_dim)
    bytes_ = (live * kv_heads * (k_dim + v_dim) * kv_bytes
              + n_slots * n_heads * (k_dim * q_bytes + v_dim * out_bytes)
              + (4.0 * n_heads if sink else 0.0))
    return flops * layers, bytes_ * layers


def kinds_attention_cost(lengths, n_heads, window, full, ring, sink=False):
    """One decode TRIP's calls over both kinds of table, `n_heads` query
    heads a call.  `full` = (layers, kv_heads, k_dim, v_dim) of the layers
    that attend over a slot's `lengths[i]` live rows; `ring` = the same four
    of the window layers, which need read min(lengths[i], window) rows of a
    ring whatever the stream's length, with a `sink` a head or without."""
    flops, bytes_ = kind_attention_cost(lengths, full[0], n_heads, *full[1:])
    f, b = kind_attention_cost([min(int(n), window) for n in lengths],
                               ring[0], n_heads, *ring[1:], sink=sink)
    return flops + f, bytes_ + b
