"""Operations and bytes a kernel call NEEDS, from its shapes alone.  Kept
with the benchmark: a roofline share is (least possible time) / (measured
time), and the least possible time must not move when the program does."""


def decode_attention_cost(lengths, n_heads, head_dim, kv_bytes=4,
                          q_bytes=4, out_bytes=4):
    """One `decode_attention` call over a slot table: every slot attends
    one query position over its `lengths[i]` live cache positions.

    FLOPs: q.k and p.v, 2 * H * Dh multiply-adds each per live position
    (softmax's exp/max/sum are not counted: lower-order, not MXU work).
    Bytes: the live K and V rows read once, q read, the output written.
    Positions past a slot's length need not be touched, so they are not
    counted — a kernel that streams the whole table is charged for it by
    its time, not credited for it here."""
    live = float(sum(int(n) for n in lengths))
    n_slots = len(lengths)
    flops = 2.0 * 2.0 * live * n_heads * head_dim
    bytes_ = (2.0 * live * n_heads * head_dim * kv_bytes
              + n_slots * n_heads * head_dim * (q_bytes + out_bytes))
    return flops, bytes_


def roofline_seconds(flops, bytes_, peak_flops, peak_bytes_per_s):
    """(least seconds, which bound binds)."""
    t_c, t_m = flops / peak_flops, bytes_ / peak_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
