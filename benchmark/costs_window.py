"""Operations and bytes the decode kernel NEEDS over a stack whose attending
layers are of two kinds, window and full, from their shapes alone
(`mixed_attention_roofline`).  A file of its own beside costs.py and
costs_hybrid.py, which a PR that adds a configuration may not edit;
`costs.roofline_seconds` turns the pair into the least possible time."""

from benchmark import costs_hybrid


def mixed_attention_cost(lengths, full_layers, window_layers, window,
                         n_heads, n_kv_heads, head_dim):
    """One decode TRIP's `decode_attention` calls over both kinds of K/V
    table, every slot attending one query position: `full_layers` calls
    over the slots' `lengths[i]` live rows, `window_layers` calls over
    min(lengths[i], window) rows of a ring - a window layer need read no
    more whatever a stream's length, and a kernel that stages a whole ring
    for a stream shorter than it is charged for that by its time.  Each
    call is `costs_hybrid.gqa_attention_cost` (rows read once by the K/V
    heads, q in and the result out by the query heads)."""
    flops = bytes_ = 0.0
    for layers, seen in ((full_layers, lengths),
                         (window_layers, [min(int(n), window)
                                          for n in lengths])):
        f, b = costs_hybrid.gqa_attention_cost(seen, n_heads, n_kv_heads,
                                               head_dim)
        flops, bytes_ = flops + f * layers, bytes_ + b * layers
    return flops, bytes_
