"""held_overflow_share (layer: kernels) - how often the routing crowds more
pairs onto this member than its grouped matmuls' rows hold: of the (routed
layer, trip) calls of the measured window's decode dispatches, the share
that ran the full-size branch of `moe_ffn`'s `cond` (`moe_cap_overflows`, an
attribute of the program's `decode/fetch` spans of `phase=step`, summed over
the routed layers and the dispatch's trips, over routed layers x the span's
`trips`), in percent.  The compact branch works over `decode.held_cap` rows,
the member's expected share and a margin; a call that keeps more is still
exact and costs what the parent's always did, so this number says whether
the margin fits the cell's router: ~0 where it does, and a reading above 1
says the margin is too tight for this traffic.  A program whose spans carry
no `moe_cap_overflows` (every one before the counter existed), or a stack
that holds all its experts, gives no reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    meta = run["meta"]
    if not meta.get("experts_held"):
        return None
    routed = int(meta["n_layers"]) - int(meta.get("n_dense_layers") or 0)
    over = calls = 0
    for s in sp.named(spans, "decode/fetch", run["window"]):
        a = s["attrs"]
        if a.get("phase") == "step" and "moe_cap_overflows" in a:
            over += int(a["moe_cap_overflows"])
            calls += routed * int(a.get("trips") or 1)
    return 100.0 * over / calls if calls else None
