"""full_kv_bytes_per_slot (layer: decode phases) - bytes of the FIRST kind of
K/V slot state a stack with window layers keeps, a slot: the `full_kv_bytes`
attribute of the program's `decode/fetch` spans of `phase=step` in the
measured window (the session's K and V tables of the layers that attend
over every position: full layers x slots x max_seq_len rows x (a K row's
lanes + a V row's), fp32) over the lane's slots.  What a slot RESERVES for a
stream of any length, beside the rings' fixed size
(`window_kv_bytes_per_slot`); with K/V heads by kind and K rows wider than V
rows it is no multiple of the rings' row.  A program whose spans carry no
such attribute gives no reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    sizes = [s["attrs"]["full_kv_bytes"]
             for s in sp.named(spans, "decode/fetch", run["window"])
             if s["attrs"].get("phase") == "step"
             and "full_kv_bytes" in s["attrs"]]
    return sizes[-1] / float(run["slots"]) if sizes else None
