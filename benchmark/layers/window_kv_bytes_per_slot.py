"""window_kv_bytes_per_slot (layer: decode phases) - bytes of the SECOND kind
of K/V slot state a stack with window layers keeps, a slot: the
`window_kv_bytes` attribute of the program's `decode/fetch` spans of
`phase=step` in the measured window (the session's K and V rings: window
layers x slots x sliding_window rows x K/V heads x head size, fp32) over the
lane's slots.  A fixed size whatever a stream's length, beside the full
layers' rows that grow with it (`full_kv_bytes` on the same spans is what
those reserve).  A program whose spans carry no such attribute gives no
reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    sizes = [s["attrs"]["window_kv_bytes"]
             for s in sp.named(spans, "decode/fetch", run["window"])
             if s["attrs"].get("phase") == "step"
             and "window_kv_bytes" in s["attrs"]]
    return sizes[-1] / float(run["slots"]) if sizes else None
