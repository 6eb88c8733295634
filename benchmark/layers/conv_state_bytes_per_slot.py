"""conv_state_bytes_per_slot (layer: decode phases) - bytes of the SECOND
kind of slot state a hybrid stack keeps, a slot: the `conv_state_bytes`
attribute of the program's `decode/fetch` spans of `phase=step` in the
measured window (the session's conv-state table: conv layers x slots x
(taps - 1) x hidden, fp32) over the lane's slots.  A fixed size whatever a
stream's length, beside K/V rows that grow with it.  A program whose spans
carry no such attribute gives no reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    sizes = [s["attrs"]["conv_state_bytes"]
             for s in sp.named(spans, "decode/fetch", run["window"])
             if s["attrs"].get("phase") == "step"
             and "conv_state_bytes" in s["attrs"]]
    return sizes[-1] / float(run["slots"]) if sizes else None
