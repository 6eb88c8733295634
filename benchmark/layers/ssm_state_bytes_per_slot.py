"""ssm_state_bytes_per_slot (layer: decode phases) - bytes of the THIRD kind
of slot state a stack with state-space mixers keeps, a slot: the
`ssm_state_bytes` attribute of the program's `decode/fetch` spans of
`phase=step` in the measured window (the session's scanned-state table:
attention+ssm layers x slots x heads x head size x state, fp32) over the
lane's slots.  A fixed size whatever a stream's length, read and rewritten
whole by every token, beside K/V rows that grow with it.  A program whose
spans carry no such attribute gives no reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    sizes = [s["attrs"]["ssm_state_bytes"]
             for s in sp.named(spans, "decode/fetch", run["window"])
             if s["attrs"].get("phase") == "step"
             and "ssm_state_bytes" in s["attrs"]]
    return sizes[-1] / float(run["slots"]) if sizes else None
