"""index_cache_bytes_per_slot (layer: decode phases) - bytes of the SIXTH kind
of slot state, the indexer's cache a stack with sparse_attention layers keeps,
a slot: the `index_cache_bytes` attribute of the program's `decode/fetch` spans
of `phase=step` in the measured window (the session's compressed-key table:
sparse layers x slots x compressed keys (one every `sparse_kernel_stride`
positions) x K/V heads x head size, fp32) over the lane's slots.  Reserved for
`max_seq_len` positions whatever a stream's length, a 32nd of the K/V rows
beside it.  A program whose spans carry no such attribute gives no reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    sizes = [s["attrs"]["index_cache_bytes"]
             for s in sp.named(spans, "decode/fetch", run["window"])
             if s["attrs"].get("phase") == "step"
             and "index_cache_bytes" in s["attrs"]]
    return sizes[-1] / float(run["slots"]) if sizes else None
