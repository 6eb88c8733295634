"""latent_cache_bytes_per_slot (layer: decode phases) - bytes of slot state a
latent-attention stack reserves, a slot: the `latent_cache_bytes` attribute
of the program's `decode/fetch` spans of `phase=step` in the measured window
(the session's one latent table: layers x slots x positions x row lanes as
the table holds them, fp32, held once: there is no V table) over the lane's
slots.  47 MB at 5 layers of 4096 positions, where 128 heads' K and V rows
would be 3.4 GB.  A program whose spans carry no such attribute gives no
reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    sizes = [s["attrs"]["latent_cache_bytes"]
             for s in sp.named(spans, "decode/fetch", run["window"])
             if s["attrs"].get("phase") == "step"
             and "latent_cache_bytes" in s["attrs"]]
    return sizes[-1] / float(run["slots"]) if sizes else None
