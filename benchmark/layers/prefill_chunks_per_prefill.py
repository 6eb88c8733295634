"""prefill_chunks_per_prefill (layer: decode phases) - the chunks a prefill
runs its bucket in, a mean over the `serving/prefill_compute` spans of the
measured window that carry `chunks` (bucket / `prefill_chunk`: 4, 8 or 12 at
this cell's buckets; a chunk wholly past the prompt is skipped in the
executable and still counted here).  With it `prefill_share_of_lane` says
what a chunk costs.  A program whose spans carry no such attribute (a stack
that prefills whole) gives no reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    chunks = [int(s["attrs"]["chunks"])
              for s in sp.named(spans, "serving/prefill_compute",
                                run["window"])
              if "chunks" in s["attrs"]]
    return sum(chunks) / float(len(chunks)) if chunks else None
