"""held_pairs_per_expert (layer: kernels) - how loaded a held expert is when
a decode trip reads its weights: the live (token, expert) pairs that STAYED
on this member (`moe_pairs_held`) over the distinct held experts they touched
(`moe_experts_touched`), both attributes of the program's `decode/fetch`
spans of `phase=step` (each summed over the routed layers and the dispatch's
trips), the mean over the measured window's step fetches.  An expert's three
matrices are read once a trip however many rows they multiply, so this is
the rows a read of 3 x d_model x expert_width weights is spread over: a
member of an expert-parallel deployment of m members sees 1/m of the streams
it would there, and this number says how far the cell is from that load.  A
program whose spans carry no `moe_pairs_held` (every one before the counter
existed), or a stack that holds all its experts, gives no reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    if not run["meta"].get("experts_held"):
        return None
    loads = [s["attrs"]["moe_pairs_held"]
             / float(s["attrs"]["moe_experts_touched"])
             for s in sp.named(spans, "decode/fetch", run["window"])
             if s["attrs"].get("phase") == "step"
             and s["attrs"].get("moe_experts_touched")
             and "moe_pairs_held" in s["attrs"]]
    return sum(loads) / len(loads) if loads else None
