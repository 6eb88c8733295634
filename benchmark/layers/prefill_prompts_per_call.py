"""prefill_prompts_per_call (layer: scheduler) - the prompts ONE prefill call
takes, at the mean: the measured window's `serving/prefill_compute` spans (one
a request) over the distinct prefill calls they rode.  Every span carries
`prompts`, the members of its call (PR 55: the same-bucket prompts of an
admission run as one call over `tokens [P, B]`, `DecodeBatcher._prefill_calls`;
1 for a prompt that ran alone), so a call of P members shows as P spans that
each count 1 / P of a call, and a call the window's edge cuts counts its part.
A lane that groups nothing (a stack that prefills in chunks) reads exactly 1.0.
A program whose spans carry no such attribute, as every one before that PR,
gives no reading."""

from benchmark import spans as sp


def read(spans, trace, run):
    sizes = [int(s["attrs"]["prompts"])
             for s in sp.named(spans, "serving/prefill_compute",
                               run["window"])
             if "prompts" in s["attrs"]]
    return len(sizes) / sum(1.0 / n for n in sizes) if sizes else None
