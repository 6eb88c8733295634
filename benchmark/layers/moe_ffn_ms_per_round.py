"""moe_ffn_ms_per_round (layer: kernels) - device time of the routed-expert
FFN (router, sort, the three grouped expert matmuls, the weighted sum: the
operations under the program's `moe_ffn` scope, all layers) per decode
round, over the rounds that lie inside the profiled sub-window, in ms.
How the operations are found: benchmark/moe_trace.py."""

from benchmark import moe_trace


def read(spans, trace, run):
    rounds = moe_trace.rounds_in_profile(spans, run)
    busy = moe_trace.scope_seconds(trace, run, rounds, "moe_ffn")
    if busy is None or busy <= 0.0:
        return None
    return 1e3 * busy / len(rounds)
