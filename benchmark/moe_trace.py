"""What the two routed-FFN readers share: which device operations of a
decode round belong to the routed-expert FFN, and how long they ran.

The device plane names an event by its HLO instruction WITHOUT its
metadata, so a `jax.named_scope` does not reach the text `xplane.Trace`
keeps.  The driver therefore lowers the lane's step executable once after a
traced window and keeps, as `run["scope_ops"]["moe_ffn"]`, the names of its
instructions whose `op_name` lies under the program's `moe_ffn` scope (a
fusion carries the op_name of the operation it was built around).  XLA's own
grouped-matmul kernels replace their metadata (`op_name="ragged-dot-none"`),
so they are found by the configuration's `kernel_trace_match.moe_ffn`
substring of the instruction's name instead.  A program with no such scope
(the parent of the PR that added it) yields no names and no reading."""

import re

from benchmark import spans as sp
from benchmark import xplane

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([A-Za-z0-9_.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_instruction_names(hlo_text, scope, name_match=None):
    """Names of the instructions of an optimized HLO module whose op_name
    holds `/<scope>/`, or whose own name holds `name_match`."""
    names = set()
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        if (op and "/%s/" % scope in op.group(1) + "/") or (
                name_match and name_match in m.group(1)):
            names.add(m.group(1))
    return names


def rounds_in_profile(spans, run):
    """The `serving/decode_step` spans that lie wholly inside the profiled
    sub-window (monotonic clock)."""
    m0, m1 = run["trace_window_monotonic"]
    return [s for s in sp.named(spans, "serving/decode_step", (m0, m1))
            if s["t1"] <= m1]


def scope_seconds(trace, run, rounds, scope):
    """Device seconds of the scope's operations inside `rounds`; None when
    the run names no operation of that scope."""
    names = set(run.get("scope_ops", {}).get(scope, ()))
    if not names or not rounds:
        return None

    def match(text):
        return xplane.short_name(text) in names
    return sum(trace.matching_seconds(trace.from_monotonic(s["t0"]),
                                      trace.from_monotonic(s["t1"]), match)
               for s in rounds)
