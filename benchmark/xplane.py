"""Reduction of a jax profiler trace (`.xplane.pb`) to the numbers the
benchmark reports: device busy time, idle share, time per operation,
exposed collective time, idle gaps attributed to host spans.

Two layers, so the arithmetic can be checked on intervals written by hand
and the reading on a small recorded trace (benchmark/tests/data/):

  read_trace(path)   -> Trace: per-device lists of (name, start_s, end_s)
  drop_containers    -> a line's events without its while/conditional/call
  Trace.busy / op_seconds / exposed_seconds / idle_gaps / breakdown
                     -> the numbers

Times inside a Trace are seconds on the profiler's clock.  The benchmark
runs a tiny `bench_anchor` computation on the device at both ends of the
profiled window and notes the host's wall and monotonic clocks when each
returns (benchmark/tracewin.py), so program spans (wall clock) and generator
stamps (monotonic) can be placed on the trace's axis with no host tracing.
"""

import glob
import os
import re

from benchmark import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")
ANCHOR = "jit_bench_anchor"


def short_name(text):
    """`%fusion.5 = f32[...] fusion(...), kind=...` -> `fusion.5`: the
    device plane names an event by its whole HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def drop_containers(rows):
    """The events of one `XLA Ops` line that are WORK: a `while`, a
    `conditional` or a `call` is on that line too, as one event from its
    first inner operation to its last (a `lax.fori_loop` of ten training
    steps is a single `while` of a second), and counting it would make the
    device busy for as long as the loop lasts and put "another operation"
    over every collective inside it.  An event that wholly contains a later
    event of its line is such a container and is left out, whatever its
    name; what it costs beyond its inner operations (the loop's own
    bookkeeping between them) then shows as idle time, which it is.
    `rows`: [(name, start_s, end_s)]; returns (work, containers), both
    sorted by start."""
    rows = sorted(rows, key=lambda r: (r[1], -r[2]))
    is_container = [False] * len(rows)
    stack = []                          # indices of the events still open
    for i, (_, s, e) in enumerate(rows):
        while stack and rows[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= rows[stack[-1]][2] and e > s:
            is_container[stack[-1]] = True
        stack.append(i)
    work = [r for r, c in zip(rows, is_container) if not c]
    return work, [r for r, c in zip(rows, is_container) if c]


class Trace(object):
    """device_ops: {device id: [(name, start_s, end_s), ...]}: the `XLA
    Ops` line, the core's own stream of operations, WITHOUT its containers
    (`drop_containers`; they are kept in `containers`), sorted by start;
    names shortened, the whole instruction text kept in `full` for matching
    a kernel.  async_ops: the same of the `Async XLA Ops` line, where an
    asynchronous operation (a copy, an all-reduce the scheduler split into
    start and done) lasts from its start to its done while the core runs
    other operations; only `exposed_seconds` reads it.  modules: [(name,
    start_s, end_s)] of the `XLA Modules` lines (whole program executions);
    anchor: (profile_s, wall_s, monotonic_s) once `set_anchor` has tied the
    clocks.

    The clocks are tied through the benchmark's own `bench_anchor` modules
    (see `set_anchor`), to within a dispatch latency - well under a
    millisecond in the recorded v5e trace: fine for windows of seconds and
    gaps of milliseconds, not for ordering single events."""

    def __init__(self, device_ops, modules=(), async_ops=None):
        self.modules = sorted(modules, key=lambda e: e[1])
        self.full = {}
        self.device_ops, self.containers, self.async_ops = {}, {}, {}

        def short(rows):
            out = []
            for n, s, e in rows:
                sn = short_name(n)
                self.full.setdefault(sn, n)
                out.append((sn, s, e))
            return out
        for d, v in device_ops.items():
            self.device_ops[d], self.containers[d] = drop_containers(short(v))
        for d, v in (async_ops or {}).items():
            self.async_ops[d] = sorted(short(v), key=lambda r: r[1])
        self.anchor = None

    def text(self, name):
        return self.full.get(name, name)

    def set_anchor(self, host_stamps):
        """Tie the clocks: `host_stamps` = [(wall_s, monotonic_s)] noted as
        each `bench_anchor` call returned, in order; the trace holds one
        module event per call.  The offset is taken from the pair with the
        least slack (the call that waited least behind other device work is
        the tightest)."""
        ends = [e for n, s, e in self.modules if n.startswith(ANCHOR)]
        if not ends or len(ends) != len(host_stamps):
            raise RuntimeError(
                "%d %s modules in the trace for %d anchor calls"
                % (len(ends), ANCHOR, len(host_stamps)))
        # every pair says: monotonic = profile + (mono - end) - latency,
        # latency >= 0; the smallest (mono - end) is the closest to truth
        best = min(range(len(ends)),
                   key=lambda i: host_stamps[i][1] - ends[i])
        self.anchor = (ends[best], host_stamps[best][0],
                       host_stamps[best][1])

    # -- clocks -------------------------------------------------------------

    def from_wall(self, wall_s):
        return self.anchor[0] + (wall_s - self.anchor[1])

    def from_monotonic(self, mono_s):
        return self.anchor[0] + (mono_s - self.anchor[2])

    # -- arithmetic ---------------------------------------------------------

    def busy(self, start, end):
        """{device: seconds in [start, end] in which an operation ran}."""
        out = {}
        for dev, ops in self.device_ops.items():
            out[dev] = stats.union_seconds(
                [(max(s, start), min(e, end)) for _, s, e in ops
                 if e > start and s < end])
        return out

    def busy_mean(self, start, end):
        b = self.busy(start, end)
        return sum(b.values()) / len(b) if b else 0.0

    def op_seconds(self, start, end):
        """{name: seconds summed over its events, averaged over devices}.
        Containers are out (`drop_containers`), so the sum over the names
        is the busy time, but for events that overlap in part."""
        tot = {}
        for ops in self.device_ops.values():
            for n, s, e in ops:
                if e <= start or s >= end:
                    continue
                tot[n] = tot.get(n, 0.0) + (min(e, end) - max(s, start))
        k = max(len(self.device_ops), 1)
        return {n: v / k for n, v in tot.items()}

    def matching_seconds(self, start, end, match):
        """Union (per device, then averaged) of the intervals of the events
        whose instruction text `match` accepts — a kernel's device time."""
        ok = {n: bool(match(self.text(n))) for n in self.full}
        per = []
        for ops in self.device_ops.values():
            per.append(stats.union_seconds(
                [(max(s, start), min(e, end)) for n, s, e in ops
                 if e > start and s < end and ok[n]]))
        return sum(per) / len(per) if per else 0.0

    def exposed_seconds(self, start, end, match=None):
        """Seconds (averaged over devices) in which an event accepted by
        `match` (default: a collective) was running on a device - in the
        core's stream or on the asynchronous line - and the core ran NO
        other operation: the time the collective was not hidden.  For an
        all-reduce split into start and done that is the two halves
        themselves (the done waits for the exchange) and any hole between
        the operations scheduled in between."""
        match = match or (lambda n: bool(COLLECTIVE.match(n)))
        per = []
        for dev, ops in self.device_ops.items():
            coll = stats.merge_intervals(
                [(max(s, start), min(e, end))
                 for n, s, e in list(ops) + self.async_ops.get(dev, [])
                 if e > start and s < end and match(n)])
            other = stats.merge_intervals(
                [(max(s, start), min(e, end)) for n, s, e in ops
                 if e > start and s < end and not match(n)])
            covered = 0.0
            for cs, ce in coll:
                covered += stats.union_seconds(
                    [(max(s, cs), min(e, ce)) for s, e in other
                     if e > cs and s < ce])
            per.append(sum(e - s for s, e in coll) - covered)
        return sum(per) / len(per) if per else 0.0

    def idle_gaps(self, start, end):
        """[(start_s, end_s)] in which the first device ran nothing, longest
        first."""
        if not self.device_ops:
            return []
        dev = min(self.device_ops)
        merged = stats.merge_intervals(
            [(s, e) for _, s, e in self.device_ops[dev]])
        return sorted(stats.gaps(merged, start, end),
                      key=lambda g: g[0] - g[1])

    def breakdown(self, start, end, spans=(), top=10):
        """The `breakdown` object of a --trace 1 line: the device ops that
        took most time, and the longest idle gaps named by the host span
        (name, start_s, end_s on this trace's clock) that covers most of
        each.  Gaps are summed by name."""
        ops = sorted(self.op_seconds(start, end).items(),
                     key=lambda kv: -kv[1])[:top]
        by_name = {}
        for gs, ge in self.idle_gaps(start, end):
            best, best_cov = "(no host span)", 0.0
            for name, ss, se in spans:
                cov = min(ge, se) - max(gs, ss)
                if cov > best_cov:
                    best, best_cov = name, cov
            by_name[best] = by_name.get(best, 0.0) + (ge - gs)
        gaps = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, v] for n, v in ops],
                "idle_gaps": [[n, v] for n, v in gaps]}


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def read_trace(path):
    """A Trace from an .xplane.pb file (or the directory the profiler wrote
    it under): the `XLA Ops`, `Async XLA Ops` and `XLA Modules` lines of
    every `/device:TPU:n` plane."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    device_ops, async_ops, modules = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                continue
            rows = [(e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events]
            if line.name == OPS_LINE:
                device_ops[int(m.group(1))] = rows
            elif line.name == ASYNC_LINE:
                async_ops[int(m.group(1))] = rows
            else:
                modules.extend(rows)
    return Trace(device_ops, modules, async_ops)
