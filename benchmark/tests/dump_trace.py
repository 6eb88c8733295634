"""One `--trace 1` run of a cell that also KEEPS what the profiler saw, so
the reduction can be re-read off the chip:

    python3 benchmark/tests/dump_trace.py <cell> <seconds> <out.npz> [seed] [no_reference]

The run is `benchmark/run.py`'s own (same driver, readers and last line); the
events of the profiled sub-window go to `<out.npz>` as `load_events` below
reads them: per device the work, container and asynchronous events of the
reduction (benchmark/xplane.py), the names' vocabulary, and the run's facts
(windows, steps).  `no_reference` replaces the training driver's comparison
with the plain reference by a stub - a run for the TRACE only, made where
the reference's cold compile (minutes, times four chips) buys nothing; its
`correct` then rests on no reference and its line is no result of the cell.
`benchmark/tests/data/dp4_loop_slice.npz` was cut from such a dump
(`cut_slice`).
"""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

KINDS = ("device_ops", "containers", "async_ops")


def save_events(path, trace, facts):
    vocab, arrays = {}, {}
    for kind in KINDS:
        for dev, rows in getattr(trace, kind).items():
            ids = np.array([vocab.setdefault(n, len(vocab))
                            for n, _, _ in rows], np.int32)
            arrays["%s/%d/name" % (kind, dev)] = ids
            arrays["%s/%d/start" % (kind, dev)] = np.array(
                [s for _, s, _ in rows], np.float64)
            arrays["%s/%d/end" % (kind, dev)] = np.array(
                [e for _, _, e in rows], np.float64)
    names = sorted(vocab, key=vocab.get)
    arrays["names"] = np.array(names)
    arrays["facts"] = np.array(json.dumps(facts))
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    np.savez_compressed(path, **arrays)


def load_events(path):
    """(Trace, facts) from a dump.  The Trace is built from the union of the
    dump's work and container events, so `drop_containers` runs again."""
    from benchmark import xplane
    z = np.load(path)
    names = [str(n) for n in z["names"]]
    rows = {k: {} for k in KINDS}
    for key in z.files:
        if not key.endswith("/name"):
            continue
        kind, dev, _ = key.split("/")
        base = "%s/%s/" % (kind, dev)
        rows[kind][int(dev)] = [
            (names[i], float(s), float(e)) for i, s, e in
            zip(z[key], z[base + "start"], z[base + "end"])]
    ops = {d: rows["device_ops"].get(d, []) + rows["containers"].get(d, [])
           for d in set(rows["device_ops"]) | set(rows["containers"])}
    return (xplane.Trace(ops, async_ops=rows["async_ops"]),
            json.loads(str(z["facts"])))


def cut_slice(src, dst, start, end, devices=None):
    """A slice [start, end] (seconds from the profiled window's start) of a
    dump, small enough to keep as a test's fixture: events that overlap the
    slice, times moved so the slice begins at 0."""
    trace, facts = load_events(src)
    w0 = facts["trace_window"][0]
    a, b = w0 + start, w0 + end

    class Cut(object):
        pass
    cut = Cut()
    for kind in KINDS:
        setattr(cut, kind, {
            d: [(n, s - a, e - a) for n, s, e in rows if e > a and s < b]
            for d, rows in getattr(trace, kind).items()
            if devices is None or d in devices})
    save_events(dst, cut, {"slice_of": os.path.basename(src),
                           "window": [0.0, end - start],
                           "cell": facts.get("cell"),
                           "device_kind": facts.get("device_kind")})


def main(argv):
    cell, seconds, out = argv[0], argv[1], argv[2]
    seed = ([a for a in argv[3:] if a.isdigit()]
            or [str(2 ** 31 + 1000003)])[0]
    import benchmark.run as run
    from benchmark import xplane
    kept = {}
    read = xplane.read_trace

    def keep(path):
        kept["trace"] = read(path)
        return kept["trace"]
    xplane.read_trace = keep
    config = run.resolve_cell(run.load_json(run.MANIFEST), cell)[1]
    driver = __import__("benchmark.drivers." + config["driver"],
                        fromlist=["run"])
    drive = driver.run

    def keep_result(ctx):
        kept["ctx"], kept["result"] = ctx, drive(ctx)
        return kept["result"]
    driver.run = keep_result
    if "no_reference" in argv[3:]:
        def stub(ctx, *a, **k):
            ctx.log(phase="reference_check", ok=None,
                    skipped="dump_trace.py no_reference: a run for the "
                            "trace only")
            return True, {}
        driver.check_against_reference = stub
    rc = run.main(["--workload", cell, "--seed", seed, "--seconds", seconds,
                   "--trace", "1"])
    if rc == 0 and "trace" in kept:
        r = kept["result"]["run"]
        facts = {k: r[k] for k in ("chips", "batch", "steps_per_call",
                                   "calls_in_trace", "steps_in_trace",
                                   "call_seconds_in_trace", "calls_window",
                                   "trace_window", "slots") if k in r}
        facts.update(cell=cell, seed=int(seed),
                     device_kind=kept["ctx"].devices[0].device_kind)
        save_events(out, kept["trace"], facts)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
