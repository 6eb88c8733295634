"""One run of one benchmark cell: a new process that loads, warms the cell's
own shapes, measures for --seconds, prints one JSON line last and exits.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's `workloads`; its configuration
(benchmark/configs/<config>.json), traffic mix (benchmark/traffic/<mix>.json),
plain reference (benchmark/reference/<config>.py) and per-layer readers
(benchmark/layers/<metric>.py) are found by name — see benchmark/README.md.
Without a TPU, or with fewer chips than the cell asks for, the run exits 3
and prints no result.  `--trace 0` reports the cell's end-to-end metrics,
`--trace 1` its per-layer metrics from spans and a profiler trace of a few
seconds inside the window.
"""

import time

T_START = time.time()           # set-up is counted from here

import argparse                 # noqa: E402
import importlib                # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
import threading                # noqa: E402
import types                    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# `python3 benchmark/run.py` puts benchmark/ itself first on the path; the
# checkout's root belongs there (for `paddle_tpu` and `benchmark.*`)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# Off-chip rehearsals import this module and rebind these (on-chip-measurement
# guide, section 2); no command-line option or environment variable does.
REQUIRED_PLATFORM = "tpu"
TRACE_SECONDS = 3.0             # length of the profiled sub-window
TRAFFIC_DIR = os.path.join(HERE, "traffic")
LAYERS_DIR = os.path.join(HERE, "layers")
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def log(**fields):
    """An earlier output line (never the last): medians, sample counts,
    set-up phases, what a check measured; `t` = seconds since the start."""
    fields["t"] = round(time.time() - T_START, 3)
    print(json.dumps(fields, default=str), flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def resolve_cell(manifest, workload):
    """(cell, config, traffic, end_to_end metric entries, per_layer metric
    entries) of one `workloads` entry, everything found by name."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit("no workload %r in BENCHMARK.json (has: %s)"
                         % (workload, ", ".join(sorted(cells))))
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    config = load_json(os.path.join(ROOT, files[cell["config"]]))
    traffic = load_json(os.path.join(TRAFFIC_DIR, cell["traffic"] + ".json"))

    def mine(entries):
        return [m for m in entries
                if "workloads" not in m or workload in m["workloads"]]
    return (cell, config, traffic, mine(manifest["end_to_end"]),
            mine(manifest["per_layer"]))


def load_reader(metric_name):
    """benchmark/layers/<metric>.py, loaded by path (a metric's name may
    hold dots)."""
    path = os.path.join(LAYERS_DIR, metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(config):
    return importlib.import_module("benchmark.reference."
                                   + config["reference"])


class CompileWatch(object):
    """Counts what jax lowers or compiles: lowering a jaxpr to MLIR,
    compiling it, or fetching it from the persistent cache each mean a
    shape the warm-up did not cover.  (Tracing alone does not count: eager
    operations re-trace a tiny jaxpr on every call and reuse their
    executable.)"""

    WATCHED = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/")

    def __init__(self):
        self.events = []
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name.startswith(self.WATCHED):
            self.events.append((time.monotonic(), name, secs))

    def between(self, t0, t1):
        return [(n, s) for t, n, s in self.events if t0 <= t <= t1]


class MemoryWatch(object):
    """`memory_peak_bytes`: the most one chip held at one moment of the
    MEASURED WINDOW.  The allocator's two lifetime peaks cannot give that:
    `peak_bytes_in_use` (arrays) includes whatever set-up held for its
    comparison with the reference, `peak_bytes_reserved` (a running
    program's temporaries: 9.2 GB of a ResNet-50 step whose arrays are
    0.84 GB, my chip run, PR 23) need not fall at the same moment, and
    neither can be reset.  So a thread reads every chip's `bytes_in_use +
    bytes_reserved` - one moment's reading - four times a second between
    the driver's `start()` and `stop()`, and the largest reading stands.
    A peak shorter than a reading's interval can be missed: one
    saturated-decode run in six read 12.7 GB where the others read 13.9 (a
    third copy of half the cache table, alive for a part of some rounds).
    Reading oftener is no cure: at twenty readings a second every waking
    of this thread took the interpreter from the decode loop, a round grew
    from 169.5 to 171-173 ms and `tokens_per_s` fell by 2% (my chip runs,
    PR 23).  At four a second no effect shows."""

    INTERVAL_S = 0.25

    def __init__(self, devices):
        self.devices = list(devices)
        self.peak, self.samples = 0, 0
        self.first = self.last = None       # time.monotonic() of readings
        self.longest_gap_s, self.longest_gap_end = 0.0, None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def read(self):
        for d in self.devices:
            st = d.memory_stats() or {}
            self.peak = max(self.peak, int(st.get("bytes_in_use", 0))
                            + int(st.get("bytes_reserved", 0)))
        now = time.monotonic()
        if self.last is None:
            self.first = now
        elif now - self.last > self.longest_gap_s:
            self.longest_gap_s, self.longest_gap_end = now - self.last, now
        self.last = now
        self.samples += 1

    def stall(self, t0):
        """What says whether the process PAUSED inside the window: the
        readings taken, those a window of that length takes when nothing
        holds this thread up (one an interval, and the two at its ends),
        the longest time between two readings and when it ended, in
        seconds from the window's start `t0` (time.monotonic()): the
        generator's process keeps the same of its own
        (`loadgen.Ticker`).  A low run has so far been a pause of the
        whole process (122 and 171 readings where a clean 45 s window
        takes 178-183, PERF.md section 7)."""
        length = (self.last - self.first) if self.samples > 1 else 0.0
        return {"readings": self.samples,
                "readings_if_clean": int(length / self.INTERVAL_S) + 2,
                "longest_gap_s": self.longest_gap_s,
                "longest_gap_at_s": (self.longest_gap_end - t0
                                     if self.longest_gap_end else None)}

    def _loop(self):
        while not self._stop.wait(self.INTERVAL_S):
            self.read()

    def start(self):
        self.read()
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join()
        self.read()


def device_record(devices, memory):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": memory.peak}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = load_json(MANIFEST)
    cell, config, traffic, e2e, per_layer = resolve_cell(manifest,
                                                         args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != REQUIRED_PLATFORM:
        print("benchmark: jax found %s devices, no %s - no measurement "
              "without the chip" % (devices[0].platform, REQUIRED_PLATFORM),
              file=sys.stderr)
        return 3
    if len(devices) < cell["chips"]:
        print("benchmark: cell %s needs %d chips, jax found %d"
              % (cell["name"], cell["chips"], len(devices)), file=sys.stderr)
        return 3

    from paddle_tpu import compile_cache
    jax_cache = compile_cache.ensure_jax_cache()
    cache_dir = compile_cache.checkout_cache_dir("benchmark")
    trace_dir = os.path.join(cache_dir, "trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    log(phase="start", cell=cell["name"], seed=args.seed,
        seconds=args.seconds, trace=args.trace, jax=jax.__version__,
        device_kind=devices[0].device_kind, devices=len(devices),
        jax_cache_dir=jax_cache)

    # what a driver gets
    ctx = types.SimpleNamespace(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace),
        trace_seconds=min(TRACE_SECONDS, args.seconds / 2.0),
        trace_dir=trace_dir, cache_dir=cache_dir, devices=devices,
        chips=cell["chips"], platform=REQUIRED_PLATFORM,
        compiles=CompileWatch(), log=log, reference=load_reference(config),
        memory=MemoryWatch(devices[:cell["chips"]]))
    driver = importlib.import_module("benchmark.drivers." + config["driver"])
    result = driver.run(ctx)

    setup_s = result["window_start_wall"] - T_START
    in_window = ctx.compiles.between(*result["window_monotonic"])
    correct = bool(result["correct"]) and not in_window
    if in_window:
        log(phase="compiles_in_window", count=len(in_window),
            first=in_window[:5])
    device = device_record(devices, ctx.memory)
    log(phase="memory", window_peak_bytes=ctx.memory.peak,
        lifetime=devices[0].memory_stats(),
        **ctx.memory.stall(result["window_monotonic"][0]))

    if not args.trace:
        values = dict(result["end_to_end"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e}
        line = {"correct": correct, "attempted": result["attempted"],
                "failed": result["failed"], "metrics": metrics,
                "device": device}
    else:
        t_read = time.time()
        trace, (w0, w1) = result["trace"], result["trace_window"]
        metrics = {}
        for m in per_layer:
            value = load_reader(m["name"])(result["spans"], trace,
                                           result["run"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = trace.busy_mean(w0, w1)
        device["window_s"] = w1 - w0
        line = {"correct": correct, "attempted": result["attempted"],
                "failed": result["failed"], "metrics": metrics,
                "device": device,
                "breakdown": trace.breakdown(
                    w0, w1, result["run"].get("host_spans", ()))}
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(phase="reduced", seconds=time.time() - t_read,
            device_events=sum(len(v) for v in trace.device_ops.values()))
    log(phase="done", setup_s=setup_s, wall_s=time.time() - T_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
