"""Rehearsal of a whole cell at a tiny size off the chip (on-chip-measurement
guide, section 2): the same run.py, drivers, generator, readers and trace
reduction, on a temp copy of the manifest whose configuration file is
shrunk, with `run.REQUIRED_PLATFORM` rebound to "cpu" HERE — run.py itself
has no option for it.  Nothing such a run prints is a device number.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python3 benchmark/tests/rehearse.py <cell> <trace 0|1> [seconds]
"""

import contextlib
import io
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = {
    "resnet50_imagenet": lambda c: (
        c["builder_args"].update(class_dim=10),
        c.update(image_hw=32, reference_sample=4, amp=False)),
    "gpt2_small": lambda c: (
        c["model"].update(vocab_size=97, d_model=32, n_heads=4, n_layers=2,
                          max_seq_len=128,
                          prefill_buckets=[16, 32, 64, 128]),
        c["deployment"].update(decode_slots=4),
        c.update(reference_check={"prompt_tokens": [5, 20, 40],
                                  "steps": 4})),
}
TINY_TRAFFIC = {
    "feed_b256": lambda m: m.update(batch_per_chip=4),
    "dp4_loop_b1024": lambda m: m.update(batch_per_chip=4, steps_per_call=2),
    "decode_saturated": lambda m: (
        m.update(requests=32),
        m["prompt_tokens"].update(min=4, max=14),
        m["output_tokens"].update(value=24)),
}


# An open-loop cell at tiny size, added the way a later PR adds one: a
# traffic file and manifest entries, no edit.  The manifest has no open-loop
# cell today (PERF.md section 7), but the generator's open loop and the
# driver's tails are part of the yardstick, which a later PR cannot extend;
# this keeps them walked.
OPEN_TINY_MIX = {
    "loop": "open", "rate_per_s": 6.0, "drain_s": 20,
    "prompt_tokens": {"kind": "lognormal", "median": 24, "sigma": 0.8,
                      "min": 4, "max": 100},
    "output_tokens": {"kind": "lognormal", "median": 10, "sigma": 0.6,
                      "min": 2, "max": 20},
    "why": "a rehearsal"}


def add_open_cell(manifest):
    manifest["workloads"].append(
        {"name": "gpt2s_open_tiny", "config": "gpt2_small",
         "traffic": "open_tiny", "chips": 1, "why": "a rehearsal"})
    for name in ("ttft_p95_ms", "itl_p95_ms"):
        manifest["end_to_end"].append(
            {"name": name, "unit": "ms", "better": "lower", "bound": 0.1,
             "source": "host_clock", "workloads": ["gpt2s_open_tiny"]})


def read_trace_cpu(path):
    """The CPU backend's ops reach a trace through the host tracer alone
    (tracewin.HOST_TRACER_LEVEL 2, rebound below): the events of the host
    plane that carry an `hlo_op` stat stand in as device 0, one pseudo
    module per `bench_anchor` op, so a rehearsal walks the whole of the
    readers' control flow.  Only a rehearsal reads a trace this way; the
    benchmark's own `xplane.read_trace` knows the device planes alone."""
    from jax.profiler import ProfileData
    from benchmark import xplane
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    ops = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if "XLA" not in line.name:
                continue
            for e in line.events:
                if e.name.startswith(("ThreadpoolListener", "end: ")):
                    continue
                st = dict(e.stats)
                if "hlo_op" in st:
                    s = e.start_ns * 1e-9
                    ops.append((e.name, s, s + e.duration_ns * 1e-9,
                                str(st.get("hlo_module", ""))))
    return xplane.Trace(
        {0: [r[:3] for r in ops]},
        [(xplane.ANCHOR, s, e) for n, s, e, mod in ops
         if xplane.ANCHOR in mod])


def rehearse(cell_name, trace, seconds=5.0, seed=2 ** 31 + 777, tiny=True,
             platform="cpu", patch=None, extra_traffic=None):
    """Run one cell in this process on a temp copy of the manifest; returns
    (exit code, parsed last line, all lines).  `tiny` shrinks every
    configuration and mix (the CPU rehearsal); `patch(manifest)` edits the
    temp copy first and `extra_traffic` = {mix name: mix} adds traffic
    files to it (a cell added by entries and files)."""
    import benchmark.run as run
    from benchmark import tracewin, xplane
    tmp = tempfile.mkdtemp(prefix="bench_rehearsal_")
    saved = (run.MANIFEST, run.TRAFFIC_DIR, run.REQUIRED_PLATFORM)
    saved_level, saved_reader = tracewin.HOST_TRACER_LEVEL, xplane.read_trace
    try:
        man = run.load_json(run.MANIFEST)
        if patch is not None:
            patch(man)
        os.makedirs(os.path.join(tmp, "traffic"))
        for c in man["configs"]:
            cfg = run.load_json(os.path.join(run.ROOT, c["file"]))
            if tiny:
                TINY[c["name"]](cfg)
            path = os.path.join(tmp, c["name"] + ".json")
            with open(path, "w") as f:
                json.dump(cfg, f)
            c["file"] = os.path.relpath(path, run.ROOT)
        extra_traffic = extra_traffic or {}
        for w in man["workloads"]:
            if w["traffic"] in extra_traffic:
                mix = extra_traffic[w["traffic"]]
            else:
                mix = run.load_json(os.path.join(run.TRAFFIC_DIR,
                                                 w["traffic"] + ".json"))
                if tiny:
                    TINY_TRAFFIC[w["traffic"]](mix)
            with open(os.path.join(tmp, "traffic",
                                   w["traffic"] + ".json"), "w") as f:
                json.dump(mix, f)
        with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
            json.dump(man, f)
        run.MANIFEST = os.path.join(tmp, "BENCHMARK.json")
        run.TRAFFIC_DIR = os.path.join(tmp, "traffic")
        run.REQUIRED_PLATFORM = platform
        if platform == "cpu":
            tracewin.HOST_TRACER_LEVEL = 2
            xplane.read_trace = read_trace_cpu
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", cell_name, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)])
        lines = out.getvalue().strip().splitlines()
        return rc, (json.loads(lines[-1]) if lines else None), lines
    finally:
        run.MANIFEST, run.TRAFFIC_DIR, run.REQUIRED_PLATFORM = saved
        tracewin.HOST_TRACER_LEVEL = saved_level
        xplane.read_trace = saved_reader
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    rc, last, lines = rehearse(sys.argv[1], int(sys.argv[2]),
                               float(sys.argv[3]) if len(sys.argv) > 3
                               else 5.0)
    print("\n".join(lines))
    sys.exit(rc)
