"""trace_top — the slowest recent requests/steps, decomposed by stage.

The operator's answer to "where did the p99 go": reads the obs span
ring (OBSERVABILITY.md) — over the serving `trace` RPC verb for a
running server, or in-process — groups serving spans by trace_id and
training spans by step, and prints the slowest roots with their stage
breakdown (queue_wait / coalesce / lane_wait / dispatch / compute /
scatter for a request; prefetch_wait / dispatch / drain / ckpt for a
train step, with the executor's own feed / dispatch / fetch phases of
that step beside them).  `--trace_id` resolves ONE reply-visible id into
its span tree; `--json` dumps raw.

`--capture` runs one traced serving run +
one traced train step in-process under the jax profiler, exports the
MERGED chrome trace (obs spans + device timeline,
profiler.export_chrome_tracing) to `--out_dir`, and prints a one-line
JSON summary (archive path, request stage breakdown, step breakdown).

Usage: python tools/trace_top.py HOST:PORT [-n 10] [--train] [--json]
       python tools/trace_top.py HOST:PORT --trace_id <id>
       python tools/trace_top.py --capture [--model resnet]
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# request root + stage names (batcher emission order)
ROOT = "serving/request"
SERVING_STAGES = ("serving/queue_wait", "serving/coalesce",
                  "serving/lane_wait", "serving/dispatch",
                  "serving/compute", "serving/scatter")
TRAIN_SPANS = ("train/prefetch_wait", "train/dispatch", "train/step",
               "train/drain", "train/ckpt")
# the phases of one Executor.run / ParallelExecutor.run (children of
# `executor/run`; they carry the step of the train span around them, or
# the executor's own count): host casts + the feed's upload, lookups + the
# jitted call, the wait for the step + the fetches' copy to the host
EXECUTOR_PHASES = ("executor/feed", "executor/dispatch", "executor/fetch")


def group_requests(spans):
    """Serving spans -> one record per trace_id: root duration + stage
    milliseconds.  Records sort slowest-first."""
    by_trace = {}
    for s in spans:
        tid = s.get("trace_id")
        if tid is None or s.get("kind") != "serving":
            continue
        rec = by_trace.setdefault(
            tid, {"trace_id": tid, "total_ms": None, "ts": s.get("ts"),
                  "stages": {}, "attrs": {}})
        if s["name"] == ROOT:
            rec["total_ms"] = s["dur_ms"]
            rec["ts"] = s.get("ts")
            rec["attrs"] = dict(s.get("attrs") or {})
        elif s["name"] in SERVING_STAGES:
            rec["stages"][s["name"].split("/", 1)[1]] = s["dur_ms"]
    out = [r for r in by_trace.values() if r["total_ms"] is not None]
    out.sort(key=lambda r: -r["total_ms"])
    return out


def group_steps(spans):
    """Train spans -> one record per step id with the per-step
    breakdown (prefetch_wait / dispatch / drain / ckpt ms) and, under
    `executor`, the feed / dispatch / fetch ms of the step's executor
    calls.  The executor's phases lie INSIDE the train spans, so they do
    not add to the total; a step run by a bare `Executor.run` loop (no
    train span) totals its `executor/run`.  Spans without a step attr
    (e.g. prefetch_wait) aggregate into step=None totals shown as the
    'unattributed' row."""
    by_step = {}
    for s in spans:
        name = s["name"]
        if s.get("kind") != "train" or not (
                name in TRAIN_SPANS or name in EXECUTOR_PHASES
                or name == "executor/run"):
            continue
        step = (s.get("attrs") or {}).get("step")
        rec = by_step.setdefault(step, {"step": step, "total_ms": 0.0,
                                        "stages": {}, "executor": {},
                                        "run_ms": 0.0})
        key = name.split("/", 1)[1]
        if name in TRAIN_SPANS:
            rec["stages"][key] = rec["stages"].get(key, 0.0) + s["dur_ms"]
            rec["total_ms"] += s["dur_ms"]
        elif name in EXECUTOR_PHASES:
            rec["executor"][key] = rec["executor"].get(key, 0.0) \
                + s["dur_ms"]
        else:
            rec["run_ms"] += s["dur_ms"]
    out = list(by_step.values())
    for rec in out:
        if not rec["stages"]:
            rec["total_ms"] = rec["run_ms"]
        del rec["run_ms"]
    out.sort(key=lambda r: -r["total_ms"])
    return out


def render_requests(recs, limit):
    lines = ["%-18s %9s  %s" % ("TRACE", "TOTALms", "stage breakdown")]
    for r in recs[:limit]:
        stages = "  ".join(
            "%s=%.1f" % (n.split("/", 1)[1], r["stages"].get(
                n.split("/", 1)[1], 0.0))
            for n in SERVING_STAGES)
        extra = ""
        a = r.get("attrs") or {}
        if a.get("model"):
            extra = "  model=%s replica=%s fill=%s" % (
                a.get("model"), a.get("replica"), a.get("batch_fill"))
        lines.append("%-18s %9.2f  %s%s"
                     % (r["trace_id"], r["total_ms"], stages, extra))
    return "\n".join(lines)


def render_steps(recs, limit):
    lines = ["%-8s %9s  %-32s  %s" % ("STEP", "TOTALms",
                                      "executor feed|dispatch|fetch",
                                      "breakdown")]
    for r in recs[:limit]:
        stages = "  ".join("%s=%.1f" % (k, v)
                           for k, v in sorted(r["stages"].items()))
        exe = r.get("executor") or {}
        phases = " | ".join(
            "%.1f" % exe[n.split("/", 1)[1]]
            if n.split("/", 1)[1] in exe else "-"
            for n in EXECUTOR_PHASES)
        step = "-" if r["step"] is None else r["step"]
        lines.append("%-8s %9.2f  %-32s  %s"
                     % (step, r["total_ms"], phases, stages))
    return "\n".join(lines)


def render_tree(spans):
    """One trace's spans, oldest first, root last — the span tree a
    reply-visible trace_id resolves to."""
    lines = []
    for s in sorted(spans, key=lambda s: (s["name"] == ROOT, s["ts"])):
        lines.append("%-22s %9.3f ms  %s"
                     % (s["name"], s["dur_ms"],
                        " ".join("%s=%s" % kv
                                 for kv in sorted(
                                     (s.get("attrs") or {}).items()))))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# --capture
# ---------------------------------------------------------------------------

def capture(model_kind=None, out_dir=None, steps=3):
    """One traced serving run + one traced train step under the jax
    profiler; archives the merged chrome trace.  Returns the summary
    dict (also printed as a JSON line by main)."""
    import tempfile

    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.obs import tracing as obs_tracing
    from paddle_tpu.serving import InferenceServer, ServingClient
    from bench_serving import build_model

    import jax
    on_tpu = jax.default_backend() == "tpu"
    if model_kind is None:
        model_kind = "resnet" if on_tpu else "fc"
    out_dir = out_dir or os.path.join(tempfile.mkdtemp(prefix="obs_"),
                                      "trace")
    os.makedirs(out_dir, exist_ok=True)
    obs_tracing.clear()
    fluid.profiler.start_profiler(output_dir=out_dir)

    # --- one traced serving run -------------------------------------
    md = os.path.join(tempfile.mkdtemp(prefix="obs_model_"), model_kind)
    md, feed_name, shape, dtype = build_model(model_kind, md)
    srv = InferenceServer(endpoint="127.0.0.1:0").start()
    try:
        srv.registry.load_model("m", md, buckets=[1, 4])
        cli = ServingClient(srv.endpoint)
        x = np.random.RandomState(0).standard_normal(
            (1,) + tuple(shape)).astype(dtype)
        cli.infer("m", {feed_name: x}, deadline_ms=60000)  # warm wire
        fetches, info = cli.infer("m", {feed_name: x},
                                  deadline_ms=60000, debug=True)
        tree = cli.trace(trace_id=info["trace_id"])["spans"]
        cli.shutdown_server()
    finally:
        srv.shutdown()

    # --- one traced train step (tiny fc regression) ------------------
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        xv = fluid.layers.data(name="x", shape=[4], dtype="float32")
        yv = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=xv, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            input=pred, label=yv))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(1)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for step in range(steps):
            with obs_tracing.trace("train/step", kind="train",
                                   step=step):
                exe.run(main_p,
                        feed={"x": rng.randn(8, 4).astype(np.float32),
                              "y": rng.randn(8, 1).astype(np.float32)},
                        fetch_list=[loss])

    fluid.profiler.stop_profiler()
    merged = fluid.profiler.export_chrome_tracing(
        trace_dir=out_dir,
        output_path=os.path.join(out_dir, "obs_merged_trace.json"))
    reqs = group_requests(obs_tracing.recent_spans(kind="serving"))
    steps_out = group_steps(obs_tracing.recent_spans(kind="train"))
    return {
        "stage": "obs", "backend": jax.default_backend(),
        "model": model_kind, "merged_trace": merged,
        "trace_id": info.get("trace_id"),
        "request_debug": info, "request_spans": len(tree),
        "requests": reqs[:3], "train_steps": steps_out[:5],
        "tracing": obs_tracing.stats(),
        "trace_flag": bool(FLAGS.trace),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("endpoint", nargs="?", default=None,
                    help="HOST:PORT of the inference server")
    ap.add_argument("-n", "--limit", type=int, default=10)
    ap.add_argument("--trace_id", default=None,
                    help="resolve one trace id into its span tree")
    ap.add_argument("--train", action="store_true",
                    help="slowest train steps instead of requests")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--capture", action="store_true",
                    help="traced serving run + train step; archive the "
                         "merged chrome trace")
    ap.add_argument("--model", default=None,
                    help="--capture model kind (default: resnet on "
                         "tpu, fc elsewhere)")
    ap.add_argument("--out_dir", default=None,
                    help="--capture trace/archive directory")
    args = ap.parse_args(argv)

    if args.capture:
        summary = capture(model_kind=args.model, out_dir=args.out_dir)
        print(json.dumps(summary, default=str))
        return 0
    if not args.endpoint:
        ap.error("need an endpoint (or --capture)")
    from paddle_tpu.serving import ServingClient
    cli = ServingClient(args.endpoint)
    try:
        if args.trace_id:
            reply = cli.trace(trace_id=args.trace_id)
            spans = reply.get("spans", [])
            if args.json:
                print(json.dumps(spans, indent=1, default=str))
            elif not spans:
                print("trace %s not found in the ring "
                      "(wrapped? buffer=%s)"
                      % (args.trace_id,
                         reply.get("tracing", {}).get("capacity")))
                return 1
            else:
                print(render_tree(spans))
            return 0
        kind = "train" if args.train else "serving"
        spans = cli.trace(kind=kind, limit=4096).get("spans", [])
        recs = group_steps(spans) if args.train \
            else group_requests(spans)
        if args.json:
            print(json.dumps(recs[:args.limit], indent=1, default=str))
        else:
            print(render_steps(recs, args.limit) if args.train
                  else render_requests(recs, args.limit))
        return 0
    finally:
        cli.close()


if __name__ == "__main__":
    sys.exit(main())
