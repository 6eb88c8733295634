"""BASELINE.json model-zoo benchmark sweep (VERDICT r3 #2).

Runs every tracked config through tools/fluid_benchmark.py in fresh
subprocesses (one clean backend init each) and writes ONE sidecar JSON
with throughput + a step-time breakdown per model.

THIS PARENT MUST STAY OFF JAX for as long as it launches children: a chip
belongs to one process at a time, and a parent that has touched jax holds
it, so every child would fail or hang. It never imports jax, never probes
a backend; each child brings the chip up itself and fails (exit 3) when
there is none. `--smoke` asks for the CPU path check explicitly (children
run tiny shapes with `--device CPU` / `--smoke`). The sweep always runs
to its end and then exits non-zero if any config errored or timed out.

Usage:  python tools/bench_zoo.py [--out BENCH_zoo.json] [--iterations N]
"""

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# (name, fluid_benchmark args, tpu batch, cpu smoke batch)
# Ordered by information value per minute of chip time (results persist
# incrementally): configs with NO real-chip number yet (or invalidated
# ones: se_resnext predates the grouped-conv VJP fix) run first,
# re-confirmations of r4-measured rows later, the heaviest compiles
# (remat) last.
CONFIGS = [
    ("se_resnext_imagenet", ["--model", "se_resnext",
                             "--layout", "NHWC"], 64, 4),
    ("resnet50_imagenet", ["--model", "resnet", "--data_set", "imagenet",
                           "--layout", "NHWC"], 256, 8),
    ("transformer_base_s512", ["--model", "transformer"], 32, 2),
    # long-context transformer lanes: the seq-1k/4k rows measure the
    # tuned Pallas flash-attention kernel pair (fwd + fused bwd) inside
    # a full training step — the end-to-end check that the attention
    # roofline work (ROOFLINE.md attention section) composes in-graph,
    # the lesson fused_bottleneck taught. Run bench_attention --tune
    # first on a fresh chip so these rows ride tuned geometry.
    ("transformer_flash_s1024",
     ["--model", "transformer", "--seq_len", "1024"], 16, 2),
    ("transformer_flash_s4096",
     ["--model", "transformer", "--seq_len", "4096"], 4, 1),
    # device-side loop: 10 steps per dispatch (lax.fori_loop over the
    # jitted step) — measures chip throughput with host round trips
    # amortized away entirely
    ("resnet50_deviceloop",
     ["--model", "resnet", "--data_set", "imagenet", "--layout", "NHWC",
      "--device_loop", "10"], 256, 8),
    ("mnist_cnn_deviceloop", ["--model", "mnist", "--device_loop", "10"],
     512, 64),
    ("transformer_deviceloop",
     ["--model", "transformer", "--device_loop", "10"], 32, 2),
    # ParallelExecutor path on silicon (degenerate 1-device mesh on the
    # single exposed chip; the SPMD step + collective insertion is the
    # code under test, the virtual-mesh suite covers >1 devices). Only
    # the small-feed config: PE re-commits host shards per dispatch, so
    # a vision-scale batch would time the host feed
    ("mnist_cnn_pe", ["--model", "mnist", "--parallel",
                      "--device_loop", "10"], 512, 64),
    ("stacked_dynamic_lstm_deviceloop",
     ["--model", "stacked_dynamic_lstm", "--device_loop", "10"], 64, 8),
    ("machine_translation_wmt", ["--model", "machine_translation"], 16, 4),
    # serving lanes (SERVING.md): open-loop Poisson load through the
    # dynamic micro-batcher onto bucketed executables — measures the
    # serving FRONT (coalescing, padding, admission) where bench_infer
    # measures the raw per-batch compute it dispatches onto. The batch
    # column is the largest bucket; the "@serving" marker routes the
    # lane to tools/bench_serving.py instead of fluid_benchmark.
    ("serving_resnet_b32",
     ["@serving", "--model", "resnet", "--qps", "100,400",
      "--duration", "20"], 32, 4),
    ("serving_resnet_b128",
     ["@serving", "--model", "resnet", "--qps", "400,1600",
      "--duration", "20"], 128, 4),
    # multi-chip serving lanes (SERVING.md "Multi-chip serving"): same
    # model, same offered load, 1 vs 4 device-placed replicas behind
    # the least-loaded router. On CPU the 4 "chips" are forced XLA host
    # devices and --dispatch_cost_ms stands in for per-batch device
    # time (deterministic, GIL-released — the same stand-in discipline
    # as the pipeline lanes' --host_stall_ms), so the r1 -> r4
    # achieved-QPS ratio IS the router/lane-parallelism number; on real
    # silicon the replicas land on actual chips and the cost stand-in
    # still bounds the routing overhead measurement. bucket=1 keeps
    # coalescing out of the comparison (bench the lanes, not the
    # batcher). Each record carries bit_exact: replica routing must
    # not change one reply bit vs direct Predictor.run.
    ("serving_mc_r1",
     ["@serving", "--model", "fc", "--replicas", "1",
      "--force_host_devices", "4", "--dispatch_cost_ms", "20",
      "--qps", "250", "--duration", "8", "--deadline_ms", "4000",
      "--max_queue", "32"], 1, 1),
    ("serving_mc_r4",
     ["@serving", "--model", "fc", "--replicas", "4",
      "--force_host_devices", "4", "--dispatch_cost_ms", "20",
      "--qps", "250", "--duration", "8", "--deadline_ms", "4000",
      "--max_queue", "32"], 1, 1),
    # quantized-serving A/B lanes (QUANTIZE.md): the SAME model name
    # served fp32 and PTQ-int8 behind the registry's precision axis,
    # identical seeded open-loop load routed per-request. On the
    # HBM-roofline-bound chip the int8 lane's weight bytes are the
    # speedup; the CPU smoke rows prove the axis end to end (per-lane
    # bit-stability, pinned accuracy delta, weight-bytes ratio <= 0.5x,
    # per-precision metrics); throughput on silicon: not measured.
    ("serving_quant_fp32",
     ["@serving", "--model", "fc", "--precision", "fp32",
      "--qps", "150", "--duration", "8"], 8, 4),
    ("serving_quant_int8",
     ["@serving", "--model", "fc", "--precision", "int8",
      "--qps", "150", "--duration", "8"], 8, 4),
    # continuous-batching decode lanes (SERVING.md "Continuous batching
    # & streaming"): identical seeded mixed-output-length streaming
    # workloads against the slot-table decode path, static whole-batch
    # scheduling vs continuous backfill. --step_cost_ms 20 is the
    # deterministic per-decode-step device-time stand-in (GIL released,
    # same discipline as --dispatch_cost_ms) that makes capacity
    # slot-bound, so the cb/static tokens_per_sec ratio IS the
    # scheduling win (>= 2x acceptance, BENCH_r10.json); offered load
    # saturates both. Each record carries bit_exact: greedy streams
    # replayed against a direct single-slot DecodeSession.
    ("serving_decode_static",
     ["@serving", "--decode", "--decode_mode", "static",
      "--decode_slots", "8", "--step_cost_ms", "20", "--qps", "30",
      "--duration", "8"], 8, 1),
    ("serving_decode_cb",
     ["@serving", "--decode", "--decode_mode", "cb",
      "--decode_slots", "8", "--step_cost_ms", "20", "--qps", "30",
      "--duration", "8"], 8, 1),
    # quantized-KV-cache A/B (QUANTIZE.md "Quantized KV cache"): the
    # same continuous-batching decode workload served with the fp32 vs
    # the int8 slot table (fresh server per dtype).  The records carry
    # static + measured cache bytes vs fp32 (<= 0.27x acceptance), a
    # per-dtype bit-exact replay (int8 streams are bit-stable against
    # an int8 direct session), and the fp32-vs-int8 greedy top-1
    # agreement (>= 0.99 acceptance) — BENCH_r14.json headline
    ("serving_decode_int8kv",
     ["@serving", "--decode", "--decode_mode", "cb",
      "--decode_slots", "8", "--step_cost_ms", "20", "--qps", "30",
      "--kv_dtype", "both", "--duration", "8"], 8, 1),
    # speculative-decoding lane (SERVING.md "Speculative decoding"):
    # same continuous-batching workload, draft depth 0 (target-only
    # baseline) vs 4 on one sweep — the same-weights twin draft makes
    # accept ~1.0, --draft_cost_ms defaults to 0.3x the step cost (the
    # BENCH_r11 int8 weight-bytes ratio), so tokens_per_sec_per_slot
    # k4/k0 reads the speculative scheduling win at equal step cost
    # (>= 1.5x acceptance, BENCH_r12.json); every point carries a
    # bit-exact replay vs the fp32-only greedy stream
    ("serving_specdec",
     ["@serving", "--decode", "--decode_mode", "cb",
      "--decode_slots", "4", "--step_cost_ms", "25",
      "--spec_k", "0,4", "--qps", "40", "--duration", "8"], 8, 1),
    # mesh-replica lane (SERVING.md "Mesh replicas"): one replica as a
    # 1- vs 2- vs 4-chip device mesh, params + KV slot table sharded
    # across members, every point replayed bit-exact vs the single-
    # device greedy oracle.  The CPU rows prove the sharded program +
    # fit columns end to end (est_per_device_mb ~1/m at flat whole-
    # model estimate, BENCH_r18.json); the QPS deltas only mean
    # something on silicon (not measured)
    ("serving_mesh",
     ["@serving", "--mesh", "1,2,4", "--decode_slots", "4",
      "--device_mem_mb", "16"], 8, 1),
    # async-training-pipeline A/B (PIPELINE.md): same model, same
    # 40 ms/batch host stall (deterministic stand-in for host-side
    # preprocessing — the host-BOUND lane), prefetch + in-flight
    # dispatch off vs on. The sync lane pays the stall + feed transfer
    # + fetch sync inside every step; the async lane hides the stall on
    # the prefetch thread and lets the loss fetch lag dispatch by 4
    # steps, so the delta between the two rows IS the pipeline win.
    ("pipeline_sync",
     ["--model", "mnist", "--host_stall_ms", "40"], 512, 64),
    ("pipeline_async",
     ["--model", "mnist", "--host_stall_ms", "40",
      "--prefetch_depth", "4", "--async_depth", "4"], 512, 64),
    # pipelined variants: fetch (host sync) every 10 steps instead of
    # each one — shows the small-model throughput with async dispatch
    # allowed to overlap steps (bench.py's flagship methodology); the
    # per-step rows above stay the reference-faithful comparison
    ("mnist_cnn_pipelined", ["--model", "mnist", "--fetch_every", "10"],
     512, 64),
    ("stacked_dynamic_lstm_pipelined",
     ["--model", "stacked_dynamic_lstm", "--fetch_every", "10"], 64, 8),
    # re-confirmations of rows measured on silicon earlier in r4
    ("mnist_cnn", ["--model", "mnist"], 512, 64),
    ("vgg16_cifar10", ["--model", "vgg", "--data_set", "cifar10"],
     128, 8),
    ("stacked_dynamic_lstm_ptb", ["--model", "stacked_dynamic_lstm"],
     64, 8),
    # whole-graph AD + rematerialized backward (ROOFLINE.md remat lever);
    # ineligible programs fail loudly (functionalizer refuses to run a
    # baseline under a remat label) rather than skewing the sweep.
    # Last: the heaviest compiles of the sweep.
    ("resnet50_imagenet_remat",
     ["--model", "resnet", "--data_set", "imagenet", "--layout", "NHWC",
      "--whole_graph_ad", "--remat_policy", "conv_out"], 256, 8),
    # block-granularity remat: save only residual-block boundaries,
    # recompute block interiors in the backward — the ~3x
    # activation-capacity lever; the measured row arbitrates the
    # ROOFLINE.md traffic model (which projects it traffic-NEUTRAL at
    # best for conv stacks at this batch)
    ("resnet50_imagenet_remat_blk",
     ["--model", "resnet", "--data_set", "imagenet", "--layout", "NHWC",
      "--whole_graph_ad", "--remat_policy", "block_out"], 256, 8),
    ("vgg16_cifar10_remat",
     ["--model", "vgg", "--data_set", "cifar10",
      "--whole_graph_ad", "--remat_policy", "conv_out"], 128, 8),
    ("stacked_dynamic_lstm_remat",
     ["--model", "stacked_dynamic_lstm",
      "--whole_graph_ad", "--remat_policy", "conv_out"], 64, 8),
]


if REPO not in sys.path:
    sys.path.insert(0, REPO)


def run_config(name, extra, batch, iterations, force_cpu):
    if extra and extra[0] == "@serving":
        # serving lane: bench_serving owns its own sweep protocol; batch
        # is the largest compiled bucket
        cmd = [sys.executable, os.path.join(HERE, "bench_serving.py")] \
            + extra[1:] + ["--max_bucket", str(batch)]
        if force_cpu:
            cmd += ["--smoke"]
        t0 = time.time()
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=1800, cwd=REPO)
        except subprocess.TimeoutExpired:
            return {"config": name, "error": "timeout after 1800s",
                    "timeout": True,
                    "wall_sec": round(time.time() - t0, 1)}
        wall = time.time() - t0
        if proc.returncode != 0:
            return {"config": name, "error": proc.stderr[-800:],
                    "wall_sec": round(wall, 1)}
        points = []
        for line in proc.stdout.splitlines():
            if line.startswith("{"):
                try:
                    points.append(json.loads(line))
                except ValueError:
                    pass
        if not points:
            return {"config": name, "wall_sec": round(wall, 1),
                    "error": "no JSON record on stdout; tail: %r"
                             % proc.stdout[-400:]}
        # one zoo record per lane: the highest-QPS point headlines, the
        # full sweep rides along
        rec = dict(points[-1])
        rec["config"] = name
        rec["sweep_points"] = points
        rec["wall_sec"] = round(wall, 1)
        return rec
    if force_cpu and "--device_loop" in extra:
        # smoke mode only checks the path works; a 10-deep loop of
        # resnet-class steps on CPU blows the per-config timeout
        extra = list(extra)
        extra[extra.index("--device_loop") + 1] = "2"
    cmd = [sys.executable, os.path.join(HERE, "fluid_benchmark.py"),
           "--batch_size", str(batch), "--iterations", str(iterations),
           "--skip_batch_num", "2"] + extra
    env = dict(os.environ)
    # --device TPU makes the child fail without a chip instead of
    # measuring whatever backend jax picked
    cmd += ["--device", "CPU" if force_cpu else "TPU"]
    t0 = time.time()
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=1800, cwd=REPO, env=env)
    except subprocess.TimeoutExpired:
        # one hung config must not cost the rest of the sweep — the
        # whole point of the information-value ordering
        return {"config": name, "error": "timeout after 1800s",
                "timeout": True, "wall_sec": round(time.time() - t0, 1)}
    wall = time.time() - t0
    if proc.returncode != 0:
        return {"config": name, "error": proc.stderr[-800:],
                "wall_sec": round(wall, 1)}
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    if not lines:
        return {"config": name, "wall_sec": round(wall, 1),
                "error": "no JSON record on stdout; tail: %r"
                         % proc.stdout[-400:]}
    rec = json.loads(lines[-1])
    rec["config"] = name
    rec["wall_sec"] = round(wall, 1)
    if rec.get("examples_per_sec"):
        rec["ms_per_step"] = round(
            rec["batch_size"] / rec["examples_per_sec"] * 1000.0, 2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_zoo.json"))
    ap.add_argument("--iterations", type=int, default=20)
    ap.add_argument("--only", default=None,
                    help="comma-separated config-name filter")
    ap.add_argument("--smoke", action="store_true",
                    help="CPU path check: tiny batches, every child held "
                         "to the CPU. Without it every child needs the "
                         "chip and errors without one")
    ap.add_argument("--resume", action="store_true",
                    help="skip configs that already have an error-free "
                         "record in --out (an interrupted sweep must "
                         "not cost completed hour-scale runs)")
    ap.add_argument("--staged", type=int, default=0, metavar="K",
                    help="append --staged_feed K to every config: batches "
                         "pre-staged on device, cycled (bench.py flagship "
                         "methodology): the step without the host feed. "
                         "Each record carries its staged_feed field")
    args = ap.parse_args()

    prior = {}       # satisfies --resume (same feed staging): skip re-run
    preserved = []   # EVERY prior error-free record: carried into --out
    if args.resume and os.path.exists(args.out):
        try:
            with open(args.out) as f:
                for rec in json.load(f).get("configs", []):
                    if not rec.get("config") or rec.get("error"):
                        continue
                    # every completed record survives the rewrite, even
                    # when --only or a mid-sweep abort means its config
                    # is never reached this run — hour-scale chip runs
                    # must not be lost to a filtered or truncated pass.
                    # But a record only satisfies --resume (skips the
                    # re-run) if it was measured under the SAME feed
                    # staging: resuming a --staged sweep over
                    # per-step-feed records would silently keep the
                    # feed-bound numbers.
                    preserved.append(rec)
                    if rec.get("staged_feed", 0) == args.staged:
                        prior[rec["config"]] = rec
        except (ValueError, OSError):
            prior, preserved = {}, []

    force_cpu = args.smoke
    results = {
        "smoke_mode": force_cpu,
        "iterations": args.iterations,
        "configs": list(preserved),
    }
    wanted = set(args.only.split(",")) if args.only else None
    consecutive_timeouts = 0
    for name, extra, tpu_batch, cpu_batch in CONFIGS:
        if wanted and name not in wanted:
            continue
        if name in prior:
            # the record is already in results via `preserved`
            print("== %s: kept prior record (--resume) ==" % name,
                  flush=True)
            continue
        batch = cpu_batch if force_cpu else tpu_batch
        print("== %s (batch %d) ==" % (name, batch), flush=True)
        if args.staged:
            extra = list(extra) + ["--staged_feed", str(args.staged)]
        rec = run_config(name, extra, batch, args.iterations, force_cpu)
        print(json.dumps(rec), flush=True)
        # a fresh measurement supersedes a prior record of the same
        # config AND same staging; different-staging records are a
        # different measurement and stay alongside. A FAILED run
        # supersedes nothing (error records carry no staged_feed and
        # must not delete a completed record of any staging)
        if not rec.get("error"):
            results["configs"] = [
                r for r in results["configs"]
                if not (r.get("config") == name
                        and r.get("staged_feed", 0)
                        == rec.get("staged_feed", 0))]
        results["configs"].append(rec)
        # persist after every config: a crash or ^C mid-sweep must not
        # discard completed hour-scale runs
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
        consecutive_timeouts = consecutive_timeouts + 1 \
            if rec.get("timeout") else 0
        # smoke mode's heavy vision configs can legitimately hit the
        # per-config ceiling on CPU — only a real-chip sweep reads
        # consecutive timeouts as a hung chip
        if consecutive_timeouts >= 2 and not force_cpu:
            # two configs in a row hitting the ceiling means the chip
            # is hung, not the configs — stop burning the remaining
            # budget
            results["aborted"] = "2 consecutive config timeouts"
            with open(args.out, "w") as f:
                json.dump(results, f, indent=2)
            print("aborting sweep: 2 consecutive timeouts", flush=True)
            break

    print("wrote %s" % args.out)
    # an aborted or partially-failed sweep must NOT look like success
    # (--resume exists to finish the missing configs on the next run)
    bad = [r["config"] for r in results["configs"] if r.get("error")]
    if results.get("aborted") or bad:
        print("sweep incomplete: aborted=%r failed=%r"
              % (results.get("aborted"), bad))
        raise SystemExit(5)


if __name__ == "__main__":
    main()
