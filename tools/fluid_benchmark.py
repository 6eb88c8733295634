"""Benchmark harness (reference benchmark/fluid/fluid_benchmark.py).

Same CLI shape as the reference runner: pick a model from the benchmark
zoo, train for a fixed number of iterations with synthetic data
(--use_fake_data is the default here: this environment generates data
procedurally), report examples/sec. `--parallel` runs through the
mesh-sharded ParallelExecutor; `--update_method` mirrors the reference's
local/pserver/nccl2 modes (nccl2 == collective DP over the jax mesh).

Examples:
    python tools/fluid_benchmark.py --model mnist --iterations 20
    python tools/fluid_benchmark.py --model resnet --batch_size 256 \
        --data_set imagenet --layout NHWC
    python tools/fluid_benchmark.py --model stacked_dynamic_lstm
    python tools/fluid_benchmark.py --model vgg --parallel
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


MODELS = ["mnist", "resnet", "vgg", "stacked_dynamic_lstm",
          "machine_translation", "se_resnext", "transformer"]


def parse_args():
    p = argparse.ArgumentParser("fluid_benchmark")
    p.add_argument("--model", default="mnist", choices=MODELS)
    p.add_argument("--batch_size", type=int, default=0,
                   help="0 = model default")
    p.add_argument("--iterations", type=int, default=20)
    p.add_argument("--skip_batch_num", type=int, default=2,
                   help="warmup batches excluded from timing")
    p.add_argument("--pass_num", type=int, default=1)
    p.add_argument("--device", default=None, choices=[None, "CPU", "TPU"],
                   help="CPU holds jax to the CPU; TPU exits 3 unless "
                        "jax found one; default: whatever jax picked")
    p.add_argument("--data_set", default=None,
                   help="imagenet|cifar10|flowers for the vision models")
    p.add_argument("--layout", default="NCHW", choices=["NCHW", "NHWC"])
    p.add_argument("--seq_len", type=int, default=0,
                   help="sequence length for the transformer model "
                        "(0 = the model default); the bench_zoo "
                        "long-context lanes use this to measure the "
                        "tuned flash-attention kernel at seq >= 1k")
    p.add_argument("--learning_rate", type=float, default=0.0)
    p.add_argument("--parallel", action="store_true",
                   help="train through ParallelExecutor (all devices)")
    p.add_argument("--update_method", default="local",
                   choices=["local", "pserver", "nccl2"],
                   help="nccl2 = collective DP (mesh); pserver = RPC PS")
    p.add_argument("--no_amp", action="store_true",
                   help="disable bf16 AMP (AMP on by default on TPU)")
    p.add_argument("--device_loop", type=int, default=0,
                   help="run N steps as ONE device computation "
                        "(lax.fori_loop over the jitted step) per "
                        "dispatch; removes host round-trips from the "
                        "loop. 0 = per-step Executor.run")
    p.add_argument("--fetch_every", type=int, default=1,
                   help="fetch loss (host sync) every N steps; 1 = the "
                        "reference's per-step methodology, >1 lets async "
                        "dispatch pipeline the steps between fetches")
    p.add_argument("--prefetch_depth", type=int, default=0,
                   help="feed the timed loop through "
                        "reader.prefetch_to_device with this queue "
                        "depth: batch synthesis + prepare_feeds + the "
                        "device_put for the NEXT batch run on a "
                        "background thread while the current step "
                        "computes (PIPELINE.md). 0 = synthesize and "
                        "transfer on the main thread each step")
    p.add_argument("--async_depth", type=int, default=0,
                   help="in-flight step dispatch: keep up to N steps' "
                        "fetches live on device (run(as_future=True)) "
                        "and resolve each at the pipeline tail — the "
                        "host sync lags dispatch by N steps. 0 = "
                        "resolve every step's loss before the next "
                        "dispatch (reference methodology)")
    p.add_argument("--host_stall_ms", type=float, default=0.0,
                   help="sleep this long on the feed path per batch — "
                        "a deterministic stand-in for host-side "
                        "preprocessing cost (decode/augment; the "
                        "chaos-harness slow-host injection). With "
                        "--prefetch_depth the stall runs on the "
                        "prefetch thread and is hidden by the pipeline; "
                        "without it, it serializes with every step — "
                        "the bench_zoo pipeline_sync/pipeline_async "
                        "lane pair measures exactly this delta")
    p.add_argument("--staged_feed", type=int, default=0,
                   help="pre-stage K synthetic batches on device before "
                        "the timed loop and cycle through them (bench.py "
                        "flagship methodology). Measures the training "
                        "step with host->device transfer amortized away. "
                        "0 = per-step host feed "
                        "(reference fluid_benchmark methodology)")
    p.add_argument("--profile", action="store_true")
    p.add_argument("--use_fake_data", action="store_true", default=True)
    p.add_argument("--whole_graph_ad", action="store_true",
                   help="serve the backward with one jax.vjp over the "
                        "forward region (enables --remat_policy)")
    p.add_argument("--remat_policy", default="",
                   help="jax.checkpoint policy under --whole_graph_ad: "
                        "'conv_out', 'dots' or 'nothing'")
    return p.parse_args()


def build_model(args):
    from paddle_tpu import models
    import importlib
    mod = importlib.import_module("paddle_tpu.models.%s" % args.model)
    kwargs = {}
    if args.batch_size:
        kwargs["batch_size"] = args.batch_size
    if args.learning_rate:
        kwargs["lr"] = args.learning_rate
    if args.model in ("resnet", "vgg") and args.data_set:
        kwargs["dataset"] = args.data_set
    if args.model in ("resnet", "se_resnext"):
        kwargs["layout"] = args.layout
    if args.model == "transformer" and args.seq_len:
        kwargs["seq_len"] = args.seq_len
    return mod.get_model(**kwargs)


def synth_feed(feeds, batch, rng, program=None):
    """Synthetic batch for the model's feed vars (the reference's
    --use_fake_data constant-fill path, fluid_benchmark.py:149)."""
    from paddle_tpu.fluid.lod import LoDTensor
    from paddle_tpu.fluid import core
    out = {}
    for v in feeds:
        if isinstance(v, str):   # some models return feed NAMES
            v = program.global_block().var(v)
        dtype = core.convert_dtype_to_np(v.dtype)
        shape = [d if isinstance(d, int) and d > 0 else None
                 for d in v.shape]
        sample_shape = [d for d in shape[1:] if d is not None]
        if v.lod_level and v.lod_level > 0:
            lens = rng.randint(3, 12, size=batch)
            flat = np.concatenate(
                [_sample(dtype, [l] + sample_shape, rng) for l in lens])
            t = LoDTensor(flat)
            t.set_recursive_sequence_lengths([lens.tolist()])
            out[v.name] = t
        else:
            out[v.name] = _sample(dtype, [batch] + sample_shape, rng)
    return out


def _sample(dtype, shape, rng):
    if np.issubdtype(dtype, np.integer):
        # ids: stay tiny so any vocab/label bound holds
        return rng.randint(0, 2, size=shape).astype(dtype)
    return rng.uniform(-0.5, 0.5, size=shape).astype(dtype)


def main():
    args = parse_args()
    import jax
    if args.device == "CPU":
        # set BEFORE any backend query — default_backend() would
        # initialize (and possibly wait on) the TPU runtime
        jax.config.update("jax_platforms", "cpu")
    elif args.device == "TPU" and jax.default_backend() != "tpu":
        print("fluid_benchmark: --device TPU but jax's backend is %r"
              % jax.default_backend(), file=sys.stderr)
        raise SystemExit(3)
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import profiler as prof

    if not args.no_amp and jax.default_backend() == "tpu":
        fluid.set_amp(True)
    if args.whole_graph_ad or args.remat_policy:
        if args.remat_policy and args.update_method == "pserver":
            # the transpiled pserver program interleaves RPC host ops;
            # whole-graph AD cannot span them — refuse rather than
            # record a baseline number under a remat label
            raise SystemExit(
                "--remat_policy not supported with --update_method "
                "pserver")
        from paddle_tpu.flags import FLAGS
        FLAGS.whole_graph_ad = True
        FLAGS.remat_policy = args.remat_policy

    if args.device_loop > 0 and args.update_method == "pserver":
        # the pserver program interleaves RPC host ops; a device loop
        # cannot span them — refuse rather than record a per-step run
        # under a device_loop label (same contract as the remat guard)
        raise SystemExit(
            "--device_loop not supported with --update_method pserver")
    if args.async_depth > 0 and args.device_loop > 0:
        raise SystemExit(
            "--async_depth not supported with --device_loop (the device "
            "loop is already one dispatch per N steps; there is no "
            "per-step fetch to defer)")
    if args.async_depth > 0 and args.update_method == "pserver":
        raise SystemExit(
            "--async_depth not supported with --update_method pserver "
            "(RPC host ops force per-step sync; the record would carry "
            "an async label over a sync run)")
    main_prog, startup, feeds, loss, acc, _ = build_model(args)
    feeds = [main_prog.global_block().var(f) if isinstance(f, str) else f
             for f in feeds]
    batch = args.batch_size or feeds[0].shape[0] or 32
    if not isinstance(batch, int) or batch <= 0:
        batch = 32
    rng = np.random.RandomState(0)

    pserver_eps = os.environ.get(
        "PADDLE_PSERVER_EPS",
        os.environ.get("PADDLE_PSERVER_IPS", "127.0.0.1") + ":" +
        os.environ.get("PADDLE_PSERVER_PORT", "6174"))
    if args.update_method == "pserver":
        # reference fluid_benchmark.py:84-86: roles and endpoints come
        # from the PADDLE_* environment (test_dist_base-style clusters)
        from paddle_tpu.fluid.transpiler import DistributeTranspiler
        from paddle_tpu.distributed.rpc import wait_server_ready
        role = os.environ.get("PADDLE_TRAINING_ROLE", "TRAINER")
        trainers = int(os.environ.get("PADDLE_TRAINERS", "1"))
        trainer_id = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
        t = DistributeTranspiler()
        t.transpile(trainer_id=trainer_id, program=main_prog,
                    pservers=pserver_eps, trainers=trainers,
                    startup_program=startup)
        if role == "PSERVER":
            ep = os.environ.get("PADDLE_CURRENT_ENDPOINT",
                                pserver_eps.split(",")[0])
            ps_prog = t.get_pserver_program(ep)
            ps_startup = t.get_startup_program(ep, ps_prog,
                                               startup_program=startup)
            exe = fluid.Executor(fluid.TPUPlace(0))
            exe.run(ps_startup)
            print(json.dumps({"role": "pserver", "endpoint": ep}),
                  flush=True)
            exe.run(ps_prog)        # listen_and_serv blocks until exit
            return
        main_prog = t.get_trainer_program()
        wait_server_ready(pserver_eps.split(","))

    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)

    pe = None
    if args.parallel or args.update_method == "nccl2":
        pe = fluid.ParallelExecutor(
            use_cuda=False, loss_name=loss.name, main_program=main_prog)

    fetch = [loss.name] + ([acc.name] if acc is not None else [])

    staged = None
    if args.staged_feed > 0:
        # Pre-stage K distinct batches on device and fence the transfers
        # so none of the H2D cost lands inside the timed window. Passing
        # the prepared dict back through Executor.run is safe: its
        # prepare_feeds keeps jax.Array values as-is (the PyReader
        # double-buffer fast path). The ParallelExecutor commits shards
        # itself, so for --parallel the staging only amortizes batch
        # *generation*, not the transfer.
        from paddle_tpu.fluid.executor import prepare_feeds
        staged = [prepare_feeds(main_prog,
                                synth_feed(feeds, batch, rng,
                                           program=main_prog),
                                device_put=(pe is None))
                  for _ in range(args.staged_feed)]
        jax.block_until_ready([a for d in staged for a in d.values()
                               if isinstance(a, jax.Array)])
        # one host round-trip per staged dict on top, so no H2D
        # transfer can leak into the profiler window or the timed region
        for d in staged:
            for a in d.values():
                if isinstance(a, jax.Array):
                    np.asarray(a.ravel()[:1])
                    break

    # staging completes BEFORE the profiler window opens so the fenced
    # H2D transfers are excluded from the trace the flag exists to clean
    if args.profile:
        prof.start_profiler("All")

    n_warm, n_timed = args.skip_batch_num, args.iterations

    def make_batch():
        # --host_stall_ms: deterministic host-side preprocessing cost;
        # on the prefetch thread it overlaps the step, on the main
        # thread it serializes with it
        if args.host_stall_ms > 0:
            time.sleep(args.host_stall_ms / 1000.0)
        return synth_feed(feeds, batch, rng, program=main_prog)

    feeds_it = None
    if args.prefetch_depth > 0:
        if staged:
            raise SystemExit(
                "--prefetch_depth and --staged_feed are mutually "
                "exclusive feed paths (staging already amortizes the "
                "transfer the prefetch queue overlaps)")
        from paddle_tpu import reader as reader_mod
        from paddle_tpu.fluid.executor import prepare_feeds as _prep
        total_batches = n_warm + n_timed

        def batch_source():
            for _ in range(total_batches):
                yield make_batch()

        # single-device path: prefetch stages host prep + device_put.
        # ParallelExecutor path (sharded prefetch, PIPELINE.md): the
        # prefetch thread ALSO commits the mesh-sharded global array
        # (make_array_from_process_local_data), so the PE's dispatch
        # sees pre-sharded feeds and pays no per-step shard commit
        feeds_it = reader_mod.prefetch_to_device(
            batch_source, args.prefetch_depth,
            prepare=lambda d: _prep(main_prog, d,
                                    device_put=(pe is None)),
            mesh=(pe.mesh if pe is not None else None))()

    pending = []
    examples = 0
    t0 = time.perf_counter()
    last = None

    def drain_oldest():
        vals = pending.pop(0).result(watchdog_scale=len(pending) + 2)
        return float(np.asarray(vals[0]).ravel()[0])

    for i in range(n_warm + n_timed):
        # start timing BEFORE the first timed batch so its runtime
        # (including jit compile when n_warm == 0) is in the denominator
        if i == n_warm:
            # async mode: warmup dispatches must fully resolve before
            # the clock starts or their compute leaks into the window
            while pending:
                last = drain_oldest()
            t0 = time.perf_counter()
        feed = (staged[i % len(staged)] if staged
                else next(feeds_it) if feeds_it is not None
                else make_batch())
        # --fetch_every N: fetch (= host sync) only every Nth step and on
        # the last, letting XLA's async dispatch pipeline the steps in
        # between. Default 1 keeps the reference methodology (the
        # reference fluid_benchmark fetched loss each iteration).
        # Fetch and no-fetch are distinct jit cache entries, so warmup
        # must compile BOTH: the FIRST warm step takes the no-fetch
        # variant, the rest fetch — so the final warm step fences the
        # device before t0 and no warmup execution leaks into the timed
        # window. (With n_warm < 2 the no-fetch compile unavoidably
        # lands in the timed region.)
        if args.fetch_every <= 1:
            do_fetch = True
        elif i < n_warm:
            do_fetch = not (i == 0 and n_warm >= 2)
        else:
            do_fetch = ((i + 1) % args.fetch_every == 0
                        or i == n_warm + n_timed - 1)
        if args.device_loop > 0:
            # one dispatch covers device_loop steps; fetch fences it
            if pe is not None:
                outs = pe.run_loop(fetch_list=fetch, feed=feed,
                                   steps=args.device_loop)
            else:
                outs = exe.run_loop(main_prog, feed=feed,
                                    fetch_list=fetch,
                                    steps=args.device_loop)
            last = float(np.asarray(outs[0]).ravel()[0])
            if i >= n_warm:
                examples += batch * args.device_loop
            continue
        if args.async_depth > 0:
            # in-flight dispatch: fetch EVERY step, resolve each at the
            # pipeline tail — the host sync lags dispatch by N steps
            # instead of fencing every one (PIPELINE.md)
            fut = (pe.run(fetch_list=fetch, feed=feed, as_future=True)
                   if pe is not None else
                   exe.run(main_prog, feed=feed, fetch_list=fetch,
                           as_future=True))
            pending.append(fut)
            while len(pending) > args.async_depth:
                last = drain_oldest()
            if i >= n_warm:
                examples += batch
            continue
        if pe is not None:
            outs = pe.run(fetch_list=fetch if do_fetch else [], feed=feed)
        else:
            outs = exe.run(main_prog, feed=feed,
                           fetch_list=fetch if do_fetch else [])
        if do_fetch:
            last = float(np.asarray(outs[0]).ravel()[0])  # host sync fence
        if i >= n_warm:
            examples += batch
    while pending:
        # drain the pipeline tail: the timed window must include every
        # timed step's compute, not leave the last N steps in flight
        last = drain_oldest()
    dt = time.perf_counter() - t0

    if args.profile:
        prof.stop_profiler("total", "/tmp/fluid_benchmark_profile")

    if args.update_method == "pserver" and \
            int(os.environ.get("PADDLE_TRAINER_ID", "0")) == 0:
        # trainer 0 tells every pserver to exit its serve loop
        from paddle_tpu.distributed.rpc import RPCClient
        client = RPCClient()
        for ep in pserver_eps.split(","):
            try:
                client.send_exit(ep)
            except Exception:
                pass
    assert np.isfinite(last), "loss diverged"
    print(json.dumps({
        "model": args.model,
        "batch_size": batch,
        "iterations": n_timed,
        "examples_per_sec": round(examples / dt, 2) if dt else None,
        "last_loss": round(last, 4),
        "device": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "parallel": bool(pe),
        "update_method": args.update_method,
        **({"device_loop": args.device_loop}
           if args.device_loop > 0 else {}),
        # staged_transfer says whether staging actually amortized the
        # H2D transfer: the ParallelExecutor re-commits shards from host
        # per step, so a --parallel run's staging only amortizes batch
        # generation and its record must not read as a framework number
        **({"staged_feed": args.staged_feed,
            "staged_transfer": pe is None}
           if args.staged_feed > 0 else {}),
        # pipeline lanes: the record self-describes its feed/dispatch
        # path so pipeline_sync vs pipeline_async deltas are readable
        # from BENCH_zoo json alone
        **({"prefetch_depth": args.prefetch_depth}
           if args.prefetch_depth > 0 else {}),
        **({"async_depth": args.async_depth}
           if args.async_depth > 0 else {}),
        **({"host_stall_ms": args.host_stall_ms}
           if args.host_stall_ms > 0 else {}),
        "whole_graph_ad": bool(args.whole_graph_ad or args.remat_policy),
        "remat_policy": args.remat_policy,
        # only models that honor --layout get the field; recording it
        # for others would mislabel an NCHW build as NHWC
        **({"layout": args.layout}
           if args.model in ("resnet", "se_resnext") else {}),
    }))


if __name__ == "__main__":
    main()
