"""Serving benchmark: open-loop load generator for the inference server.

Open-loop matters: a closed-loop client (send, wait, send) slows down
exactly when the server does, hiding queueing collapse. Here request
arrivals are a Poisson process at a target QPS, generated on schedule
whether or not earlier requests returned — so an overloaded server shows
up as latency blowup + sheds, never as a flattered throughput number.

Per (replica-count, target-QPS) point it prints ONE JSON line
compatible with the bench_zoo lane format:

  {"metric": "serving_qps", "model": ..., "target_qps": ...,
   "achieved_qps": ..., "p50_ms": ..., "p95_ms": ..., "p99_ms": ...,
   "shed_rate": ..., "batch_fill": ..., "bucket_fill_ratio": ...,
   "errors": ..., "replicas": ..., "bit_exact": ..., "platform": ...,
   "device_kind": ..., "device_count": ...,
   "cold_start_ms": ..., "swap_flip_ms": ..., "compile_cache": {...}}

Compile-cache columns (COMPILE_CACHE.md): `cold_start_ms` is server
start -> model loaded+warmed -> first reply; `swap_flip_ms` is a full
hot-swap flip of the same model (build + warm every bucket on every
replica, then the atomic latest flip). Run the tool twice with the same
--compile_cache_dir to measure the before/after: the first run compiles
and commits (cold), the second deserializes stored executables for
every (model, bucket, device-kind) triple (warm — the BENCH_r08.json
acceptance pair). --compile_cache off disables the cache entirely for
a no-cache baseline.

The server runs in-process (threads, same machine) on a model exported
fresh: `--model fc` (tiny, the CPU/CI path), `--model mnist`, or
`--model resnet` (the TPU serving flagship). `--smoke` forces the tiny
fc model with a short sweep — tier-1 CI proof that the whole
client->wire->router->lane->predictor->scatter path works.

Multi-chip serving (SERVING.md): `--replicas` takes a placement spec
('auto', an explicit device list) or a comma sweep of counts ('1,4' —
each count gets a fresh server, so the scaling curve is apples to
apples). `--force_host_devices N` splits the CPU backend into N XLA
host devices (the dryrun_multichip trick) so replica placement and
routing run for real without silicon. `--dispatch_cost_ms` injects a
deterministic per-dispatch stall in the lane worker (GIL released, the
same methodology as fluid_benchmark's --host_stall_ms): it stands in
for per-batch device time, so the r1 -> rN throughput ratio measures
the router/lane parallelism honestly even on a single host core.
Every point also replays a few requests against a direct in-process
Predictor.run and records `bit_exact` — replica routing must never
change a single bit of any reply.

Chaos: --chaos_proxy routes traffic through tools/chaos.py's FlakyProxy
(connection kills mid-flight), --chaos_slow_ms injects a slow-worker
stall per dispatch — the shed-not-hang proof under real overload.
"""

import argparse
import json
import os
import random
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def build_model(kind, model_dir, seed=17):
    """Train-free export of an inference artifact; returns
    (model_dir, feed_name, feed_shape_per_sample, dtype)."""
    import paddle_tpu.fluid as fluid
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        if kind == "fc":
            x = fluid.layers.data(name="x", shape=[16], dtype="float32")
            h = fluid.layers.fc(input=x, size=32, act="relu")
            pred = fluid.layers.fc(input=h, size=10, act="softmax")
            shape = (16,)
        elif kind == "fc_deep":
            # CPU-safe but compile-heavy: 8 hidden layers make the
            # trace+lower+XLA share of a boot dominate the fixed costs,
            # so the compile-cache cold/warm pair measures the cache,
            # not the wire overhead (COMPILE_CACHE.md bench lane)
            x = fluid.layers.data(name="x", shape=[16], dtype="float32")
            h = x
            for _ in range(8):
                h = fluid.layers.fc(input=h, size=128, act="relu")
            pred = fluid.layers.fc(input=h, size=10, act="softmax")
            shape = (16,)
        elif kind == "mnist":
            x = fluid.layers.data(name="x", shape=[1, 28, 28],
                                  dtype="float32")
            conv = fluid.layers.conv2d(input=x, num_filters=8,
                                       filter_size=3, padding=1,
                                       act="relu")
            pool = fluid.layers.pool2d(input=conv, pool_size=2,
                                       pool_stride=2)
            pred = fluid.layers.fc(input=pool, size=10, act="softmax")
            shape = (1, 28, 28)
        elif kind == "resnet":
            from paddle_tpu.models.resnet import resnet_imagenet
            x = fluid.layers.data(name="x", shape=[224, 224, 3],
                                  dtype="float32")
            pred = resnet_imagenet(x, class_dim=1000, depth=50,
                                   is_train=False, layout="NHWC")
            shape = (224, 224, 3)
        else:
            raise ValueError("unknown model kind %r" % kind)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.save_inference_model(model_dir, ["x"], [pred], exe,
                                   main_program=main)
    return model_dir, "x", shape, "float32"


def run_point(endpoint, model, feed_name, sample_shape, dtype,
              target_qps, duration, req_batch, deadline_ms, seed=0,
              precision=None):
    """One open-loop measurement point at `target_qps` for `duration`s.
    `precision` pins every request to one numerics lane (the fp32-vs-
    int8 A/B drives identical seeded workloads through each)."""
    from paddle_tpu.serving import DeadlineExceeded, ServerOverloaded
    rng = random.Random(seed)
    data = np.asarray(
        np.random.RandomState(seed).randn(req_batch, *sample_shape),
        dtype=dtype)
    lat_lock = threading.Lock()
    latencies = []
    counters = {"ok": 0, "shed": 0, "deadline": 0, "error": 0}

    def fire(scheduled):
        cli = _pool_client(endpoint)
        # open-loop latency: measured from the SCHEDULED arrival, so
        # time lost waiting for a free connection counts against the
        # server, not the harness
        try:
            cli.infer(model, {feed_name: data}, deadline_ms=deadline_ms,
                      retry_sheds=False, precision=precision)
            key = "ok"
        except ServerOverloaded:
            key = "shed"
        except DeadlineExceeded:
            key = "deadline"
        except Exception:
            key = "error"
        done = time.monotonic()
        with lat_lock:
            counters[key] += 1
            if key == "ok":
                latencies.append((done - scheduled) * 1000.0)

    clients = {}

    def _pool_client(ep):
        tid = threading.get_ident()
        c = clients.get(tid)
        if c is None:
            from paddle_tpu.serving import ServingClient as SC
            c = clients[tid] = SC(ep)
        return c

    threads = []
    t_end = time.monotonic() + duration
    next_t = time.monotonic()
    while next_t < t_end:
        now = time.monotonic()
        if next_t > now:
            time.sleep(next_t - now)
        th = threading.Thread(target=fire, args=(next_t,), daemon=True)
        th.start()
        threads.append(th)
        next_t += rng.expovariate(target_qps)
    for th in threads:
        th.join(timeout=max(deadline_ms / 1000.0, 1.0) + 10.0)
    sent = sum(counters.values())
    with lat_lock:
        ls = sorted(latencies)

    def pct(q):
        if not ls:
            return None
        return round(ls[min(int(len(ls) * q / 100.0), len(ls) - 1)], 3)

    return {
        "metric": "serving_qps",
        "target_qps": target_qps,
        "sent": sent,
        "ok": counters["ok"],
        "achieved_qps": round(counters["ok"] / duration, 2),
        "shed_rate": round(counters["shed"] / sent, 4) if sent else 0.0,
        "deadline_rate": round(counters["deadline"] / sent, 4)
        if sent else 0.0,
        "errors": counters["error"],
        "p50_ms": pct(50), "p95_ms": pct(95), "p99_ms": pct(99),
        "req_batch": req_batch,
    }


# ---------------------------------------------------------------------------
# decode / continuous-batching lanes (SERVING.md "Continuous batching &
# streaming").  Mixed-output-length streams are the shape that separates
# continuous from static batching: a static batch decodes until its
# LONGEST member finishes (short streams' slots idle), continuous
# batching backfills a freed slot the next step.  The length mix below
# (mostly short, a tail of long) makes the expected ratio
# E[max of batch] / E[length] ~ 2.3 at 4 slots — the >= 2x acceptance
# band with honest headroom.
# ---------------------------------------------------------------------------

DECODE_LEN_MIX = ((6, 0.5), (12, 0.3), (48, 0.2))


def _decode_request(seed, i, vocab, max_prompt=7):
    """Deterministic (prompt, max_new_tokens) for request index i —
    identical across the cb and static lanes, so the A/B compares
    scheduling, not workloads."""
    rng = random.Random((seed << 20) ^ i)
    plen = rng.randint(2, max_prompt)
    prompt = [rng.randrange(1, vocab) for _ in range(plen)]
    r = rng.random()
    acc = 0.0
    max_new = DECODE_LEN_MIX[-1][0]
    for n, p in DECODE_LEN_MIX:
        acc += p
        if r <= acc:
            max_new = n
            break
    return prompt, max_new


def build_decode_model(model_dir, seed=7):
    """Tiny random-weight causal LM (the decode analogue of the fc
    smoke model).  eos_id=-1 keeps greedy streams running to their
    max_new_tokens budget, so the bench's length mix — not the random
    weights — controls the output-length distribution."""
    from paddle_tpu.inference.decode import build_tiny_decode_model
    return build_tiny_decode_model(
        model_dir, vocab_size=64, d_model=32, n_heads=4, n_layers=2,
        max_seq_len=64, eos_id=-1, seed=seed)


def _measure_idle_ttft(endpoint, model, vocab, seed=99, n=40):
    """Idle-server TTFT p95 — the baseline the under-load TTFT p95
    acceptance bound (<= 1.5x) compares against.  Probes run
    SEQUENTIALLY (so the server is idle for each) but through the same
    machinery as the load generator — one spawned thread + fresh
    connection per stream, measured from the pre-spawn stamp — and the
    same p95 estimator over a comparable sample count, so the ratio
    isolates QUEUEING rather than thread-start/connect jitter."""
    from paddle_tpu.serving import ServingClient
    vals = []

    def probe(i, scheduled):
        cli = ServingClient(endpoint)
        prompt, _ = _decode_request(seed, i, vocab)
        try:
            for _ in cli.infer_stream(model, prompt, max_new_tokens=2,
                                      deadline_ms=60000.0):
                vals.append((time.monotonic() - scheduled) * 1000.0)
                break
        finally:
            cli.close()

    for i in range(n):
        t0 = time.monotonic()
        th = threading.Thread(target=probe, args=(i, t0), daemon=True)
        th.start()
        th.join(timeout=30)
    vals.sort()
    if not vals:
        return None
    return round(vals[min(int(len(vals) * 0.95), len(vals) - 1)], 3)


def _verify_decode_bit_exact(endpoint, model, model_dir, seed, vocab,
                             n=3, kv_cache_dtype=None):
    """Replay a few prompts through the served continuous batch and
    against a direct single-slot DecodeSession on the same artifact
    (opened with the SAME kv_cache_dtype — an int8-cache server must be
    bit-exact against an int8-cache direct session) — requests
    joining/leaving the running batch must not move one token (greedy
    parity acceptance)."""
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             greedy_decode)
    from paddle_tpu.serving import ServingClient
    pred = GenerativePredictor(model_dir, kv_cache_dtype=kv_cache_dtype)
    cli = ServingClient(endpoint)
    try:
        for i in range(n):
            prompt, max_new = _decode_request(seed + 7000, i, vocab)
            served = [t for c in cli.infer_stream(
                model, prompt, max_new_tokens=max_new,
                deadline_ms=120000.0) for t in c]
            ref, _ = greedy_decode(pred, prompt, max_new)
            if served != ref:
                return False
        return True
    finally:
        cli.close()


def run_decode_point(endpoint, model, vocab, target_qps, duration,
                     deadline_ms, seed=0):
    """One open-loop streaming measurement point: Poisson arrivals of
    mixed-output-length generation requests; reports aggregate
    tokens/sec (the continuous-batching acceptance number), stream
    completion rate, and TTFT percentiles measured from the SCHEDULED
    arrival (open-loop discipline, same as run_point)."""
    from paddle_tpu.serving import (DeadlineExceeded, ServerOverloaded,
                                    ServingClient)
    rng = random.Random(seed)
    lock = threading.Lock()
    ttfts = []
    counters = {"ok": 0, "shed": 0, "deadline": 0, "error": 0}
    tokens_out = [0]

    def fire(i, scheduled):
        cli = ServingClient(endpoint)
        prompt, max_new = _decode_request(seed, i, vocab)
        first = None
        got = 0
        try:
            for chunk in cli.infer_stream(model, prompt,
                                          max_new_tokens=max_new,
                                          deadline_ms=deadline_ms):
                if first is None:
                    first = (time.monotonic() - scheduled) * 1000.0
                got += len(chunk)
            key = "ok"
        except ServerOverloaded:
            key = "shed"
        except DeadlineExceeded:
            key = "deadline"
        except Exception:
            key = "error"
        finally:
            cli.close()
        with lock:
            counters[key] += 1
            tokens_out[0] += got
            if first is not None:
                ttfts.append(first)

    threads = []
    t_start = time.monotonic()
    t_end = t_start + duration
    next_t = time.monotonic()
    i = 0
    while next_t < t_end:
        now = time.monotonic()
        if next_t > now:
            time.sleep(next_t - now)
        th = threading.Thread(target=fire, args=(i, next_t), daemon=True)
        th.start()
        threads.append(th)
        i += 1
        next_t += rng.expovariate(target_qps)
    for th in threads:
        th.join(timeout=max(deadline_ms / 1000.0, 1.0) + 30.0)
    wall = time.monotonic() - t_start
    sent = sum(counters.values())
    with lock:
        ts = sorted(ttfts)

    def pct(q):
        if not ts:
            return None
        return round(ts[min(int(len(ts) * q / 100.0), len(ts) - 1)], 3)

    return {
        "metric": "serving_decode",
        "target_qps": target_qps,
        "sent": sent,
        "ok": counters["ok"],
        "shed": counters["shed"],
        "deadline": counters["deadline"],
        "errors": counters["error"],
        "achieved_qps": round(counters["ok"] / wall, 2),
        "tokens_per_sec": round(tokens_out[0] / wall, 2),
        "tokens_total": tokens_out[0],
        "ttft_p50_ms": pct(50),
        "ttft_p95_ms": pct(95),
    }


def _kv_top1_agreement(model_dir, seed, vocab, n=5, max_new=12):
    """Greedy-stream top-1 agreement of the int8-cache twin vs the
    fp32-cache stream on identical prompts: matched-prefix tokens over
    total tokens (a first divergence charges the whole tail — the
    honest metric for greedy streams).  The acceptance bound is
    >= 0.99 on the tiny fixture."""
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             greedy_decode)
    fp = GenerativePredictor(model_dir, kv_cache_dtype="float32")
    q8 = GenerativePredictor(model_dir, kv_cache_dtype="int8")
    agree = total = 0
    for i in range(n):
        prompt, _ = _decode_request(seed + 5000, i, vocab)
        a, _ = greedy_decode(fp, prompt, max_new)
        b, _ = greedy_decode(q8, prompt, max_new)
        m = 0
        for x, y in zip(a, b):
            if x != y:
                break
            m += 1
        agree += m
        total += max(len(a), len(b))
    return round(agree / float(total), 4) if total else None


def run_decode_lane(args, device):
    """The --decode entry point: fresh in-process server per decode
    mode (cb = continuous batching, static = whole-batch baseline) and
    per `--spec_k` sweep point, identical seeded arrival schedule and
    per-request workloads, one JSON record per (mode, spec_k, qps)
    point.

    Speculative sweep (SERVING.md "Speculative decoding"): `--spec_k
    0,2,4,8` serves the same workload with draft depths 0 (target-only
    baseline) through 8.  The draft defaults to the SAME artifact
    (`--spec_draft twin`), the synthetic high-accept workload: accept
    rate ~1.0, so the accept-rate x speedup table reads the scheduling
    ceiling.  `--draft_cost_ms` prices each draft step (default 0.3x
    `--step_cost_ms` — the BENCH_r11 int8 weight-bytes ratio, what the
    int8-twin draft would cost on a bandwidth-bound chip); the verify
    step costs one `--step_cost_ms` like any target step.  Every point
    replays prompts against the fp32-only greedy stream and records
    `bit_exact` — speculation must never move one token.  Headline:
    `tokens_per_sec_per_slot` at equal step cost, spec_k=N vs 0.

    Fused-decode sweep (SERVING.md "Fused multi-step decode"):
    `--fuse_steps 1,4,8` pins the cap of the lane's per-dispatch
    window per point (1: every dispatch one step; past
    `decode.STEP_WINDOW` it is clamped; without the option the lane
    picks its windows under the built-in cap); `--host_cost_ms` charges the per-DISPATCH host round-trip
    the window amortizes (once per dispatch, however many trips run).
    Because the bit-exact replay goes through the loaded server, each
    fused point PROVES its stream equals the N=1 greedy oracle before
    any stand-in cost is armed.  Headline pair: tokens_per_sec_per_slot
    at N vs 1, and `dispatches_per_token` <= 1/N·(1+eps)."""
    from paddle_tpu.serving import (InferenceServer, ServingClient,
                                    set_dispatch_delay, set_draft_delay,
                                    set_host_delay)
    vocab = 64
    workdir = tempfile.mkdtemp(prefix="bench_serving_decode_")
    model_dir = build_decode_model(os.path.join(workdir, "lm"))
    modes = {"cb": ["cb"], "static": ["static"],
             "both": ["static", "cb"]}[args.decode_mode]
    spec_points = [int(s) for s in args.spec_k.split(",")
                   if s.strip() != ""] if args.spec_k else [0]
    # fused-decode sweep (SERVING.md "Fused multi-step decode"): one
    # fresh server per window so the amortization curve is honest
    fuse_points = [int(s) for s in args.fuse_steps.split(",")
                   if s.strip() != ""] if args.fuse_steps else [None]
    # KV-cache dtype A/B (QUANTIZE.md "Quantized KV cache"): one fresh
    # server per cache dtype, identical seeded workloads — the ratio
    # columns read the 4x cache-byte cut directly
    kv_points = {"fp32": ["float32"], "int8": ["int8"],
                 "both": ["float32", "int8"]}[args.kv_dtype]
    top1_agreement = _kv_top1_agreement(model_dir, seed=11,
                                        vocab=vocab) \
        if "int8" in kv_points else None
    # closed-form slot-table bytes per cache dtype (the static half of
    # the <= 0.27x acceptance ratio; measured comes from server stats)
    from paddle_tpu.inference.decode import GenerativePredictor
    _kv_closed = {kv: GenerativePredictor(
        model_dir, kv_cache_dtype=kv).kv_cache_bytes
        for kv in set(kv_points) | {"float32"}}
    draft_cost_ms = args.draft_cost_ms if args.draft_cost_ms is not None \
        else 0.3 * args.step_cost_ms
    qps_points = [float(q) for q in args.qps.split(",") if q] \
        if args.qps else [8.0]
    duration = 6.0 if args.duration is None else args.duration
    for mode in modes:
        for spec_k, kv_dtype, fuse in [(s, kv, f) for s in spec_points
                                       for kv in kv_points
                                       for f in fuse_points]:
            server = InferenceServer(max_queue=args.max_queue).start()
            boot = ServingClient(server.endpoint)
            try:
                t_boot = time.monotonic()
                draft_dir = None
                if spec_k > 0:
                    draft_dir = model_dir if args.spec_draft == "twin" \
                        else args.spec_draft
                loaded = boot.load_model(
                    "lm", model_dir, decode_slots=args.decode_slots,
                    decode_mode="static" if mode == "static" else None,
                    draft=draft_dir, spec_k=spec_k if draft_dir else 0,
                    kv_cache_dtype=kv_dtype,
                    fuse_steps=fuse,
                    replicas=args.replicas
                    if not args.replicas.isdigit()
                    or args.replicas != "1"
                    else None)
                # idle-server TTFT (loaded + warm, zero traffic): the
                # baseline the under-load TTFT p95 bound compares with
                idle_ttft = _measure_idle_ttft(server.endpoint, "lm",
                                               vocab)
                cold_start_ms = round(
                    (time.monotonic() - t_boot) * 1e3, 1)
                bit_exact = _verify_decode_bit_exact(
                    server.endpoint, "lm", model_dir, seed=11,
                    vocab=vocab, kv_cache_dtype=kv_dtype)
                if args.step_cost_ms:
                    # after the bit-exact replay and idle-TTFT
                    # baseline: the stand-in slows steps, not
                    # correctness
                    set_dispatch_delay(args.step_cost_ms / 1000.0)
                    if spec_k > 0:
                        set_draft_delay(draft_cost_ms / 1000.0)
                if args.host_cost_ms:
                    # per-DISPATCH host cost: the round-trip the fused
                    # window amortizes (charged once per dispatch
                    # regardless of trips)
                    set_host_delay(args.host_cost_ms / 1000.0)
                for q in qps_points:
                    rec = run_decode_point(
                        server.endpoint, "lm", vocab, target_qps=q,
                        duration=duration,
                        deadline_ms=args.deadline_ms, seed=3)
                    stats = boot.stats()["stats"]["models"].get(
                        "lm", {})
                    n_rep = int(loaded.get("replicas", 1))
                    slots_total = int(loaded.get("decode_slots", 0)) \
                        * n_rep
                    slots_per = int(loaded.get("decode_slots",
                                               args.decode_slots))
                    kv_static = _kv_closed[kv_dtype](slots_per) * n_rep
                    kv_fp32_static = _kv_closed["float32"](
                        slots_per) * n_rep
                    rec.update({
                        "model": "tiny_lm",
                        "mode": mode,
                        "step_cost_ms": args.step_cost_ms,
                        "decode_slots": int(
                            loaded.get("decode_slots", 0)),
                        "replicas": int(loaded.get("replicas", 1)),
                        "idle_ttft_ms": idle_ttft,
                        "ttft_ratio_vs_idle": round(
                            rec["ttft_p95_ms"] / idle_ttft, 3)
                        if rec.get("ttft_p95_ms") and idle_ttft
                        else None,
                        "bit_exact": bool(bit_exact),
                        "cold_start_ms": cold_start_ms,
                        "slot_occupancy": stats.get("slot_occupancy"),
                        "decode_steps": stats.get("decode_steps"),
                        # fused-decode columns (SERVING.md "Fused
                        # multi-step decode"): the dispatch-
                        # amortization headline pair
                        "fuse_steps": int(loaded.get("fuse_steps", 1)),
                        "host_cost_ms": args.host_cost_ms,
                        "decode_dispatches": stats.get(
                            "decode_dispatches"),
                        "tokens_per_dispatch": round(
                            stats.get("decode_tokens", 0)
                            / float(stats["decode_dispatches"]), 3)
                        if stats.get("decode_dispatches") else None,
                        "dispatches_per_token": round(
                            stats["decode_dispatches"]
                            / float(stats["decode_tokens"]), 4)
                        if stats.get("decode_dispatches")
                        and stats.get("decode_tokens") else None,
                        "server_tokens_per_sec": stats.get(
                            "tokens_per_sec"),
                        "compile_cache": loaded.get(
                            "compile_cache", {}),
                        "len_mix": [list(m) for m in DECODE_LEN_MIX],
                        # speculative-decoding columns: the accept-rate
                        # x speedup table keys on these (BENCH_r12)
                        "spec_k": spec_k,
                        "draft": draft_dir,
                        "draft_cost_ms": draft_cost_ms
                        if spec_k else 0.0,
                        # quantized-KV-cache columns (QUANTIZE.md):
                        # static closed form + the MEASURED slot-table
                        # bytes from stats, both ratioed against the
                        # fp32 closed form at equal slots
                        "kv_cache_dtype": loaded.get("kv_cache_dtype"),
                        "kv_cache_bytes_static": kv_static,
                        "kv_cache_bytes": stats.get("kv_cache_bytes"),
                        "kv_bytes_ratio_vs_fp32": round(
                            kv_static / kv_fp32_static, 4)
                        if kv_fp32_static else None,
                        "kv_measured_ratio_vs_fp32": round(
                            stats.get("kv_cache_bytes", 0)
                            / kv_fp32_static, 4)
                        if kv_fp32_static
                        and stats.get("kv_cache_bytes") else None,
                        "kv_top1_agreement": top1_agreement
                        if kv_dtype == "int8" else None,
                        "tokens_per_sec_per_slot": round(
                            rec["tokens_per_sec"] / slots_total, 3)
                        if slots_total else None,
                        "accept_rate": stats.get("spec_accept_rate"),
                        "spec_rounds": stats.get("spec_rounds"),
                        "spec_degraded": stats.get("spec_degraded", 0),
                    })
                    rec.update(device)
                    print(json.dumps(rec), flush=True)
            finally:
                set_dispatch_delay(0.0)
                set_draft_delay(0.0)
                set_host_delay(0.0)
                boot.close()
                server.shutdown(drain=True)


def _fleet_drive(endpoint, model, feed_name, shape, dtype, qps,
                 duration, deadline_ms):
    """Open-loop burst on one model: fire `qps*duration` requests on
    schedule, account every one exactly once.  Returns ok/dropped
    counts, latency percentiles, and the FIRST request's reply latency
    (the fault-in TTFR when the model was paged)."""
    from paddle_tpu.serving import (DeadlineExceeded, ServerOverloaded,
                                    ServingClient, ServingError)
    k = max(int(round(qps * duration)), 1)
    x = np.zeros((1,) + shape, dtype=dtype)
    results = [None] * k
    threads = []

    def fire(i):
        cli = ServingClient(endpoint)
        time.sleep(i / qps)
        t0 = time.monotonic()
        try:
            cli.infer(model, {feed_name: x}, deadline_ms=deadline_ms)
            results[i] = ("ok", (time.monotonic() - t0) * 1e3)
        except (ServerOverloaded, DeadlineExceeded, ServingError,
                ConnectionError, OSError, EOFError) as e:
            results[i] = ("fail", type(e).__name__)
        finally:
            cli.close()

    for i in range(k):
        t = threading.Thread(target=fire, args=(i,), daemon=True)
        threads.append(t)
        t.start()
    for t in threads:
        t.join(timeout=120)
    oks = [r[1] for r in results if r and r[0] == "ok"]
    lat = sorted(oks)

    def pct(q):
        if not lat:
            return None
        return round(lat[min(int(q / 100.0 * (len(lat) - 1)),
                             len(lat) - 1)], 1)

    return {"sent": k, "ok": len(oks), "dropped": k - len(oks),
            "p50_ms": pct(50), "p95_ms": pct(95),
            "ttfr_ms": round(oks[0], 1) if oks else None}


def run_fleet_lane(args, device):
    """The fleet-controller A/B (SERVING.md "Fleet controller"): the
    SAME shifting-traffic schedule — warm two models, idle the cold
    one past its page TTL, then flash-crowd it — once with the
    controller on (pages out, faults in, scales within [1,3]) and once
    with the static placement.  Per phase the record carries achieved
    ok/dropped/p95 per model, plus the fault-in time-to-first-reply
    and the server-measured fault_in_ms for the controller run
    (BENCH_r15.json)."""
    from paddle_tpu.flags import set_flags
    from paddle_tpu.obs import events as obs_events
    from paddle_tpu.serving import (InferenceServer, ServingClient,
                                    set_dispatch_delay)
    workdir = tempfile.mkdtemp(prefix="bench_fleet_")
    hot_dir, feed_name, shape, dtype = build_model(
        "fc", os.path.join(workdir, "hot"), seed=17)
    cold_dir, _, _, _ = build_model(
        "fc", os.path.join(workdir, "cold"), seed=29)
    step_ms = args.dispatch_cost_ms or 50.0
    lane_qps = 1000.0 / step_ms          # one replica's capacity
    flash_qps = 3.0 * lane_qps           # past one lane, at three
    flash_s = args.duration if args.duration is not None \
        else (1.0 if args.smoke else 2.0)
    warm_s = min(flash_s, 2.0)
    page_ttl_s = 0.6 if args.smoke else 1.0
    deadline_ms = args.deadline_ms or 2500.0
    modes = {"on": (True,), "off": (False,),
             "both": (True, False)}[args.fleet]

    for fleet_on in modes:
        set_flags({
            "fleet_controller": bool(fleet_on),
            "fleet_eval_interval_ms": 100.0,
            "slo_monitor": True,
            "slo_eval_interval_ms": 100.0,
            "serving_slo": (("cold:p95_ms=%d,budget=0.2,fast_window=3,"
                             "slow_window=10,fast_burn=5,"
                             "breach_evals=2,recover_evals=2"
                             % int(4 * step_ms)) if fleet_on else ""),
        })
        server = InferenceServer(max_queue=args.max_queue or 24,
                                 buckets=[1]).start()
        cli = ServingClient(server.endpoint)
        rec = {"metric": "serving_fleet",
               "fleet": "on" if fleet_on else "off",
               "step_cost_ms": step_ms, "flash_qps": flash_qps,
               "deadline_ms": deadline_ms, "phases": {}}
        try:
            cli.load_model("hot", hot_dir, buckets=[1])
            cli.load_model(
                "cold", cold_dir, buckets=[1],
                fleet_policy=("min_replicas=1,max_replicas=3,"
                              "page_ttl_s=%g,page_cooldown_s=0.5,"
                              "scale_up_queue=3,scale_cooldown_s=0.4,"
                              "scale_down_idle_s=60" % page_ttl_s)
                if fleet_on else None)
            ref = cli.infer("cold",
                            {feed_name: np.zeros((1,) + shape,
                                                 dtype=dtype)},
                            deadline_ms=10000)
            set_dispatch_delay(step_ms / 1000.0)
            # phase 1 — diurnal warm: both models lightly loaded
            rec["phases"]["warm"] = {
                "hot": _fleet_drive(server.endpoint, "hot", feed_name,
                                    shape, dtype, 0.3 * lane_qps,
                                    warm_s, deadline_ms),
                "cold": _fleet_drive(server.endpoint, "cold",
                                     feed_name, shape, dtype,
                                     0.2 * lane_qps, warm_s,
                                     deadline_ms)}
            # phase 2 — idle: hot-only traffic; with the controller on
            # the cold model pages out past its TTL
            t0 = time.monotonic()
            idle = _fleet_drive(server.endpoint, "hot", feed_name,
                                shape, dtype, 0.3 * lane_qps,
                                page_ttl_s + 1.0, deadline_ms)
            while fleet_on and time.monotonic() - t0 < 8.0 \
                    and not server.registry.paged_models():
                time.sleep(0.05)
            idle["cold_paged"] = bool(server.registry.paged_models())
            rec["phases"]["idle"] = {"hot": idle}
            # phase 3 — flash crowd on the (possibly paged) cold model
            flash = _fleet_drive(server.endpoint, "cold", feed_name,
                                 shape, dtype, flash_qps, flash_s,
                                 deadline_ms)
            rec["phases"]["flash"] = {"cold": flash}
            rec["flash_ttfr_ms"] = flash.get("ttfr_ms")
            rec["dropped"] = flash["dropped"]
            stats = cli.stats()["stats"]["models"]
            rec["shed_total"] = sum(
                (m.get("shed") or 0) for m in stats.values())
            if fleet_on:
                fi = server.registry.last_fault_in.get("cold") or {}
                rec["fault_in_ms"] = fi.get("ms")
                rec["scale_ups"] = len(
                    obs_events.recent_events(kind="fleet_scale_up"))
                rec["paged_out"] = bool(
                    obs_events.recent_events(kind="fleet_paged_out"))
                fleet_status = cli.fleet()
                rec["fleet_models"] = sorted(fleet_status["models"])
            # replies stay bit-exact through page/fault/scale
            set_dispatch_delay(0.0)
            out = cli.infer("cold",
                            {feed_name: np.zeros((1,) + shape,
                                                 dtype=dtype)},
                            deadline_ms=10000)
            rec["bit_exact"] = bool(np.array_equal(out[0], ref[0]))
        finally:
            set_dispatch_delay(0.0)
            try:
                cli.close()
            finally:
                server.shutdown(drain=False, timeout=5.0)
        rec.update(device)
        print(json.dumps(rec), flush=True)


def _wave_drive(endpoint, model, feed_name, shape, dtype, wave,
                interval, waves, deadline_ms):
    """Flash-crowd driver: `waves` bursts of `wave` SIMULTANEOUS
    requests, `interval` seconds apart, NO client-side shed retries —
    every request is answered exactly once or definitively dropped
    (shed / deadline / transport), so `ok` measures ADMISSION under
    arrival spikes: a single server takes at most queue+lanes of a
    wave and sheds the rest, the federation spreads the same wave
    across N queues via least-loaded placement + spillover at equal
    aggregate compute."""
    from paddle_tpu.serving import (DeadlineExceeded, ServerOverloaded,
                                    ServingClient, ServingError)
    k = wave * waves
    x = np.zeros((1,) + shape, dtype=dtype)
    results = [None] * k
    threads = []

    def fire(i):
        cli = ServingClient(endpoint)
        time.sleep((i // wave) * interval)
        t0 = time.monotonic()
        try:
            cli.infer(model, {feed_name: x}, deadline_ms=deadline_ms,
                      retry_sheds=False)
            results[i] = ("ok", (time.monotonic() - t0) * 1e3)
        except ServerOverloaded:
            results[i] = ("shed", None)
        except DeadlineExceeded:
            results[i] = ("deadline", None)
        except (ServingError, ConnectionError, OSError, EOFError):
            results[i] = ("conn", None)
        finally:
            cli.close()

    for i in range(k):
        t = threading.Thread(target=fire, args=(i,), daemon=True)
        threads.append(t)
        t.start()
    for t in threads:
        t.join(timeout=120)
    oks = sorted(r[1] for r in results if r and r[0] == "ok")
    outcomes = {}
    for r in results:
        key = r[0] if r else "lost"
        outcomes[key] = outcomes.get(key, 0) + 1

    def pct(q):
        if not oks:
            return None
        return round(oks[min(int(q / 100.0 * (len(oks) - 1)),
                             len(oks) - 1)], 1)

    first = [r[1] for r in results if r and r[0] == "ok"]
    return {"sent": k, "ok": len(oks), "dropped": k - len(oks),
            "shed": outcomes.get("shed", 0),
            "deadline_expired": outcomes.get("deadline", 0),
            "conn_failed": outcomes.get("conn", 0),
            "p50_ms": pct(50), "p95_ms": pct(95),
            "ttfr_ms": round(first[0], 1) if first else None}


def _parse_topology(spec):
    """'1x4,2x2,4x1' -> [(1, 4), (2, 2), (4, 1)] — N backend servers x
    R replicas each; every point spends the same total replica
    budget."""
    points = []
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        n, _, r = part.lower().partition("x")
        points.append((int(n), int(r or 1)))
    if not points:
        raise ValueError("empty --topology spec %r" % (spec,))
    return points


def run_topology_lane(args, device):
    """Federated-serving topology sweep (SERVING.md "Federated
    serving"): the SAME total replica budget arranged as N backend
    servers x R replicas each — 1xR is the single-server static
    control (direct endpoint, no frontend); every N>1 point runs
    behind the front-door router with per-server leases.  Each point
    takes the same open-loop flash crowd against deliberately small
    per-server admission queues: the federated shapes hold N queues
    plus cross-server spillover where the static control sheds into
    client retry deadlines, so `ok` — answered exactly once, routing
    bit-exact — is the headline number (BENCH_r17.json)."""
    from paddle_tpu.federation import FrontendServer
    from paddle_tpu.flags import set_flags
    from paddle_tpu.serving import (InferenceServer, ServingClient,
                                    set_dispatch_delay)
    workdir = tempfile.mkdtemp(prefix="bench_fed_")
    model_dir, feed_name, shape, dtype = build_model(
        "fc", os.path.join(workdir, "m"), seed=17)
    step_ms = args.dispatch_cost_ms or 25.0
    lane_qps = 1000.0 / step_ms
    duration = args.duration if args.duration is not None \
        else (1.0 if args.smoke else 1.5)
    deadline_ms = args.deadline_ms or 1500.0
    queue_per = args.max_queue or 6
    set_flags({"federation_heartbeat_ms": 150.0})

    for n_srv, n_rep in _parse_topology(args.topology):
        total = n_srv * n_rep
        fe, boot, servers = None, None, []
        rec = {"metric": "serving_federation",
               "topology": "%dx%d" % (n_srv, n_rep),
               "servers": n_srv, "replicas_per_server": n_rep,
               "total_replicas": total, "federated": n_srv > 1,
               "step_cost_ms": step_ms,
               "max_queue_per_server": queue_per,
               "deadline_ms": deadline_ms}
        try:
            if n_srv > 1:
                fe = FrontendServer(ttl_s=2.0).start()
            for i in range(n_srv):
                servers.append(InferenceServer(
                    max_queue=queue_per, buckets=[1],
                    federation=fe.endpoint if fe else None,
                    backend_id="b%02d" % i).start())
            endpoint = fe.endpoint if fe else servers[0].endpoint
            boot = ServingClient(endpoint)
            if fe is not None:
                t0 = time.monotonic()
                while (time.monotonic() - t0 < 30.0
                       and len(fe.membership.backends(
                           accepting_only=True)) < n_srv):
                    time.sleep(0.02)
            boot.load_model("m", model_dir, buckets=[1],
                            replicas=n_rep)  # fans out when federated
            warm = np.zeros((1,) + shape, dtype=dtype)
            boot.infer("m", {feed_name: warm}, deadline_ms=60000.0)
            # routing through the relay must not change one bit —
            # checked before the dispatch-cost stand-in arms
            rec["bit_exact"] = bool(_verify_bit_exact(
                endpoint, "m", model_dir, [1], feed_name, shape,
                dtype))
            set_dispatch_delay(step_ms / 1000.0)
            # flash crowd: simultaneous waves sized past ONE server's
            # admission (queue + lanes) but under the aggregate
            # compute — arrival rate at 80% of total capacity, so
            # what drops is admission, not capacity
            total_qps = total * lane_qps
            wave = 24
            interval = wave / (0.8 * total_qps)
            waves = max(int(round(duration / interval)), 1)
            burst = _wave_drive(endpoint, "m", feed_name, shape,
                                dtype, wave, interval, waves,
                                deadline_ms)
            set_dispatch_delay(0.0)
            rec.update(burst)
            rec["wave"] = wave
            rec["wave_interval_ms"] = round(interval * 1e3, 1)
            rec["target_qps"] = round(wave / interval, 1)
            rec["answered_rate"] = round(
                burst["ok"] / float(burst["sent"]), 4)
            if fe is not None:
                rec["spillover"] = fe._counters["spillover"]
                rec["frontend_shed"] = fe._counters["shed"]
                rec["placed"] = dict(fe._placed)
        finally:
            set_dispatch_delay(0.0)
            if boot is not None:
                boot.close()
            for s in servers:
                s.shutdown(drain=False, timeout=5.0)
            if fe is not None:
                fe.shutdown()
        rec.update(device)
        print(json.dumps(rec), flush=True)


def run_mesh_lane(args, device):
    """Mesh-replica sweep (SERVING.md "Mesh replicas"): `--mesh 1,2,4`
    serves the SAME decode workload from one replica built as an
    m-chip device mesh per point — params and the KV slot table
    sharded across the members, compute replicated, so every point's
    streams must be BIT-EXACT vs the single-device greedy oracle
    (checked per point, before any throughput number is read).  Fresh
    server per point.

    The headline is the FIT column pair, not the QPS column: the
    static per-member estimate (`est_per_device_mb`, what the
    admission gate prices each member chip at) drops ~1/m while the
    whole-model estimate stays flat — the axis along which a model too
    big for any single chip admits on a mesh.  `fit_headroom_mb` is
    budget − per-member estimate when a device budget is known
    (FLAGS.serving_device_mem_mb, or the chip's HBM on recognized
    TPUs; None on unconfigured CPU smoke).  QPS on the CPU smoke lane
    reads scheduling overhead only — mesh points pay XLA's
    cross-device collectives for no compute win on a host core;
    on silicon, where the sharded weights actually buy HBM: not
    measured.

    `--mesh_tp on|off|both` (SERVING.md "Tensor-parallel compute")
    A/Bs the compute mode per mesh point: off = PR 18's gather-and-
    replicate (every member streams the whole model per step), on =
    the shard_map'd partitioned program (each member streams ~1/m).
    Each record carries the MODELED per-member step traffic
    (`step_bytes_per_member`, ResourceReport.per_device_step_bytes)
    and its ratio vs gather mode; with `--step_cost_ms` the stand-in
    per-dispatch device cost is scaled by that ratio, so the CPU-smoke
    QPS curve shows the bandwidth win the model predicts for silicon.
    Streams stay token-identical to the single-device oracle in BOTH
    modes (TP's top-1 contract)."""
    import jax
    from paddle_tpu.analysis.resources import (analyze_artifact,
                                               device_memory_bytes)
    from paddle_tpu.flags import set_flags
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             greedy_decode)
    from paddle_tpu.serving import (InferenceServer, ServingClient,
                                    set_dispatch_delay)

    if args.device_mem_mb > 0:
        set_flags({"serving_device_mem_mb": int(args.device_mem_mb)})

    workdir = tempfile.mkdtemp(prefix="bench_serving_mesh_")
    model_dir = build_decode_model(os.path.join(workdir, "lm"))
    budget = 24
    rng = random.Random(41)
    prompts = [[rng.randrange(1, 60) for _ in range(rng.randrange(2, 8))]
               for _ in range(8)]
    oracle = GenerativePredictor(model_dir)
    refs = [greedy_decode(oracle, p, budget)[0] for p in prompts]
    points = [int(p) for p in str(args.mesh).split(",") if p.strip()]
    devs = jax.devices()
    n_streams = len(prompts)
    tp_modes = {"off": (False,), "on": (True,),
                "both": (False, True)}[args.mesh_tp]

    for m in points:
        if m < 1 or m > len(devs):
            # no silent caps: a skipped point is announced, not dropped
            print(json.dumps({"metric": "serving_mesh", "mesh": m,
                              "skipped": "host has %d device(s)"
                              % len(devs)}), flush=True)
            continue
        spec = "+".join("%s:%d" % (d.platform, d.id) for d in devs[:m])
        for tp_on in tp_modes:
            if tp_on and m < 2:
                # TP needs members to split over — announced, not
                # silently folded into the gather point
                print(json.dumps({"metric": "serving_mesh", "mesh": m,
                                  "mesh_tp": True,
                                  "skipped": "tp needs mesh >= 2"}),
                      flush=True)
                continue
            _run_mesh_point(args, device, model_dir, m, spec,
                            tp_on, prompts, refs, budget, devs,
                            set_flags, set_dispatch_delay,
                            analyze_artifact, device_memory_bytes,
                            InferenceServer, ServingClient)
    set_flags({"mesh_tp": False})


def _run_mesh_point(args, device, model_dir, m, spec, tp_on,
                    prompts, refs, budget, devs, set_flags,
                    set_dispatch_delay, analyze_artifact,
                    device_memory_bytes, InferenceServer,
                    ServingClient):
    """One (mesh size, compute mode) point of the mesh sweep: fresh
    server, oracle-exact streams, fit + modeled-traffic columns."""
    n_streams = len(prompts)
    set_flags({"mesh_tp": bool(tp_on)})
    # the modeled per-member decode traffic (ROOFLINE.md): gather mode
    # streams the whole model per member per step, TP streams ~1/m —
    # the ratio also scales the --step_cost_ms stand-in so the smoke
    # QPS curve shows the predicted bandwidth win
    rep = analyze_artifact(model_dir, decode_slots=args.decode_slots,
                           mesh_size=m, tp=tp_on)
    gather_bytes = rep.per_device_step_bytes(m, tp=False)
    member_bytes = rep.per_device_step_bytes(m, tp=tp_on)
    ratio = member_bytes / float(max(gather_bytes, 1))
    server = InferenceServer().start()
    cli = ServingClient(server.endpoint)
    rec = {"metric": "serving_mesh", "mesh": m, "devices": spec,
           "mesh_tp": bool(tp_on), "replicas": 1,
           "streams": n_streams, "max_new_tokens": budget,
           "step_bytes_per_member": int(member_bytes),
           "step_bytes_gather": int(gather_bytes),
           "step_bytes_ratio_vs_gather": round(ratio, 4)}
    if args.step_cost_ms:
        rec["step_cost_ms"] = round(args.step_cost_ms * ratio, 4)
        set_dispatch_delay(args.step_cost_ms * ratio / 1000.0)
    try:
        t0 = time.monotonic()
        loaded = cli.load_model(
            "lm", model_dir, replicas=spec,
            decode_slots=args.decode_slots,
            kv_cache_dtype=None if args.kv_dtype == "fp32"
            else "int8" if args.kv_dtype == "int8" else None)
        rec["cold_start_ms"] = round(
            (time.monotonic() - t0) * 1e3, 1)
        rec["resolved_mesh"] = loaded.get("mesh", [1])
        outs = [None] * n_streams
        errs = []

        def drive(i):
            c = ServingClient(server.endpoint)
            try:
                outs[i] = [t for ch in c.infer_stream(
                    "lm", prompts[i], max_new_tokens=budget,
                    deadline_ms=120000.0) for t in ch]
            except Exception as e:
                errs.append(e)
            finally:
                c.close()

        t0 = time.monotonic()
        threads = [threading.Thread(target=drive, args=(i,))
                   for i in range(n_streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        wall = time.monotonic() - t0
        assert not errs, "mesh=%d streams failed: %r" % (m, errs[:2])
        rec["wall_s"] = round(wall, 3)
        rec["qps"] = round(n_streams / wall, 2)
        rec["tokens_per_sec"] = round(
            n_streams * budget / wall, 1)
        # every point replays against the single-device oracle:
        # sharding must never move one token
        rec["bit_exact"] = bool(
            all(outs[i] == refs[i] for i in range(n_streams)))
        # the fit columns: whole-model vs per-member pricing
        d = cli.stats()["models"]["lm"]
        rec["est_peak_mb"] = d.get("est_peak_mb")
        rec["est_per_device_mb"] = d.get(
            "est_per_device_mb", d.get("est_peak_mb"))
        # what the server actually built: True only when the flag AND
        # the TP grammar both admitted the model
        rec["mesh_tp_active"] = bool(d.get("mesh_tp", False))
        avail = device_memory_bytes(devs[0])
        if avail is not None and rec["est_per_device_mb"]:
            rec["device_budget_mb"] = round(avail / float(1 << 20), 1)
            rec["fit_headroom_mb"] = round(
                rec["device_budget_mb"] - rec["est_per_device_mb"],
                3)
        else:
            rec["device_budget_mb"] = None
            rec["fit_headroom_mb"] = None
    finally:
        set_dispatch_delay(0.0)
        cli.close()
        server.shutdown(drain=False, timeout=10.0)
    rec.update(device)
    print(json.dumps(rec), flush=True)


def _parse_replica_sweep(spec):
    """'1,4' -> sweep of counts; 'auto' / '4' / 'cpu:0,cpu:1' -> one
    placement spec point (a comma list containing ':' is a device list,
    not a sweep)."""
    parts = [p.strip() for p in spec.split(",") if p.strip()]
    if len(parts) > 1 and all(p.isdigit() or p == "auto" for p in parts):
        return parts
    return [spec.strip()]


def _verify_bit_exact(endpoint, model, model_dir, buckets, feed_name,
                      shape, dtype, n=3, seed=123):
    """Replay `n` random requests through the served replica set and
    against a direct in-process Predictor.run on the same artifact —
    routing across device-placed replicas must not change one bit."""
    from paddle_tpu.inference import AnalysisConfig, Predictor
    from paddle_tpu.serving import ServingClient
    cfg = AnalysisConfig(model_dir=model_dir)
    cfg.batch_size_buckets = tuple(buckets)
    direct = Predictor(cfg)
    rng = np.random.RandomState(seed)
    cli = ServingClient(endpoint)
    try:
        for i in range(n):
            x = rng.randn(1 + i % buckets[0], *shape).astype(dtype)
            served = cli.infer(model, {feed_name: x},
                               deadline_ms=60000.0)
            ref = direct.run({feed_name: x})
            if len(served) != len(ref) or any(
                    not np.array_equal(a, b)
                    for a, b in zip(served, ref)):
                return False
        return True
    finally:
        cli.close()


# ---------------------------------------------------------------------------
# quantized A/B lanes (QUANTIZE.md): one server, both numerics lanes of
# ONE model name (fp32 + the PTQ int8 sibling), identical seeded
# open-loop workloads routed per-request by the `precision` field.  The
# roofline argument says int8 weight bytes are the speedup on a memory-
# bound chip; on CPU smoke the lanes mostly prove the axis end to end
# (routing, per-precision metrics, bit-stability, pinned accuracy
# delta); throughput on silicon: not measured.
# ---------------------------------------------------------------------------


def _verify_precision_lanes(endpoint, model, model_dir, buckets,
                            feed_name, shape, dtype, lanes, n=3,
                            seed=321):
    """Per-lane bit-stability + the pinned accuracy delta: each lane
    must answer the SAME request bit-identically every time (replay
    twice), and the int8 lane's outputs must sit within a small delta
    of the served fp32 lane / the direct fp32 Predictor."""
    from paddle_tpu.inference import AnalysisConfig, Predictor
    from paddle_tpu.serving import ServingClient
    cfg = AnalysisConfig(model_dir=model_dir)
    cfg.batch_size_buckets = tuple(buckets)
    direct = Predictor(cfg)
    rng = np.random.RandomState(seed)
    cli = ServingClient(endpoint)
    out = {"bit_stable": {lane: True for lane in lanes},
           "max_abs_delta": 0.0, "top1_agreement": None}
    agree, total = 0, 0
    try:
        for i in range(n):
            x = rng.randn(1 + i % buckets[0], *shape).astype(dtype)
            ref = direct.run({feed_name: x})
            per_lane = {}
            for lane in lanes:
                a = cli.infer(model, {feed_name: x}, precision=lane,
                              deadline_ms=60000.0)
                b = cli.infer(model, {feed_name: x}, precision=lane,
                              deadline_ms=60000.0)
                if any(not np.array_equal(u, v) for u, v in zip(a, b)):
                    out["bit_stable"][lane] = False
                per_lane[lane] = a
            if "fp32" in per_lane and any(
                    not np.array_equal(u, v)
                    for u, v in zip(per_lane["fp32"], ref)):
                out["bit_stable"]["fp32"] = False
            if "int8" in per_lane:
                for u, v in zip(per_lane["int8"], ref):
                    u = np.asarray(u, np.float32)
                    v = np.asarray(v, np.float32)
                    out["max_abs_delta"] = max(
                        out["max_abs_delta"],
                        float(np.abs(u - v).max()) if u.size else 0.0)
                    if u.ndim == 2 and u.shape[1] > 1:
                        agree += int((u.argmax(1) == v.argmax(1)).sum())
                        total += u.shape[0]
        if total:
            out["top1_agreement"] = round(agree / total, 4)
        out["max_abs_delta"] = round(out["max_abs_delta"], 6)
        return out
    finally:
        cli.close()


def run_precision_lanes(args, device, kind, qps_points, duration,
                        buckets):
    """The --precision entry point: export the fp32 artifact, PTQ it
    into the int8 sibling, load both lanes behind ONE model name, and
    drive identical seeded open-loop sweeps through each requested
    lane.  One JSON record per (precision, qps) point."""
    from paddle_tpu.inference import (quantize_inference_model,
                                      read_quant_meta)
    from paddle_tpu.serving import InferenceServer, ServingClient
    lanes = {"fp32": ["fp32"], "int8": ["int8"],
             "both": ["fp32", "int8"]}[args.precision]
    workdir = tempfile.mkdtemp(prefix="bench_serving_quant_")
    model_dir, feed_name, shape, dtype = build_model(
        kind, os.path.join(workdir, kind))
    rng = np.random.RandomState(17)
    calib = [{feed_name: rng.randn(buckets[0], *shape).astype(dtype)}
             for _ in range(4)]
    summary = quantize_inference_model(model_dir, calib_feeds=calib,
                                       min_weight_elems=64)
    qmeta = read_quant_meta(summary["dst"])

    server = InferenceServer(max_queue=args.max_queue,
                             deadline_ms=args.deadline_batch_ms,
                             buckets=buckets).start()
    boot = ServingClient(server.endpoint)
    try:
        loaded = {}
        t0 = time.monotonic()
        loaded["fp32"] = boot.load_model(kind, model_dir,
                                         buckets=buckets)
        t1 = time.monotonic()
        loaded["int8"] = boot.load_model(kind, summary["dst"],
                                         buckets=buckets)
        load_ms = {"fp32": round((t1 - t0) * 1e3, 1),
                   "int8": round((time.monotonic() - t1) * 1e3, 1)}
        checks = _verify_precision_lanes(
            server.endpoint, kind, model_dir, buckets, feed_name,
            shape, dtype, lanes)
        for lane in lanes:
            for q in qps_points:
                rec = run_point(server.endpoint, kind, feed_name,
                                shape, dtype, target_qps=q,
                                duration=duration,
                                req_batch=args.req_batch,
                                deadline_ms=args.deadline_ms,
                                precision=lane)
                stats = boot.stats()["stats"]["models"]
                lane_key = kind if lane == "fp32" \
                    else "%s@%s" % (kind, lane)
                lane_stats = stats.get(lane_key, {})
                rec.update({
                    "model": kind,
                    "precision": lane,
                    "buckets": buckets,
                    "bit_stable": checks["bit_stable"].get(lane),
                    "accuracy_delta": {
                        "max_abs": checks["max_abs_delta"],
                        "top1_agreement": checks["top1_agreement"],
                        "calibration": dict(
                            qmeta.get("calibration", {})),
                    } if lane == "int8" else None,
                    "quant_bytes": dict(qmeta.get("bytes", {})),
                    "load_ms": load_ms.get(lane),
                    "compile_cache": dict(
                        loaded[lane].get("compile_cache", {})),
                    "lane_requests": lane_stats.get("requests"),
                    "lane_qps_recent": lane_stats.get("qps_recent"),
                    "lane_latency_p95":
                        (lane_stats.get("latency_ms") or {}).get("p95"),
                })
                rec.update(device)
                print(json.dumps(rec), flush=True)
    finally:
        boot.close()
        server.shutdown(drain=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="fc",
                    choices=["fc", "fc_deep", "mnist", "resnet"])
    ap.add_argument("--qps", default=None,
                    help="comma-separated target-QPS sweep "
                         "(default 50,200; smoke default 100)")
    ap.add_argument("--duration", type=float, default=None,
                    help="seconds per QPS point (default 10, smoke 2)")
    ap.add_argument("--req_batch", type=int, default=1,
                    help="rows per client request (the batcher coalesces "
                         "across requests on top of this)")
    ap.add_argument("--max_bucket", type=int, default=None,
                    help="largest compiled batch bucket; the bucket set "
                         "is {max/4, max/2, max} (default 32, smoke 8)")
    ap.add_argument("--deadline_ms", type=float, default=None,
                    help="per-request deadline (default 2000; decode "
                         "lanes 60000 — the deadline now covers the "
                         "whole stream's decode time)")
    ap.add_argument("--precision", choices=["fp32", "int8", "both"],
                    default=None,
                    help="quantized A/B lane (QUANTIZE.md): PTQ the "
                         "exported model into an int8 sibling, load "
                         "BOTH numerics lanes behind one model name, "
                         "and drive identical seeded sweeps through "
                         "the requested lane(s) via the per-request "
                         "precision field; records carry per-lane "
                         "bit-stability, the pinned accuracy delta, "
                         "and the weight-bytes ratio")
    ap.add_argument("--decode", action="store_true",
                    help="streaming-generation lane: serve a tiny "
                         "decode artifact and drive open-loop Poisson "
                         "arrivals of mixed-output-length "
                         "infer_stream requests (SERVING.md "
                         "continuous batching)")
    ap.add_argument("--decode_mode", choices=["cb", "static", "both"],
                    default="cb",
                    help="cb = continuous batching (slots backfill "
                         "the step after they free), static = whole-"
                         "batch baseline (a lane admits only when "
                         "idle and decodes until its last member "
                         "finishes), both = A/B with identical "
                         "seeded workloads")
    ap.add_argument("--decode_slots", type=int, default=4,
                    help="slot-table size per replica lane "
                         "(FLAGS.serving_decode_slots override)")
    ap.add_argument("--kv_dtype", choices=["fp32", "int8", "both"],
                    default="fp32",
                    help="decode lane KV-cache dtype A/B (QUANTIZE.md "
                         "\"Quantized KV cache\"): fresh server per "
                         "dtype, identical seeded workloads; records "
                         "carry static+measured cache bytes vs fp32, "
                         "per-dtype bit-exact replay, and the "
                         "fp32-vs-int8 greedy top-1 agreement")
    ap.add_argument("--step_cost_ms", type=float, default=0.0,
                    help="deterministic per-decode-step stall in the "
                         "lane loop (GIL released — the same stand-in "
                         "discipline as --dispatch_cost_ms): makes the "
                         "cb-vs-static throughput ratio measurable on "
                         "a 1-core host by making capacity slot-bound; "
                         "a speculative VERIFY step costs exactly one "
                         "of these, like any target step")
    ap.add_argument("--fuse_steps", default=None,
                    help="fused multi-step decode sweep (SERVING.md "
                         "\"Fused multi-step decode\"): comma list of "
                         "per-dispatch window caps ('1,4,8'); each point "
                         "gets a fresh server with the batcher's "
                         "fuse_steps pinned, a per-point bit-exact "
                         "replay vs the N=1 greedy stream, and "
                         "dispatches/tokens-per-dispatch columns — "
                         "the host-floor amortization curve")
    ap.add_argument("--host_cost_ms", type=float, default=0.0,
                    help="deterministic per-DISPATCH host stall (GIL "
                         "released): the stand-in for the host-side "
                         "round-trip cost a fused window amortizes — "
                         "pair with --step_cost_ms to reproduce the "
                         "host-dominated regime where N-step fusion "
                         "buys ~N/(1+N·step/host) per-slot throughput")
    ap.add_argument("--spec_k", default=None,
                    help="speculative-decoding sweep: comma list of "
                         "draft depths ('0,2,4,8'); 0 = target-only "
                         "baseline, each point gets a fresh server and "
                         "a per-point bit-exact replay vs the "
                         "fp32-only greedy stream (SERVING.md)")
    ap.add_argument("--spec_draft", default="twin",
                    help="draft artifact for the spec sweep: 'twin' "
                         "(default) drafts with the SAME artifact — "
                         "the synthetic high-accept workload, accept "
                         "rate ~1.0 — or a path to any vocab-"
                         "compatible decode artifact (e.g. the int8 "
                         "sibling)")
    ap.add_argument("--draft_cost_ms", type=float, default=None,
                    help="deterministic per-DRAFT-step stall (GIL "
                         "released); default 0.3x --step_cost_ms — "
                         "the BENCH_r11 int8 weight-bytes ratio, i.e. "
                         "what the int8-twin draft costs on a "
                         "bandwidth-bound chip")
    ap.add_argument("--deadline_batch_ms", type=float, default=None,
                    help="batcher coalescing window override "
                         "(default FLAGS.serving_batch_deadline_ms)")
    ap.add_argument("--max_queue", type=int, default=None)
    ap.add_argument("--topology", default=None,
                    help="federated topology sweep 'NxR,...': N "
                         "backend servers x R replicas each behind "
                         "the front-door router (N=1 = single-server "
                         "static control, direct endpoint), same "
                         "total replica budget per point, one flash-"
                         "crowd burst each (SERVING.md 'Federated "
                         "serving', BENCH_r17.json)")
    ap.add_argument("--device_mem_mb", type=int, default=0,
                    help="per-device memory budget (MB) for the "
                         "admission fit check during the --mesh sweep "
                         "(sets FLAGS.serving_device_mem_mb; 0 keeps "
                         "the backend's own budget) — makes the "
                         "fit_headroom_mb column live on CPU smoke")
    ap.add_argument("--mesh", default=None,
                    help="mesh-replica sweep (SERVING.md 'Mesh "
                         "replicas'): comma list of mesh sizes "
                         "('1,2,4') — each point serves one replica "
                         "built as an m-chip device mesh (params + KV "
                         "sharded) from a FRESH server, replays "
                         "bit-exact vs the single-device oracle, and "
                         "records the per-member fit estimate + "
                         "headroom (BENCH_r18.json)")
    ap.add_argument("--mesh_tp", choices=["on", "off", "both"],
                    default="off",
                    help="tensor-parallel A/B for the --mesh sweep "
                         "(SERVING.md 'Tensor-parallel compute'): "
                         "'on' runs each mesh point as the shard_"
                         "map'd partitioned program (~1/m per-member "
                         "step bytes), 'both' runs gather + TP per "
                         "point; records carry the modeled per-member "
                         "step traffic and scale --step_cost_ms by "
                         "the TP/gather byte ratio (BENCH_r20.json)")
    ap.add_argument("--replicas", default="1",
                    help="replica placement spec per point: a count, "
                         "'auto' (one replica per local device), an "
                         "explicit device list ('cpu:0,cpu:1'), or a "
                         "comma sweep of counts ('1,4') — each sweep "
                         "point gets a fresh server so the scaling "
                         "curve is honest")
    ap.add_argument("--force_host_devices", type=int, default=0,
                    help="split the CPU backend into N XLA host "
                         "devices (xla_force_host_platform_device_count"
                         ") so replica placement runs without silicon")
    ap.add_argument("--dispatch_cost_ms", type=float, default=0.0,
                    help="deterministic per-dispatch stall in the lane "
                         "worker (GIL released): the stand-in for "
                         "per-batch device time that makes the replica-"
                         "scaling ratio measurable on a 1-core host")
    ap.add_argument("--compile_cache_dir", default=None,
                    help="persistent compile-cache store root "
                         "(FLAGS.compile_cache_dir); point two runs at "
                         "the same dir for the cold/warm pair")
    ap.add_argument("--compile_cache", choices=["on", "off"],
                    default="on",
                    help="'off' disables the persistent compile cache "
                         "(the no-cache baseline)")
    ap.add_argument("--trace", choices=["on", "off"], default=None,
                    help="force FLAGS.trace for the run — the tracing-"
                         "overhead A/B pair (OBSERVABILITY.md pins "
                         "<3%% throughput delta on this smoke lane, "
                         "BENCH_r09.json)")
    ap.add_argument("--slo", choices=["on", "off"], default=None,
                    help="force the SLO monitor for the run: 'on' also "
                         "declares a default p95/error-rate SLO so the "
                         "monitor does real evaluation work — the "
                         "monitor-overhead A/B pair (<3%% delta "
                         "acceptance, BENCH_r13.json)")
    ap.add_argument("--fleet", choices=["on", "off", "both"],
                    default=None,
                    help="fleet-controller A/B (SERVING.md \"Fleet "
                         "controller\"): run the shifting-traffic "
                         "schedule — warm two models, idle the cold "
                         "one past its page TTL, flash-crowd it — "
                         "with the controller on and/or off; records "
                         "carry per-phase ok/dropped/p95, fault-in "
                         "TTFR + server-measured fault_in_ms, and "
                         "scale-up counts (BENCH_r15.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fc model, short sweep (CI path)")
    ap.add_argument("--chaos_proxy", action="store_true",
                    help="route through a FlakyProxy that kills the "
                         "first connection mid-flight (shed-not-hang "
                         "under transport chaos)")
    ap.add_argument("--chaos_slow_ms", type=float, default=0.0,
                    help="slow-worker injection: stall every dispatch "
                         "this many ms")
    args = ap.parse_args()

    if args.mesh and args.force_host_devices == 0:
        # the mesh sweep needs as many host devices as its widest
        # point; harmless on real TPU (the flag only splits CPU)
        args.force_host_devices = max(
            [4] + [int(p) for p in str(args.mesh).split(",")
                   if p.strip()])
    if args.force_host_devices > 0:
        # must land before jax backend init (init_backend below)
        import re
        flags = os.environ.get("XLA_FLAGS", "")
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       "", flags)
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d"
            % args.force_host_devices).strip()

    from bench import init_backend
    device = init_backend(smoke=args.smoke, tool="bench_serving")
    on_tpu = device["platform"] == "tpu"

    from paddle_tpu.flags import FLAGS, set_flags
    if args.compile_cache == "off":
        set_flags({"compile_cache": False})
    elif args.compile_cache_dir:
        set_flags({"compile_cache": True,
                   "compile_cache_dir": args.compile_cache_dir})
    if args.trace is not None:
        set_flags({"trace": args.trace == "on"})
    if args.slo is not None:
        if args.slo == "on":
            # a real SLO so every tick samples AND evaluates burn
            # windows — the honest monitor-ON configuration (targets
            # generous enough that the bench itself never breaches)
            set_flags({"slo_monitor": True,
                       "slo_eval_interval_ms": 250.0,
                       "serving_slo": "p95_ms=10000,error_rate=0.05"})
        else:
            set_flags({"slo_monitor": False, "serving_slo": ""})

    if args.mesh:
        run_mesh_lane(args, device)
        return
    if args.topology:
        run_topology_lane(args, device)
        return
    if args.fleet:
        run_fleet_lane(args, device)
        return
    if args.decode:
        if args.deadline_ms is None:
            args.deadline_ms = 60000.0
        run_decode_lane(args, device)
        return
    if args.deadline_ms is None:
        args.deadline_ms = 2000.0

    kind = args.model
    qps_points = [float(q) for q in args.qps.split(",") if q] \
        if args.qps else [50.0, 200.0]
    duration = 10.0 if args.duration is None else args.duration
    max_bucket = 32 if args.max_bucket is None else args.max_bucket
    if args.smoke or not on_tpu:
        # CPU path: tiny fc model, short points — proves the serving
        # path end-to-end, never mistakable for a chip number.
        # Explicit --qps/--duration/--max_bucket survive (the
        # multi-chip lanes drive their own small sweeps through the
        # smoke path); fc_deep stays — it is the CPU-safe compile-heavy
        # lane the compile-cache cold/warm pair is measured on
        if kind != "fc_deep":
            kind = "fc"
        if args.smoke and args.qps is None:
            qps_points = [100.0]
        if args.duration is None:
            duration = 2.0
        if args.max_bucket is None:
            max_bucket = 8

    buckets = sorted({max(max_bucket // 4, 1), max(max_bucket // 2, 1),
                      max_bucket})
    if args.precision:
        run_precision_lanes(args, device, kind, qps_points,
                            duration, buckets)
        return
    workdir = tempfile.mkdtemp(prefix="bench_serving_")
    model_dir, feed_name, shape, dtype = build_model(
        kind, os.path.join(workdir, kind))

    from paddle_tpu.serving import (InferenceServer, ServingClient,
                                    set_dispatch_delay)

    for replica_spec in _parse_replica_sweep(args.replicas):
        t_boot = time.monotonic()
        server = InferenceServer(
            max_queue=args.max_queue,
            deadline_ms=args.deadline_batch_ms,
            buckets=buckets).start()
        endpoint = server.endpoint
        proxy = None
        if args.chaos_proxy:
            from tools.chaos import FlakyProxy
            proxy = FlakyProxy(server.endpoint, drop_first=1).start()
            endpoint = proxy.endpoint
        if args.chaos_slow_ms:
            set_dispatch_delay(args.chaos_slow_ms / 1000.0)

        try:
            boot = ServingClient(endpoint)
            loaded = boot.load_model(kind, model_dir, buckets=buckets,
                                     replicas=replica_spec)
            n_replicas = int(loaded.get("replicas", 1))
            devices = loaded.get("devices", [])
            # first reply closes the cold-start window: server boot +
            # load + every-bucket warm on every replica + one infer
            warm = np.zeros((1,) + shape, dtype=dtype)
            boot.infer(kind, {feed_name: warm}, deadline_ms=60000.0)
            cold_start_ms = round(
                (time.monotonic() - t_boot) * 1000.0, 1)
            cold_cc = loaded.get("compile_cache", {})
            # a full hot-swap flip of the same model: build + warm a
            # new version of the whole replica set, atomic latest flip,
            # drain the displaced set (the autoscaling-path number)
            t_flip = time.monotonic()
            flipped = boot.load_model(kind, model_dir, buckets=buckets,
                                      replicas=replica_spec)
            swap_flip_ms = round(
                (time.monotonic() - t_flip) * 1000.0, 1)
            flip_cc = flipped.get("compile_cache", {})
            # routing must be invisible in the bits (acceptance
            # criterion) — checked before the dispatch-cost chaos is on
            bit_exact = _verify_bit_exact(
                endpoint, kind, model_dir, buckets, feed_name, shape,
                dtype)
            if args.dispatch_cost_ms:
                set_dispatch_delay(args.dispatch_cost_ms / 1000.0)
            for q in qps_points:
                rec = run_point(endpoint, kind, feed_name, shape, dtype,
                                target_qps=q, duration=duration,
                                req_batch=args.req_batch,
                                deadline_ms=args.deadline_ms)
                stats = boot.stats()["stats"]["models"].get(kind, {})
                rec.update({
                    "model": kind,
                    "buckets": buckets,
                    "replicas": n_replicas,
                    "devices": devices,
                    "bit_exact": bool(bit_exact),
                    "cold_start_ms": cold_start_ms,
                    "swap_flip_ms": swap_flip_ms,
                    "compile_cache": {"cold": cold_cc,
                                      "flip": flip_cc,
                                      "enabled":
                                      args.compile_cache == "on"},
                    "batch_fill": stats.get("batch_fill"),
                    "bucket_fill_ratio": stats.get("bucket_fill_ratio"),
                    "shed_total": stats.get("shed"),
                    "replica_stats": stats.get("replicas"),
                    "dispatch_cost_ms": args.dispatch_cost_ms,
                    "chaos_proxy": bool(proxy),
                    "chaos_slow_ms": args.chaos_slow_ms,
                    "trace": bool(FLAGS.trace),
                    "slo_monitor": bool(FLAGS.slo_monitor),
                })
                rec.update(device)
                print(json.dumps(rec), flush=True)
        finally:
            set_dispatch_delay(0.0)
            if proxy is not None:
                proxy.stop()
            server.shutdown(drain=True)


if __name__ == "__main__":
    main()
