"""Driver `serve_decode`: a decode artifact behind the generation server,
under a serving traffic mix, through the path a deployment uses:

    save_decode_model -> InferenceServer -> registry.load_model ->
    DecodeBatcher -> ServingClient.infer_stream over the loopback wire

with the default placement and no flag set; what a deployment sets
(`decode_slots`) comes from the configuration file.

The callers live in a process of their own (`loadgen.Generator`, started
first so that its imports cost no set-up time): no thread of the process
that holds the chip calls `infer_stream`, and the window's line says how
late the generator ran and what CPU time it took.

Traffic `loop` kinds (benchmark/loadgen.py):
  open    arrivals due at fixed times from the seed at `rate_per_s`, each
          followed to its end (drain limit `drain_s`); the tails are the
          end-to-end metrics.
  closed  `clients_per_slot * decode_slots` callers that each wait for
          their reply; tokens per second is the end-to-end metric.

Set-up: weights from --seed on the device in one jitted call, written as an
artifact into the checkout's cache directory, loaded and warmed by the
server; prefill plus decode-through-the-cache held to the plain reference by
logits; the cell's prompt buckets warmed through the wire.
"""

import os
import shutil
import threading
import time

import numpy as np

from benchmark import idle, loadgen, stats, tracewin

# --- tolerances of the comparison with the plain reference -----------------
# TOL_LOGITS: max |logit_program - logit_reference| over the compared
# positions (logits have std ~1).  The program computes fp32 at the TPU's
# DEFAULT matmul precision (operands rounded to bf16, one MXU pass), the
# reference at "highest".  PR 21 measured what that rounding alone does at
# this width: two programs that agree to 5.7e-7 at the attention kernel
# differ by up to 0.021-0.022 in a logit after 12 layers (PERF.md section
# 6).  Program-vs-"highest" carries that rounding once more on every matmul
# of one side only, so the bound is a few times that figure.  bf16 STORAGE
# of weights or cache (8 mantissa bits kept between layers, ROADMAP S6)
# moves logits by ~0.1-0.3 at this depth and would fail it; a fault in the
# semantics (a position off by one, a stale cache row, a wrong mask) moves
# them by O(1).
TOL_LOGITS = 8e-2
# A served token must be the reference's own top-1 or within this of it (a
# near-tie may flip under the rounding above): twice the logit bound.
TOL_TOP1_GAP = 2 * TOL_LOGITS


def make_state_on_device(meta, seed):
    """The artifact's weight dict, made on the device in ONE jitted call
    from the seed: matrices normal(0, 1/sqrt(d_model)), LayerNorm gains 1,
    biases 0 — the distributions `build_tiny_decode_model` draws on the
    host (which takes 40 s at this size)."""
    import jax
    import jax.numpy as jnp
    V, D, L, S = (int(meta[k]) for k in
                  ("vocab_size", "d_model", "n_layers", "max_seq_len"))
    shapes = {"embed": (V, D), "pos": (S, D), "lm_head": (D, V)}
    ones, zeros = {"lnf_g": (D,)}, {"lnf_b": (D,)}
    for i in range(L):
        p = "l%d_" % i
        for n in ("wq", "wk", "wv", "wo"):
            shapes[p + n] = (D, D)
        shapes[p + "w1"], shapes[p + "w2"] = (D, 4 * D), (4 * D, D)
        ones[p + "ln1_g"] = ones[p + "ln2_g"] = (D,)
        zeros[p + "ln1_b"] = zeros[p + "ln2_b"] = zeros[p + "b2"] = (D,)
        zeros[p + "b1"] = (4 * D,)

    @jax.jit
    def draw(seed_u32):
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed_u32)
        out = {n: jax.random.normal(jax.random.fold_in(key, i), s,
                                    jnp.float32) / np.sqrt(D)
               for i, (n, s) in enumerate(sorted(shapes.items()))}
        out.update({n: jnp.ones(s, jnp.float32) for n, s in ones.items()})
        out.update({n: jnp.zeros(s, jnp.float32) for n, s in zeros.items()})
        return out

    return draw(np.uint32(int(seed) % (1 << 32)))


def _reference_fn(ctx, meta):
    import jax
    L, H = int(meta["n_layers"]), int(meta["n_heads"])
    return jax.jit(lambda st, t: ctx.reference.forward(st, t, L, H))


def check_against_reference(ctx, pred, state_dev, meta):
    """Prefill plus decode steps through the cache
    (`DecodeSession.decode_logits`) against the reference's full forward,
    by logits, on seeded sequences that cover two prompt buckets.  The
    decode steps are teacher-forced with the program's own tokens, so the
    reference sees the very sequence the cache holds."""
    import jax.numpy as jnp
    chk = ctx.config["reference_check"]
    tol = dict({"logits": TOL_LOGITS, "top1_gap": TOL_TOP1_GAP},
               **ctx.config.get("tolerances", {}))
    lens, steps = [int(n) for n in chk["prompt_tokens"]], int(chk["steps"])
    rng = np.random.default_rng([int(ctx.seed), 3])
    prompts = [rng.integers(1, meta["vocab_size"], n, dtype=np.int32)
               for n in lens]
    sess = pred.new_session(len(prompts))
    seqs = [list(p) + [sess.prefill(i, p)] for i, p in enumerate(prompts)]
    got = []                                   # [steps][n_prompts, vocab]
    for _ in range(steps):
        toks, logits = sess.decode_logits()
        got.append(logits)
        for i, s in enumerate(seqs):
            s.append(int(toks[i]))
    for i in range(len(prompts)):
        sess.free(i)
    del sess
    pad = max(len(s) for s in seqs)
    pad = min(-(-pad // 128) * 128, pred.max_seq_len)   # one program
    ref_fn = _reference_fn(ctx, meta)
    max_diff, max_gap, buckets = 0.0, 0.0, set()
    for i, s in enumerate(seqs):
        buckets.add(pred.prompt_bucket(lens[i]))
        tokens = np.zeros(pad, np.int32)
        tokens[:len(s)] = s
        want = np.asarray(ref_fn(state_dev, jnp.asarray(tokens)))
        n = lens[i]
        # prefill: its greedy token against the reference's logits at the
        # prompt's last position
        max_gap = max(max_gap, float(want[n - 1].max() - want[n - 1, s[n]]))
        for t in range(steps):
            row = want[n + t]                  # predicts token n + t + 1
            max_diff = max(max_diff, float(np.max(np.abs(got[t][i] - row))))
            max_gap = max(max_gap, float(row.max() - row[s[n + t + 1]]))
    ok = (max_diff <= tol["logits"] and max_gap <= tol["top1_gap"]
          and len(buckets) >= min(2, len(pred.prefill_buckets())))
    ctx.log(phase="reference_check", ok=bool(ok), max_logit_diff=max_diff,
            tol_logits=tol["logits"], max_top1_gap=max_gap,
            tol_top1_gap=tol["top1_gap"], prompt_tokens=lens, steps=steps,
            buckets=sorted(buckets))
    return ok


def check_served(ctx, recs, requests, pred, state_dev, meta, sample=4):
    """After the window: a seeded sample of the streams the server really
    served, each token held to the reference's top-1 (within TOL_TOP1_GAP)
    on the sequence as served."""
    import jax.numpy as jnp
    tol = dict({"top1_gap": TOL_TOP1_GAP},
               **ctx.config.get("tolerances", {}))["top1_gap"]
    pad = min(512, pred.max_seq_len)
    done = [r for r in recs if r.ok(pred.eos_id, pred.max_seq_len)
            and r.prompt_len + len(r.tokens) <= pad]
    if not done:
        return True
    rng = np.random.default_rng([int(ctx.seed), 4])
    picks = [done[i] for i in rng.choice(len(done), min(sample, len(done)),
                                         replace=False)]
    ref_fn = _reference_fn(ctx, meta)
    worst = 0.0
    for r in picks:
        seq = list(requests[r.index % len(requests)]["prompt"]) + r.tokens
        tokens = np.zeros(pad, np.int32)
        tokens[:len(seq)] = seq
        want = np.asarray(ref_fn(state_dev, jnp.asarray(tokens)))
        for t, tok in enumerate(r.tokens):
            row = want[r.prompt_len - 1 + t]
            worst = max(worst, float(row.max() - row[tok]))
    ctx.log(phase="served_check", ok=bool(worst <= tol), streams=len(picks),
            max_top1_gap=worst, tol_top1_gap=tol)
    return worst <= tol


def _kv_live_bytes(recs, at, meta, bytes_per_value=4):
    """Bytes of K and V the streams live at time `at` have really written:
    (prompt + tokens received so far) positions each, against the whole
    rows the slot table reserves for them."""
    positions = sum(r.prompt_len + sum(1 for t in r.token_times if t <= at)
                    for r in recs
                    if r.token_times and r.token_times[0] <= at
                    and (r.done is None or r.done > at))
    return (positions * 2 * int(meta["n_layers"]) * int(meta["d_model"])
            * bytes_per_value)


def program_spans(t0, t1):
    """The program's spans (obs tracing ring) that began inside [t0, t1]
    (monotonic), as {"name", "t0", "t1", "attrs"} on the monotonic clock."""
    from paddle_tpu.obs import tracing
    off = time.time() - time.monotonic()
    out = []
    for s in tracing.recent_spans():
        a = s["ts"] - off
        if t0 <= a <= t1:
            out.append({"name": s["name"], "t0": a,
                        "t1": a + s["dur_ms"] * 1e-3,
                        "attrs": s.get("attrs", {})})
    return out


# --- what `serve_decode_arch.run` shares with `run` below: the traffic, its
# warm-up, the window and the reduction, all through the generator's process

def plan_traffic(ctx, n_slots, vocab_size):
    """(requests, the loop's arguments after them) of the cell's mix."""
    mix = ctx.traffic
    if mix["loop"] == "open":
        dues = loadgen.due_times(mix, ctx.seed, ctx.seconds)
        n_req, args = len(dues), (dues, float(mix["drain_s"]))
    elif mix["loop"] == "closed":
        n_req = int(mix["requests"])
        args = (int(mix["clients_per_slot"]) * n_slots, ctx.seconds)
    else:
        raise ValueError("%s: unknown loop %r" % (ctx.config["driver"],
                                                  mix["loop"]))
    return loadgen.make_requests(mix, ctx.seed, n_req, vocab_size), args


def warm_up(ctx, gen, name, requests, pred):
    """Two short streams a prompt bucket of the mix, through the wire and
    the generator's process."""
    t_phase = time.time()
    by_bucket = {}
    for r in requests:
        by_bucket.setdefault(pred.prompt_bucket(len(r["prompt"])), r)
    warm = [dict(r, max_new=4) for r in by_bucket.values()] * 2
    _, wrecs = loadgen.run_open_loop(gen, name, warm, [0.0] * len(warm),
                                     120.0)
    bad_warm = [r.error or r.info for r in wrecs
                if not (r.info and r.info.get("done"))]
    if bad_warm:
        raise RuntimeError("warm-up stream failed: %r" % bad_warm[:2])
    ctx.log(phase="warmed", seconds=time.time() - t_phase,
            buckets=sorted(by_bucket), generator=gen.stats)


def measure(ctx, gen, name, requests, args):
    """The measured window.  Returns a dict: `t0`, `t1` (monotonic; t0 is
    the generator's), `t0_wall`, `recs`, `spans`, `ring`, `win`."""
    from paddle_tpu.obs import tracing
    tracing.clear()
    win = tracewin.Window(ctx)
    ctx.memory.start()
    t0_wall = time.time()
    t0, recs = gen.run(ctx.traffic["loop"], name, requests, *args)
    t1 = time.monotonic()
    ctx.memory.stop()
    win.close()
    return {"t0": t0, "t1": t1, "t0_wall": t0_wall, "recs": recs,
            "spans": program_spans(t0, t1 + 1.0), "ring": tracing.stats(),
            "win": win, "generator": gen.stats}


def reduce_window(ctx, w, ok, pred, n_slots, meta, **run_facts):
    """The generator's reduction of a measured window `w` (`measure`) to
    the driver's result; `ok`: what the comparisons with the reference
    said.  `run_facts` go to the per-layer readers beside the rest."""
    t0, t1, recs, spans, ring = (w[k] for k in ("t0", "t1", "recs", "spans",
                                                "ring"))
    eos, S = pred.eos_id, pred.max_seq_len
    judged = [r for r in recs if not r.cancelled]
    failed = [r for r in judged if not r.ok(eos, S)]
    ttft = [(r.token_times[0] - r.due) * 1e3 for r in judged
            if r.token_times]
    itl = [(b - a) * 1e3 for r in judged
           for a, b in zip(r.token_times, r.token_times[1:])]
    late = [(r.sent - r.due) * 1e3 for r in recs if r.sent is not None]
    # tokens_per_s: the tokens that reached the clients from the window's
    # start to the LAST arrival inside it, over that time - all the work and
    # all the time up to the last frame, as the training driver counts whole
    # steps up to the end of the last one.  Over the nominal window instead
    # the count moves by whole decode rounds (every live slot's token comes
    # in one frame time), which at 32 slots and 169 ms a round made the
    # rate jump in steps of 0.4% (my chip runs, PR 23).
    arrivals = sorted(t for r in recs for t in r.token_times
                      if t0 <= t <= t0 + ctx.seconds)
    in_window = len(arrivals)
    span_s = (arrivals[-1] - t0) if arrivals else ctx.seconds
    e2e = {"tokens_per_s": in_window / span_s}
    if ttft:
        e2e["ttft_p95_ms"] = stats.percentile(ttft, 95)
    if itl:
        e2e["itl_p95_ms"] = stats.percentile(itl, 95)
    thirds = [[], [], []]
    for sp_ in spans:
        if sp_["name"] == "serving/queue_wait":
            k = int((sp_["t0"] - t0) / ctx.seconds * 3)
            if 0 <= k < 3:
                thirds[k].append((sp_["t1"] - sp_["t0"]) * 1e3)
    child = w["generator"]
    # which of the program's phases grew in a low run: an untraced run
    # keeps nothing else that says (PERF.md section 7)
    table = idle.span_table(spans, (t0, t1))
    ctx.log(phase="window", loop=ctx.traffic["loop"], requests=len(recs),
            queue_wait_p50_ms_by_third=[stats.median(t) if t else None
                                        for t in thirds],
            judged=len(judged), failed=len(failed),
            cancelled_at_window_end=len(recs) - len(judged),
            tokens_in_window=in_window, last_arrival_s=span_s,
            tokens_per_s=e2e["tokens_per_s"],
            tokens_per_nominal_window_s=in_window / ctx.seconds,
            kv_reserved_bytes=pred.kv_cache_bytes(n_slots),
            kv_live_bytes_mid_window=_kv_live_bytes(
                recs, t0 + ctx.seconds / 2.0, meta),
            ttft_samples=len(ttft), itl_samples=len(itl),
            ttft_p50_ms=stats.median(ttft) if ttft else None,
            ttft_p95_ms=e2e.get("ttft_p95_ms"),
            itl_p50_ms=stats.median(itl) if itl else None,
            itl_p95_ms=e2e.get("itl_p95_ms"),
            gen_late_p95_ms=stats.percentile(late, 95) if late else None,
            gen_cpu_s=child["cpu_user_s"] + child["cpu_sys_s"],
            generator=child,
            spans=len(spans), spans_dropped=ring["dropped"],
            span_median_ms={k: float("%.4g" % v[0]) for k, v in table.items()},
            span_mean_ms={k: float("%.4g" % v[1]) for k, v in table.items()},
            span_count={k: v[2] for k, v in table.items()},
            first_failures=[r.error or r.info for r in failed[:3]],
            threads_left=threading.active_count())
    result = {"correct": bool(ok and not failed and ring["dropped"] == 0),
              "attempted": len(recs), "failed": len(failed),
              "end_to_end": e2e, "window_start_wall": w["t0_wall"],
              "window_monotonic": (t0, t1)}
    if ctx.trace:
        win = w["win"]
        trace, w0, w1 = win.read()
        result.update(
            trace=trace, trace_window=(w0, w1), spans=spans,
            run=dict(
                run_facts, chips=ctx.chips, slots=n_slots, window=(t0, t1),
                seconds=ctx.seconds, records=recs, meta=meta,
                trace_window=(w0, w1),
                trace_window_monotonic=(win.t_start, win.t_stop),
                device_kind=ctx.devices[0].device_kind,
                kernel_match=ctx.config.get("kernel_trace_match", {}),
                host_spans=[(s["name"], trace.from_monotonic(s["t0"]),
                             trace.from_monotonic(s["t1"]))
                            for s in spans
                            if s["name"] in ("serving/decode_step",
                                             "serving/prefill_compute")]))
    return result


def run(ctx):
    # the generator's process first: its imports run beside everything
    # up to the warm-up
    gen = loadgen.Generator()
    try:
        return _run(ctx, gen)
    finally:
        gen.close()


def _run(ctx, gen):
    from paddle_tpu.inference.decode import save_decode_model
    from paddle_tpu.serving.server import InferenceServer

    cfg = ctx.config
    meta = dict(cfg["model"])
    n_slots = int(cfg["deployment"]["decode_slots"])
    art = os.path.join(ctx.cache_dir, "artifacts", cfg["name"])

    t_phase = time.time()
    state_dev = make_state_on_device(meta, ctx.seed)
    shutil.rmtree(art, ignore_errors=True)
    save_decode_model(art, {n: np.asarray(v) for n, v in state_dev.items()},
                      meta)
    ctx.log(phase="artifact", seconds=time.time() - t_phase, path=art)

    srv = InferenceServer("127.0.0.1:0").start()
    try:
        t_phase = time.time()
        name = cfg["name"]
        entry = srv.registry.load_model(name, art, decode_slots=n_slots)
        pred = entry.predictor
        if entry.batcher.n_slots != n_slots:
            raise RuntimeError("the lane has %d slots, the configuration "
                               "says %d" % (entry.batcher.n_slots, n_slots))
        ctx.log(phase="loaded", seconds=time.time() - t_phase,
                compile_cache=entry.compile_cache, slots=n_slots,
                kv_cache_bytes=pred.kv_cache_bytes(n_slots),
                param_bytes=pred.param_bytes(),
                devices=entry.device_labels())

        t_phase = time.time()
        ok_ref = check_against_reference(ctx, pred, state_dev, meta)
        ctx.log(phase="checked", seconds=time.time() - t_phase)

        requests, args = plan_traffic(ctx, n_slots, meta["vocab_size"])
        gen.serve(srv.endpoint)
        warm_up(ctx, gen, name, requests, pred)

        # the reference's copy of the weights (0.65 GB at GPT-2 small) is
        # not the deployment's: dropped for the window, redrawn after it
        del state_dev
        w = measure(ctx, gen, name, requests, args)

        t_phase = time.time()
        state_dev = make_state_on_device(meta, ctx.seed)
        ok_served = check_served(ctx, w["recs"], requests, pred, state_dev,
                                 meta)
        ctx.log(phase="after_window", window_to_here_s=time.time()
                - w["t0_wall"] - ctx.seconds,
                served_check_s=time.time() - t_phase)
    finally:
        t_phase = time.time()
        srv.shutdown(drain=False, timeout=10.0)
        ctx.log(phase="shutdown", seconds=time.time() - t_phase)
        shutil.rmtree(art, ignore_errors=True)
    return reduce_window(ctx, w, ok_ref and ok_served, pred, n_slots, meta)
