"""Driver `serve_decode_arch`: `serve_decode`'s path, traffic kinds, window
and reduction, for a decode artifact whose meta DESCRIBES its block (norm,
positions, qk-norm, routed experts) and whose weights do not fit the chip
twice:

    save_decode_model -> InferenceServer -> registry.load_model ->
    DecodeBatcher -> ServingClient.infer_stream over the loopback wire

with the default placement and no flag set.

Why it exists beside `serve_decode.py`, which a PR that adds a configuration
may not edit: that driver's `make_state_on_device` draws GPT-2's weight
names and `run` calls it by name, and both of its checks hand the reference
the whole state on the device.  Here

  * the state is drawn by the REFERENCE module (`tensor_shapes`,
    `draw_tensor`: each tensor a pure function of (--seed, its name)), one
    tensor at a time, and goes to the host at once: the device never holds
    it beside the predictor's copy;
  * the reference is run A LAYER AT A TIME (`reference_rows`): layer i's
    weights are drawn again from the seed, all sequences pass through it,
    and they are dropped.  The reference is never handed the predictor's
    arrays (a weight loaded wrongly would then pass);
  * both comparisons know NEAR-TIES of the router: a position where the
    reference's k-th and (k+1)-th router probabilities lie closer than
    `tolerances.router_gap` in some layer is one on which the program (whose
    other matmuls round to bf16) may rightly keep another expert.  Every
    position is held to the bounds; a near-tie that misses them is excused
    up to a looser bound, counted and logged, and the excused are bounded
    in share, as are the few flips at a wider gap (`_judge`);
  * the ROUNDING itself is held against the precision below: in set-up the
    reference runs a second time wholly in bfloat16 on the same sequences,
    and the program's differences from the float32 reference have to stay
    under `tolerances.precision_ratio` of the bfloat16 reference's own,
    position by position (`_precision`).  No fixed logit bound separates the
    two at this depth; the same run's bfloat16 reading does.

Shared with `serve_decode.py` (imported): the tolerances' defaults and,
since PR 34, everything from the traffic on: `plan_traffic`, `warm_up`
through the generator's process, the window (`measure`) and the reduction
to the result (`reduce_window`).  Its `check_served` does not fit (whole
state on the device, `forward(state, tokens, L, H)`), so the replay after
the window is written here on `reference_rows`.  What is left apart is the
set-up up to the checks (PERF.md section 7: let `serve_decode.run` take the
state maker and the reference runner from the reference module, and the two
drivers become one).
"""

import os
import shutil
import time

import numpy as np

from benchmark import loadgen, stats
from benchmark.drivers.serve_decode import (TOL_LOGITS, TOL_TOP1_GAP,
                                            measure, plan_traffic,
                                            reduce_window, warm_up)

# What the configuration's `tolerances` may override; reasons beside the
# numbers there.  With router_gap 0 no position is a near-tie and none is
# excused.
TOL_DEFAULTS = {"logits": TOL_LOGITS, "top1_gap": TOL_TOP1_GAP,
                "router_gap": 0.0, "near_tie_share": 0.0,
                "router_gap_stray": 0.0, "stray_share": 0.0,
                "logits_near_tie": TOL_LOGITS, "precision_ratio": 1.0}


def tolerances(ctx):
    return dict(TOL_DEFAULTS, **{k: v for k, v in ctx.config.get(
        "tolerances", {}).items() if k in TOL_DEFAULTS})


def state_to_host(ctx, meta):
    """The artifact's weight dict as numpy arrays: each tensor drawn on the
    device by the reference module from (seed, name) and copied out before
    the next is drawn."""
    ref = ctx.reference
    return {n: np.asarray(ref.draw_tensor(n, s, ctx.seed))
            for n, s in ref.tensor_shapes(meta).items()}


def reference_rows(ctx, meta, seqs, rows, pad, dtype="float32"):
    """The reference's logits, and its router gaps, at the positions
    `rows[i]` (a slice) of each token sequence `seqs[i]`, every sequence
    padded to `pad` positions (causal: a pad changes nothing before it).
    One layer's weights are on the device at a time, drawn from the seed.
    `dtype` is the precision the WHOLE forward runs in (the reference's
    own is float32).  Returns ([n_seqs][n_rows, vocab] float32 logits,
    [n_seqs][n_rows] the least gap over the layers)."""
    import jax
    import jax.numpy as jnp
    ref = ctx.reference
    model = {k: meta[k] for k in sorted(meta)}
    shapes = ref.tensor_shapes(meta)
    table = ref.draw_tensor("embed", shapes["embed"], ctx.seed, dtype)
    xs = []
    for s in seqs:
        tokens = np.zeros(pad, np.int32)
        tokens[:len(s)] = s
        xs.append(ref.embed(table, jnp.asarray(tokens)))
    del table
    fns = getattr(ctx, "_arch_reference_fns", None)
    if fns is None:                 # one trace for both comparisons
        fns = ctx._arch_reference_fns = (
            jax.jit(lambda x, w: ref.layer(x, w, model)),
            jax.jit(lambda x, g, h: ref.head(x, g, h, model)))
    layer, head = fns
    gaps = [None] * len(seqs)
    for i in range(int(meta["n_layers"])):
        w = ref.layer_weights(meta, ctx.seed, i, dtype)
        for j, x in enumerate(xs):
            xs[j], g = layer(x, w)
            g = np.asarray(g[rows[j]])
            gaps[j] = g if gaps[j] is None else np.minimum(gaps[j], g)
        del w
    lnf = ref.draw_tensor("lnf_g", shapes["lnf_g"], ctx.seed, dtype)
    lm_head = ref.draw_tensor("lm_head", shapes["lm_head"], ctx.seed, dtype)
    return [np.asarray(head(x[rows[j]], lnf, lm_head), np.float32)
            for j, x in enumerate(xs)], gaps


def _judge(tol, cases):
    """`cases`: [(least router gap over the layers, logit difference or
    None, gap of the program's token below the reference's top-1)].

    A ROUTER NEAR-TIE (the program may rightly keep another k-th expert
    there, which is another function and no rounding) moves single
    positions by far more than rounding does.  A position over tol.logits,
    or whose token lies more than tol.top1_gap under the reference's
    top-1, is EXCUSED only if its least gap is under tol.router_gap (where
    all but one in a hundred of the flips seen on the chip lie) and it
    stays within tol.logits_near_tie (its token within twice that), and
    the excused are at most tol.near_tie_share of the positions.  The rare
    flip at a wider gap is a STRAY: under tol.router_gap_stray, within the
    same loose bound, and the strays at most tol.stray_share of the
    positions - what moves positions without regard to the router lands a
    third of its hits there and is refused.  Near-ties, excused and strays
    are counted and logged, never dropped.  What moves EVERY position (a
    lower precision) is `_precision`'s to refuse.  Returns (ok, facts)."""
    n = float(max(len(cases), 1))
    over = [c for c in cases if (c[1] or 0.0) > tol["logits"]
            or c[2] > tol["top1_gap"]]
    loose = [c for c in over if (c[1] or 0.0) <= tol["logits_near_tie"]
             and c[2] <= 2 * tol["logits_near_tie"]]
    excused = [c for c in loose if c[0] < tol["router_gap"]]
    strays = [c for c in loose if tol["router_gap"] <= c[0]
              < tol["router_gap_stray"]]
    kept = [c for c in cases if c not in over]

    def worst(cs, k):
        return max([c[k] for c in cs if c[k] is not None], default=0.0)
    ok = (len(excused) + len(strays) == len(over)
          and len(excused) / n <= tol["near_tie_share"] + 1e-12
          and len(strays) / n <= tol["stray_share"] + 1e-12)
    return ok, {"positions": len(cases),
                "near_ties": sum(c[0] < tol["router_gap"] for c in cases),
                "router_gap": tol["router_gap"],
                "over_the_bounds": len(over), "excused": len(excused),
                "excused_share": len(excused) / n,
                "tol_excused_share": tol["near_tie_share"],
                "strays": len(strays), "stray_share": len(strays) / n,
                "router_gap_stray": tol["router_gap_stray"],
                "tol_stray_share": tol["stray_share"],
                "max_logit_diff": worst(kept, 1),
                "tol_logits": tol["logits"],
                "max_logit_diff_excused": worst(excused + strays, 1),
                "tol_logits_near_tie": tol["logits_near_tie"],
                "max_top1_gap": worst(kept, 2),
                "max_top1_gap_excused": worst(excused + strays, 2),
                "tol_top1_gap": tol["top1_gap"]}


def _precision(tol, pairs):
    """`pairs`: at each compared position, (the program's logit difference
    from the float32 reference, the bfloat16 reference's own difference
    from it).  Rounding moves every position, by an amount that differs
    from sequence to sequence for both sides alike; a router flip moves
    single positions, on either side.  So the two are compared POSITION BY
    POSITION and the median of the ratios is held under
    tol.precision_ratio: a flip on either side of a pair is an outlier the
    median does not see, and a program that computes in bfloat16 reads 1
    by construction.  Returns (ok, facts)."""
    ratios = [p / l for p, l in pairs if l > 0.0]
    if not ratios:
        return False, {"precision_positions": 0}
    mid = stats.median(ratios)
    return mid <= tol["precision_ratio"], {
        "precision_positions": len(ratios), "precision_ratio": mid,
        "tol_precision_ratio": tol["precision_ratio"],
        "logit_diff_median": stats.median([p for p, _ in pairs]),
        "logit_diff_median_lower_precision": stats.median(
            [l for _, l in pairs])}


def check_pad(ctx, pred):
    """One padded length for both comparisons (one reference program)."""
    chk = ctx.config["reference_check"]
    longest = max(int(n) for n in chk["prompt_tokens"]) + int(chk["steps"])
    return min(-(-(longest + 1) // 128) * 128, pred.max_seq_len)


def program_logits(ctx, pred, meta):
    """Prefill plus decode steps through the cache
    (`DecodeSession.decode_logits`) on seeded prompts of the configuration's
    `reference_check` lengths.  The decode steps are teacher-forced with the
    program's own tokens, so the reference sees the very sequence the cache
    holds.  Returns (prompt lengths, [n_prompts] token sequences,
    [steps][n_prompts, vocab] logits)."""
    chk = ctx.config["reference_check"]
    lens, steps = [int(n) for n in chk["prompt_tokens"]], int(chk["steps"])
    rng = np.random.default_rng([int(ctx.seed), 3])
    prompts = [rng.integers(1, meta["vocab_size"], n, dtype=np.int32)
               for n in lens]
    sess = pred.new_session(len(prompts))
    seqs = [list(p) + [sess.prefill(i, p)] for i, p in enumerate(prompts)]
    got = []
    for _ in range(steps):
        toks, logits = sess.decode_logits()
        got.append(logits)
        for i, s in enumerate(seqs):
            s.append(int(toks[i]))
    for i in range(len(prompts)):
        sess.free(i)
    return lens, seqs, got


def check_against_reference(ctx, pred, meta):
    """The program's prefill and decode steps through the cache against the
    reference's full forward, by logits, on sequences that cover the cell's
    prompt buckets and one past them; and the size of the program's
    rounding against the reference run in the precision below."""
    tol = tolerances(ctx)
    lens, seqs, got = program_logits(ctx, pred, meta)
    steps = len(got)
    # row 0: the prompt's last position (predicts the prefill's token);
    # rows 1..steps: the decode steps
    rows = [slice(n - 1, n + steps) for n in lens]
    pad = check_pad(ctx, pred)
    want, gaps = reference_rows(ctx, meta, seqs, rows, pad)
    low, _ = reference_rows(ctx, meta, seqs, rows, pad, "bfloat16")
    cases, pairs, buckets = [], [], set()
    for i, s in enumerate(seqs):
        buckets.add(pred.prompt_bucket(lens[i]))
        n = lens[i]
        for t in range(steps + 1):
            row = want[i][t]                   # predicts token n + t
            diff = None
            if t:
                diff = float(np.max(np.abs(got[t - 1][i] - row)))
                pairs.append((diff, float(np.max(np.abs(low[i][t] - row)))))
            cases.append((float(gaps[i][t]), diff,
                          float(row.max() - row[s[n + t]])))
    ok, facts = _judge(tol, cases)
    ok_precision, precision = _precision(tol, pairs)
    ok = (ok and ok_precision
          and len(buckets) >= min(2, len(pred.prefill_buckets())))
    ctx.log(phase="reference_check", ok=bool(ok), prompt_tokens=lens,
            steps=steps, buckets=sorted(buckets),
            gap_diff_top1=[[float("%.3g" % v) if v is not None else None
                            for v in c] for c in cases],
            diff_lower_precision=[float("%.3g" % l) for _, l in pairs],
            **dict(facts, **precision))
    return ok


def check_served(ctx, recs, requests, pred, meta, sample=4):
    """After the window: a seeded sample of the streams the server really
    served, each token held to the reference's top-1 on the sequence as
    served (near-ties of the router counted apart, as in set-up)."""
    tol, pad = tolerances(ctx), check_pad(ctx, pred)
    done = [r for r in recs if r.ok(pred.eos_id, pred.max_seq_len)
            and r.tokens and r.prompt_len + len(r.tokens) <= pad]
    if not done:
        ctx.log(phase="served_check", ok=True, streams=0)
        return True
    rng = np.random.default_rng([int(ctx.seed), 4])
    picks = [done[i] for i in rng.choice(len(done), min(sample, len(done)),
                                         replace=False)]
    seqs = [list(requests[r.index % len(requests)]["prompt"]) + r.tokens
            for r in picks]
    rows = [slice(r.prompt_len - 1, r.prompt_len - 1 + len(r.tokens))
            for r in picks]
    want, gaps = reference_rows(ctx, meta, seqs, rows, pad)
    cases = [(float(gaps[i][t]), None, float(want[i][t].max()
                                             - want[i][t][tok]))
             for i, r in enumerate(picks) for t, tok in enumerate(r.tokens)]
    ok, facts = _judge(tol, cases)
    ctx.log(phase="served_check", ok=bool(ok), streams=len(picks), **facts)
    return ok


def step_scope_ops(pred, n_slots, cfg):
    """{scope: names of the lane's step executable's instructions under
    it} for the routed-FFN readers (benchmark/moe_trace.py says why the
    trace alone cannot tell).  Lowered and compiled after the window: the
    same jitted callable and shapes, so a compile-cache hit."""
    import jax
    from benchmark import moe_trace
    fn = pred.step_fn(n_slots)
    if not hasattr(fn, "as_text"):
        state = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for n, v in pred._state.items()}
        fn = fn.lower(state, *pred._step_specs(n_slots)).compile()
    match = cfg.get("kernel_trace_match", {}).get("moe_ffn")
    return {"moe_ffn": sorted(moe_trace.scope_instruction_names(
        fn.as_text(), "moe_ffn", match))}


def run(ctx):
    # the generator's process first (serve_decode.run says why)
    gen = loadgen.Generator()
    try:
        return _run(ctx, gen)
    finally:
        gen.close()


def _run(ctx, gen):
    # a program that cannot describe a block fails here, at once
    from paddle_tpu.inference.decode import block_of, save_decode_model
    from paddle_tpu.serving.server import InferenceServer

    cfg = ctx.config
    meta = dict(cfg["model"])
    block_of(meta)
    n_slots = int(cfg["deployment"]["decode_slots"])
    art = os.path.join(ctx.cache_dir, "artifacts", cfg["name"])

    t_phase = time.time()
    shutil.rmtree(art, ignore_errors=True)
    state_host = state_to_host(ctx, meta)
    t_drawn = time.time()
    save_decode_model(art, state_host, meta)
    del state_host
    ctx.log(phase="artifact", seconds=time.time() - t_phase,
            draw_seconds=t_drawn - t_phase, path=art)

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
                param_bytes=pred.param_bytes(), block=pred.block,
                devices=entry.device_labels())

        t_phase = time.time()
        ok_ref = check_against_reference(ctx, pred, meta)
        ctx.log(phase="checked", seconds=time.time() - t_phase)

        requests, args = plan_traffic(ctx, n_slots, meta["vocab_size"])
        gen.serve(srv.endpoint)
        warm_up(ctx, gen, name, requests, pred)
        w = measure(ctx, gen, name, requests, args)

        t_phase = time.time()
        ok_served = check_served(ctx, w["recs"], requests, pred, meta)
        scope_ops = step_scope_ops(pred, n_slots, cfg) if ctx.trace else {}
        ctx.log(phase="after_window", window_to_here_s=time.time()
                - w["t0_wall"] - ctx.seconds,
                served_check_s=time.time() - t_phase)
    finally:
        t_phase = time.time()
        srv.shutdown(drain=False, timeout=10.0)
        ctx.log(phase="shutdown", seconds=time.time() - t_phase)
        shutil.rmtree(art, ignore_errors=True)
    return reduce_window(ctx, w, ok_ref and ok_served, pred, n_slots, meta,
                         scope_ops=scope_ops)
