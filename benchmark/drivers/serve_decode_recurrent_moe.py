"""Driver `serve_decode_recurrent_moe`: `serve_decode_arch`'s path, checks,
window and reduction (its `run`, unedited), for a decode artifact in which a
ROUTED FFN stands behind RECURRENT layers (a state-space mixer alone in most
layers, an attention layer among them), under a tied head.

Why it exists beside `serve_decode_arch.py`, which a PR that adds a
configuration may not edit, and beside the seven thin drivers that are
there.  Five of that driver's functions cannot carry the stack, `run`
reaches them by their module-level names, and no driver that is there puts
the right five in their place:

  * `state_to_host` calls `draw_tensor(name, shape, seed)`: this
    reference's matrices are scaled by the meta's multipliers, so it is
    handed the meta too (`serve_decode_ssm.state_to_host`, taken as it is).
  * `step_scope_ops` names the step's instructions under `moe_ffn` alone;
    this stack's readers need those under `ssm_update`, `ssm_proj`,
    `gqa_attention`, `shared_expert`, and a prefill's under `ssm_scan`
    (`serve_decode_ssm.step_scope_ops`, taken as it is).
  * `program_logits` keeps the logits alone; here the program's chosen
    experts are kept beside them at EVERY position of a check sequence:
    the prefill's own at its prompt's positions
    (`DecodeSession.last_prefill_picks`, which a stack with routed FFNs
    behind state-space layers hands out behind its first token) and the
    step's at the decode steps (`DecodeSession.last_picks`).
  * `reference_rows` draws an `lm_head` (the head is TIED), and gives a
    position the reference's least router gap AT that position.  Behind
    every routed FFN of this stack lies a layer that hands a position's
    routing on to EVERY later position: a state-space layer through its
    scanned state, the attention layer through its K/V rows.
    `serve_decode_hybrid.reference_rows` follows the program's picks
    through the reference's near-ties, but reasons with a CONE of finitely
    many positions (a conv layer's taps) and refuses a stack with an
    attending layer behind a routed FFN; `serve_decode_ssm`'s knows no
    router.  Here: at every position the
    program's picks are the reference's hint in every layer, followed where
    the reference's OWN logits put every hinted expert within
    `tolerances.router_margin` of its 10th and nowhere else
    (`reference.layer_hinted`), so the two stay one function of the later
    positions and every compared position is held to `tolerances.logits`
    with NO excuse (its flag is `AGREE`).  With the decode steps alone
    hinted, the program's eight in a hundred other decisions in a prompt's
    last positions showed in the first ten steps behind it (0.12-0.47 where
    later steps read 0.06: PERF.md section 6, PR 56).  A served stream, of
    which no picks are kept, carries the reference's own least gap at each
    position, as `serve_decode_arch` gives it.
  * `check_pad` is where the check's reference part begins: the
    reference's compiles (a float32 layer at "highest" 12-14 s of the
    chip's compiler, two kinds of layer, four passes) are started beside
    the draw and the write of the weights and awaited there
    (`compile_reference_ahead`, after `serve_decode_kinds`'s, for THIS
    file's jitted functions and shapes): a cold set-up has to fit the
    driver's time limit.
  * `check_against_reference` is that driver's, and then ONE comparison
    more, which that driver's bfloat16 pass cannot make here.  The
    configuration states float32 activations on bfloat16 matmul operands.
    The reference computed WHOLLY in bfloat16 (its scanned state and its
    router too) is 2.5 times as far from the float32 forward as the
    program is, and a program that merely keeps its ACTIVATIONS as
    bfloat16 numbers is 1.14-1.20 times as far as the sound one: between
    those two no limit on `precision_ratio` stands at all (PERF.md
    section 6, PR 56).  So the reference runs twice more
    (`reference.PRECISIONS`): at the precision the configuration STATES
    (float32, every matmul's activation operand rounded to bfloat16) and
    at the nearest BELOW it (every matmul's result, every norm's and the
    residual stream bfloat16 numbers besides; state and router float32),
    both following the program's picks everywhere, and at every compared
    decode step the program's logits are held to lie NEARER a pass at or
    above the stated precision than the pass below it: the median over
    the positions of min(|program - stated|, |program - float32|) /
    |program - below| (`_precision`'s own median of ratios) stays under
    `tolerances.stated_precision_ratio`.  No pass TRACKS a program's
    roundings through ten layers (a last-place difference in float32, a
    chunked scan against a recurrence say, becomes a whole step of 2^-8
    at the next rounding and the two decorrelate within a few layers: the
    sound program lies 0.039 from the stated pass where it lies 0.045
    from the float32 forward); what the ratio reads is HOW MUCH rounding
    a program carries, in units of the same seed's own two passes, and
    that is steady to a hundredth from seed to seed where a difference
    from the float32 forward alone moves by a tenth (on the chip: sound
    0.478-0.491, a program with bfloat16 activations 0.67-1.04, six seeds;
    the configuration's `tolerances.why`).  A program that rounds nothing
    (the CPU's) reads ~0 through the float32 term.

What decides `correct` besides (`_judge`, `_precision`, `check_served`, the
tolerances' defaults) is `serve_decode_arch`'s own.

The profiled sub-window of a `--trace 1` run is the configuration's
`trace_seconds` where it gives one; the cap at half the window is run.py's.

A program that cannot describe the stack fails HERE, at once, before the
generator's process starts and before a byte of the 5.9 GB of weights is
drawn: in `block_of`, with the typed error that names the meta key (a
program without `position: "none"` says so), or where its `BLOCK_DEFAULTS`
lacks a key the configuration's `model` names (`block_of` passes over a key
it does not know, and would build another model under this one's name).
"""

import concurrent.futures
import time
import types

import numpy as np

from benchmark import stats
from benchmark.drivers import serve_decode_arch as arch
from benchmark.drivers.serve_decode_hybrid import AGREE
from benchmark.drivers.serve_decode_kinds import META_KEYS
from benchmark.drivers.serve_decode_ssm import state_to_host, step_scope_ops


# that driver's, whatever stands under its name while `run` is on
_arch_check = arch.check_against_reference


def _reference_fns(ctx, meta, precision=None):
    """The two jitted functions of the reference at `precision`
    (`reference.PRECISIONS`), one trace a kind of layer and dtype for every
    comparison (and for the compiles ahead)."""
    import jax
    fns = getattr(ctx, "_recurrent_moe_reference_fns", None)
    if fns is None:
        fns = ctx._recurrent_moe_reference_fns = {}
    if precision not in fns:
        ref = ctx.reference
        model = {k: meta[k] for k in sorted(meta)}
        fns[precision] = (
            jax.jit(lambda x, w, hint, within, n: ref.layer_hinted(
                x, w, model, hint, within, precision, n)),
            jax.jit(lambda x, g, t: ref.head(x, g, t, model, precision)))
    return fns[precision]


def program_logits(ctx, pred, meta):
    """`serve_decode_arch.program_logits`, which also keeps the program's
    chosen experts at every position of a sequence but its last:
    `ctx._program_picks` = {sequence: (0, [routed layers, positions, k])},
    each position's picks ascending; and the logits themselves
    (`ctx._program_logits`, for `check_against_reference`)."""
    chk = ctx.config["reference_check"]
    lens, steps = [int(n) for n in chk["prompt_tokens"]], int(chk["steps"])
    rng = np.random.default_rng([int(ctx.seed), 3])
    prompts = [rng.integers(1, meta["vocab_size"], n, dtype=np.int32)
               for n in lens]
    sess = pred.new_session(len(prompts))
    seqs, ahead = [], []
    for i, p in enumerate(prompts):
        seqs.append(list(p) + [sess.prefill(i, p)])
        # [routed layers, bucket, k]: the prompt's positions of it
        ahead.append(np.sort(sess.last_prefill_picks[:, :len(p)], axis=-1))
    got, picks = [], []
    for _ in range(steps):
        toks, logits = sess.decode_logits()
        got.append(logits)
        picks.append(np.sort(sess.last_picks, axis=-1))
        for i, s in enumerate(seqs):
            s.append(int(toks[i]))
    for i in range(len(prompts)):
        sess.free(i)
    ctx._program_picks = {
        tuple(s): (0, np.concatenate(
            [ahead[i], np.stack(picks)[:, :, i].transpose(1, 0, 2)], axis=1))
        for i, s in enumerate(seqs)}
    ctx._program_logits = got
    return lens, seqs, got


def reference_rows(ctx, meta, seqs, rows, pad, dtype="float32",
                   precision=None):
    """`serve_decode_arch.reference_rows` for a tied head and a stack whose
    recurrent layers carry a routing decision forward: the reference's
    logits at the positions `rows[i]` of each sequence `seqs[i]`, computed
    with the program's picks as hints where `program_logits` kept them,
    and the flag of each position (the module's
    docstring); one layer's weights on the device at a time, the head given
    the embedding table.  What the hints did is logged (`routing_check`).
    `precision`: the float32 forward (None), or one of the two passes
    `check_against_reference` stands the program between."""
    import jax.numpy as jnp
    ref = ctx.reference
    shapes = ref.tensor_shapes(meta)
    margin = float(ctx.config.get("tolerances", {}).get("router_margin", 0))
    k = int(meta["experts_per_token"])
    kept = getattr(ctx, "_program_picks", {})
    layer, head = _reference_fns(ctx, meta, precision)

    def table():
        return ref.draw_tensor("embed", shapes["embed"], ctx.seed, dtype,
                               meta)
    embedding, xs, hints, own = table(), [], [], []
    for s, at in zip(seqs, rows):
        tokens = np.zeros(pad, np.int32)
        tokens[:len(s)] = s
        xs.append(ref.embed(embedding, jnp.asarray(tokens), meta))
        n, mine = kept.get(tuple(s), (0, None))
        hint = np.full((0 if mine is None else len(mine), pad, k), -1,
                       np.int32)
        if mine is not None:    # from position n on
            hint[:, n:n + mine.shape[1]] = mine
        hints.append(hint)
        # which of the compared positions are hinted (held without excuse)
        own.append(np.array([mine is None or not n <= pos < n + mine.shape[1]
                             for pos in range(*at.indices(pad))]))
    del embedding
    # every pass but the float32 forward gives a comparison the SIZE of a
    # rounding and nothing else: it follows the program's picks
    # whatever the margin (its own bfloat16 logits decide a sixth of the
    # decisions otherwise, and a decision not followed moves every later
    # position of its sequence by more than the rounding it is to measure)
    plain = str(dtype) == "float32" and precision is None
    within = np.full(pad, margin if plain else np.inf, np.float32)
    # a sequence's positions before this are a prefill's (`reference.
    # attention`); a served stream's, of which no picks are kept: its prompt
    prompt_lens = [np.int32(rows[j].start + 1) for j in range(len(seqs))]
    gaps = [None] * len(seqs)
    hinted, differ, followed, short = 0, 0, 0, [0.0]
    for i in range(int(meta["n_layers"])):
        w = ref.layer_weights(meta, ctx.seed, i, dtype)
        for j, x in enumerate(xs):
            hint = hints[j][i] if len(hints[j]) else np.full(
                (pad, k), -1, np.int32)
            xs[j], gap, used, lag = layer(x, w, hint, within,
                                          prompt_lens[j])
            g = np.asarray(gap[rows[j]])
            gaps[j] = g if gaps[j] is None else np.minimum(gaps[j], g)
            at = hint[:, 0] >= 0
            if at.any():
                lag = np.asarray(lag)[at]
                mine = (np.asarray(used)[at] == hint[at]).all(axis=-1)
                hinted += int(at.sum())
                differ += int((lag > 0).sum())
                followed += int(((lag > 0) & mine).sum())
                short += [float(v) for v in lag[lag > 0]]
        del w
    if hinted:
        ctx.log(phase="routing_check", dtype=str(dtype),
                precision=precision, decisions=hinted,
                program_chose_otherwise=differ, followed=followed,
                not_followed=differ - followed, router_margin=margin,
                margin_largest=max(short),
                margin_median=sorted(short)[len(short) // 2])
    lnf = ref.draw_tensor("lnf_g", shapes["lnf_g"], ctx.seed, dtype, meta)
    tied = table()
    logits = [np.asarray(head(x[rows[j]], lnf, tied), np.float32)
              for j, x in enumerate(xs)]
    if plain:       # what `check_against_reference` compared, and with what
        ctx._compared = (seqs, rows, pad, logits)
    return logits, [np.where(o, g, AGREE).astype(np.float32)
                    for o, g in zip(own, gaps)]


def check_against_reference(ctx, pred, meta):
    """`serve_decode_arch.check_against_reference`, and then the program
    between the stated precision and the one below it (the module's
    docstring): at each compared decode step, the program's distance from
    the nearer of the float32 forward and the pass at the stated precision,
    over its distance from the pass below; `_precision`'s median of the
    ratios under `tolerances.stated_precision_ratio`."""
    ok = _arch_check(ctx, pred, meta)
    got = ctx._program_logits
    seqs, rows, pad, want = ctx._compared
    stated, _ = reference_rows(ctx, meta, seqs, rows, pad,
                               precision="stated")
    below, _ = reference_rows(ctx, meta, seqs, rows, pad, precision="below")
    off = [[float(np.max(np.abs(got[t - 1][i] - side[i][t])))
            for side in (want, stated, below)]
           for i in range(len(seqs)) for t in range(1, len(got) + 1)]
    limit = float(ctx.config.get("tolerances", {}).get(
        "stated_precision_ratio", 1.0))
    ok_stated, facts = arch._precision(
        {"precision_ratio": limit},
        [(min(f, s), b) for f, s, b in off])
    med = [stats.median([o[j] for o in off]) for j in range(3)]
    ctx.log(phase="stated_precision_check", ok=bool(ok_stated),
            positions=facts.get("precision_positions", 0),
            stated_precision_ratio=facts.get("precision_ratio"),
            tol_stated_precision_ratio=limit,
            logit_diff_median_float32=med[0],
            logit_diff_median_stated=med[1],
            logit_diff_median_below=med[2],
            off_float32_stated_below=[[float("%.3g" % v) for v in o]
                                      for o in off])
    return bool(ok and ok_stated)


def compile_reference_ahead(ctx, meta):
    """Start the reference's compiles NOW, beside the draw and the write of
    the weights, and return what waits for them
    (`serve_decode_kinds.compile_reference_ahead` says why; these are THIS
    file's jitted functions, lowered for the shapes `reference_rows` hands
    them).  Nothing runs and nothing is held on the device; a compile that
    fails here is logged and the check compiles as before."""
    import jax
    ref = ctx.reference
    chk = ctx.config["reference_check"]
    pad = arch.check_pad(ctx, types.SimpleNamespace(
        max_seq_len=int(meta["max_seq_len"])))
    D, V, k = (int(meta[n]) for n in ("d_model", "vocab_size",
                                      "experts_per_token"))
    jobs = []
    for dtype, precision in (("float32", None), ("bfloat16", None),
                             ("float32", "stated"), ("float32", "below")):
        layer, head = _reference_fns(ctx, meta, precision)
        x = jax.ShapeDtypeStruct((pad, D), dtype)
        kinds = {}
        for i in range(int(meta["n_layers"])):
            w = jax.eval_shape(
                lambda i=i: ref.layer_weights(meta, ctx.seed, i, dtype))
            kinds.setdefault(str(sorted(w.items())), w)
        jobs += [(layer, (x, w, jax.ShapeDtypeStruct((pad, k), "int32"),
                          jax.ShapeDtypeStruct((pad,), "float32"),
                          jax.ShapeDtypeStruct((), "int32")))
                 for w in kinds.values()]
        jobs.append((head, (
            jax.ShapeDtypeStruct((int(chk["steps"]) + 1, D), dtype),
            jax.ShapeDtypeStruct((D,), dtype),
            jax.ShapeDtypeStruct((V, D), dtype))))

    def compile_one(fn, specs):
        t = time.time()
        fn.lower(*specs).compile()
        return time.time() - t
    pool = concurrent.futures.ThreadPoolExecutor(4)
    futures = [pool.submit(compile_one, *job) for job in jobs]
    pool.shutdown(wait=False)
    t0 = time.time()

    def wait():
        waited = time.time()
        done = [f.exception() or f.result() for f in futures]
        ctx.log(phase="reference_compiled_ahead",
                seconds=time.time() - t0, waited_s=time.time() - waited,
                each=[d if isinstance(d, float) else repr(d) for d in done])
    return wait


def run(ctx):
    from paddle_tpu.inference import decode
    meta = dict(ctx.config["model"])
    # a program that cannot describe the stack fails here, at once
    decode.block_of(meta)
    described = dict(decode.BLOCK_DEFAULTS)
    missing = [k for k in meta if k not in described and k not in META_KEYS]
    if missing:
        raise SystemExit("serve_decode_recurrent_moe: this program's decode "
                         "meta cannot describe %s" % ", ".join(sorted(missing)))
    if ctx.config.get("trace_seconds"):
        ctx.trace_seconds = min(float(ctx.config["trace_seconds"]),
                                ctx.seconds / 2.0)
    waits = [compile_reference_ahead(ctx, meta)]
    check_pad = arch.check_pad

    def check_pad_once_compiled(ctx, pred):
        # the check asks for its pad when the program's part is done and
        # the reference's begins: the compiles started above end here
        while waits:
            waits.pop()()
        return check_pad(ctx, pred)
    names = ("state_to_host", "step_scope_ops", "program_logits",
             "reference_rows", "check_pad", "check_against_reference")
    theirs = [getattr(arch, n) for n in names]
    for n, fn in zip(names, (state_to_host, step_scope_ops, program_logits,
                             reference_rows, check_pad_once_compiled,
                             check_against_reference)):
        setattr(arch, n, fn)
    try:
        return arch.run(ctx)
    finally:
        for n, fn in zip(names, theirs):
            setattr(arch, n, fn)
        while waits:                # a run that failed before its check
            waits.pop()()
