"""Driver `serve_decode_sparse`: `serve_decode_arch`'s path, checks, window
and reduction (its `run`, unedited), for a decode artifact of block-sparse
and linear attention layers that reads prompts of tens of thousands of
tokens and writes answers of thousands.

Why it exists beside `serve_decode_arch.py`, which a PR that adds a
configuration may not edit, and beside the thin drivers that are there.
Four of that driver's functions cannot carry the stack, `run` reaches them
by their module-level names, and one of the server's settings has to be
another:

  * `reference_rows` draws `embed` and `lm_head` WHOLE in float32 (1.2 GB
    each here, beside 9.3 GB at rest).  Here the reference gathers the rows
    it embeds and computes the head in blocks of the vocabulary, as
    `serve_decode_ssm.reference_rows` does (`reference.embed_tokens`,
    `reference.head_blocked`), and hands the comparison the SELECTION's
    gaps (`reference.layer -> (x, gap)`: how near a position came to
    selecting another 64th block) where that one hands `NO_ROUTER`:
    `serve_decode_arch._judge` reads them as it reads a router's.  Every
    sequence is padded to the caller's ONE length, as that driver pads, and
    a sequence nothing was handed over for runs under the trace of one that
    was: one trace a kind of layer and precision for both comparisons; the
    reference leaves the whole blocks of padding behind a sequence
    uncomputed (`live`), so a short sequence costs what it holds.  At
    these widths a layer's float32 trace for the chip costs 9-14 s and its
    run over 17,152 positions 0.5-0.9 s, so a length of its own a sequence
    (PR 48's first form) paid four lengths' traces to save three seconds,
    and the run was cut at its limit (PERF.md section 6, PR 48).
  * `program_logits` keeps the logits alone.  With seeded weights the
    blocks' scores are nearly flat, the 64th and the 65th lie within the
    program's rounding at most positions, and a block that enters the
    selection moves a logit by far more than rounding does
    (reference/minicpm_sala_9b.py).  Excusing those positions excuses a
    fault too.  So, as `serve_decode_hybrid` does for a router's picks, the
    blocks the program selected at each decode step are kept beside its
    logits (`DecodeSession.last_picks`, the step_logits phase only) and
    handed to the reference as a HINT, which it follows through a near-tie
    of its OWN scores (`tolerances.selection_margin`) and through nothing
    else: a decode step's position is held to `tolerances.logits` with no
    excuse (its gap reads `NO_GAP`).  A selection the reference's scores do
    NOT nearly tie on is refused as what it is: one block of 64 more or
    less moves a logit by less than the bounds, so at such a position the
    reference's logit of the program's token is moved out of every bound
    (`OUT_OF_BOUNDS`), and
    `_judge`, unedited, counts it over the bounds with nothing to excuse
    it.  The PREFILL's selection is held the same way at the one position
    it hands out, the prompt's last (`DecodeSession.last_prefill_picks`,
    which rides the fetch of the first token): a prefill that selects by
    another rule than the step is refused there (PERF.md section 6, PR 48:
    top-63 in the prefill alone moved no logit past the bounds).  The
    prompt's earlier positions and a served stream keep the reference's own
    gaps.
  * `state_to_host` and `step_scope_ops` are `serve_decode_ssm`'s, imported:
    a draw that knows the meta's multipliers, and the names of the step's
    instructions under the configuration's `trace_scopes` and of each
    bucket's prefill's under `prefill_trace_scopes`.
  * the server clamps a request's `max_new_tokens` to its flag
    `serving_max_new_tokens` (128 by default): the configuration's
    `assumed.max_new_tokens_cap` (2,048) is set for the one call of
    `serve_decode_arch.run` a process makes, before the server starts, and
    the flag is put back.  It is the server's existing ceiling, no new
    setting.

Everything else is `serve_decode_arch`'s own; what decides `correct`
(`_judge`, `_precision`, `check_against_reference`, `check_served`, the
tolerances' defaults) is that file's.  PERF.md section 7 says which edits of
it make this file go.

The profiled sub-window of a `--trace 1` run is the configuration's
`trace_seconds` where it gives one; the cap at half the window is run.py's.

A program that cannot describe the stack fails in `serve_decode_arch._run`'s
`block_of`, at once, with a typed error that names the key, before a byte of
the 5.6 GB of weights is drawn.
"""

import time

import numpy as np

from benchmark.drivers import serve_decode_arch as arch
from benchmark.drivers.serve_decode_ssm import state_to_host, step_scope_ops

# taken off the reference's logit of the program's token at a position whose
# handed-over selection the reference did not follow: over every bound, and
# never excused
OUT_OF_BOUNDS = 1e3


def _widened(ids, k):
    """Block ids [..., j] as [..., k], -1 (none) behind them."""
    return np.pad(ids, [(0, 0)] * (ids.ndim - 1) + [(0, k - ids.shape[-1])],
                  constant_values=-1)


def program_logits(ctx, pred, meta):
    """`serve_decode_arch.program_logits`, which also keeps the blocks the
    program selected at the prompt's last position (the PREFILL's
    selection: `DecodeSession.last_prefill_picks`) and at each step:
    `ctx._sparse_hints[i]` = (sequence i as compared, the prompt's last
    position and those of its decode steps, [1 + steps, sparse layers, K/V
    heads, k] block ids, -1 = none)."""
    t_start = time.time()
    chk = ctx.config["reference_check"]
    lens, steps = [int(n) for n in chk["prompt_tokens"]], int(chk["steps"])
    rng = np.random.default_rng([int(ctx.seed), 3])
    prompts = [rng.integers(1, meta["vocab_size"], n, dtype=np.int32)
               for n in lens]
    sess = pred.new_session(len(prompts))
    seqs, at_the_end = [], []
    for i, p in enumerate(prompts):
        seqs.append(list(p) + [sess.prefill(i, p)])
        at_the_end.append(sess.last_prefill_picks)   # [sparse layers, Hc, k]
    got, picks = [], []
    for _ in range(steps):
        toks, logits = sess.decode_logits()
        got.append(logits)
        picks.append(sess.last_picks)        # [sparse layers, N, Hc, k]
        for i, s in enumerate(seqs):
            s.append(int(toks[i]))
    for i in range(len(prompts)):
        sess.free(i)
    # a short bucket holds fewer blocks than the step's k
    k = max(p.shape[-1] for p in picks + at_the_end)
    ctx._sparse_hints = [
        (list(s), n - 1 + np.arange(steps + 1),
         np.stack([_widened(at_the_end[i], k)]
                  + [_widened(p[:, i], k) for p in picks]))
        for i, (s, n) in enumerate(zip(seqs, lens))]
    ctx.log(phase="program_logits", seconds=time.time() - t_start)
    return lens, seqs, got


def _handed_over(ctx, meta, seqs, pad):
    """[(rows [M], ids [M, sparse layers, K/V heads, k])] a sequence: the
    program's selections where these are the sequences it decoded
    (`program_logits`), else rows past the padded length, which name no
    position (`reference.sparse_attention`), in the shapes of those that
    do; and whether anything was handed over."""
    hints = getattr(ctx, "_sparse_hints", None) or ()
    if len(hints) == len(seqs) and all(
            list(s) == h[0] for s, h in zip(seqs, hints)):
        return [h[1:] for h in hints], True
    sparse = meta["layer_types"].count("sparse_attention")
    shape = (int(ctx.config["reference_check"]["steps"]) + 1, sparse,
             int(meta.get("n_kv_heads") or meta["n_heads"]),
             int(meta["sparse_topk"]))
    return [(np.full(shape[0], pad), np.full(shape, -1))] * len(seqs), False


def reference_rows(ctx, meta, seqs, rows, pad, dtype="float32"):
    """The reference's logits and its selection gaps at the positions
    `rows[i]` (a slice) of each sequence `seqs[i]`, every sequence padded
    to `pad` positions (causal: a pad changes nothing before it), one
    layer's weights on the device at a time, the embedding's rows gathered
    and the head computed block by block.  Returns ([n_seqs][n_rows, vocab]
    float32 logits, [n_seqs][n_rows] the least gap over the layers)."""
    import jax
    import jax.numpy as jnp
    t_start = time.time()
    ref = ctx.reference
    model = {k: meta[k] for k in sorted(meta)}
    margin = float(ctx.config.get("tolerances", {}).get(
        "selection_margin", 0.0))
    fns = getattr(ctx, "_sparse_reference_fns", None)
    if fns is None:                 # one trace a kind of layer
        fns = ctx._sparse_reference_fns = {}
    hints, handed_over = _handed_over(ctx, meta, seqs, pad)
    sparse = [i for i, k in enumerate(meta["layer_types"])
              if k == "sparse_attention"]
    xs = []
    for s in seqs:
        tokens = np.zeros(pad, np.int32)
        tokens[:len(s)] = s
        xs.append(ref.embed_tokens(model, ctx.seed, tokens, dtype))
    gaps = [None] * len(seqs)
    for i in range(int(meta["n_layers"])):
        kind = meta["layer_types"][i]
        if kind not in fns:
            fns[kind] = jax.jit(
                lambda x, w, live, at=None, ids=None, i=i: ref.layer(
                    x, w, model, i, None if at is None else (at, ids),
                    margin, live))
        w = ref.layer_weights(model, ctx.seed, i, dtype)
        for j, x in enumerate(xs):
            hint = (hints[j][0], hints[j][1][:, sparse.index(i)]) \
                if kind == "sparse_attention" else ()
            xs[j], g = fns[kind](x, w, np.int32(len(seqs[j])), *hint)
            g = np.asarray(g[rows[j]])
            gaps[j] = g if gaps[j] is None else np.minimum(gaps[j], g)
        del w
    at = np.cumsum([0] + [len(range(*r.indices(len(x))))
                          for x, r in zip(xs, rows)])
    logits = ref.head_blocked(
        model, ctx.seed, jnp.concatenate([x[r] for x, r in zip(xs, rows)]),
        dtype)
    logits = [logits[a:b] for a, b in zip(at, at[1:])]
    off = [g == ref.UNFOLLOWED for g in gaps]
    ctx.log(phase="selection_hints", dtype=str(dtype), sequences=len(seqs),
            pad=int(pad), seconds=time.time() - t_start, margin=margin,
            hinted=sum(len(h[0]) for h in hints) if handed_over else 0,
            unfollowed=int(sum(o.sum() for o in off)))
    for lg, o, g, s, r in zip(logits, off, gaps, seqs, rows):
        # at a hinted position the reference did not follow, the PROGRAM's
        # token falls out of every bound: its logit differs by that much and
        # lies that far under the reference's top-1, which is all that is
        # compared at a prompt's last position (the prefill hands out no
        # logits)
        at = np.arange(len(s))[r][o]
        lg[np.flatnonzero(o), [s[p + 1] for p in at]] -= OUT_OF_BOUNDS
        g[o] = ref.NO_GAP
    return logits, gaps


def run(ctx):
    from paddle_tpu.flags import FLAGS, set_flags
    if ctx.config.get("trace_seconds"):
        ctx.trace_seconds = min(float(ctx.config["trace_seconds"]),
                                ctx.seconds / 2.0)
    cap = FLAGS.serving_max_new_tokens
    theirs = (arch.state_to_host, arch.reference_rows, arch.step_scope_ops,
              arch.program_logits)
    (arch.state_to_host, arch.reference_rows, arch.step_scope_ops,
     arch.program_logits) = (state_to_host, reference_rows, step_scope_ops,
                             program_logits)
    set_flags({"serving_max_new_tokens": int(
        ctx.config["deployment"]["max_new_tokens_cap"])})
    try:
        return arch.run(ctx)
    finally:
        set_flags({"serving_max_new_tokens": cap})
        (arch.state_to_host, arch.reference_rows, arch.step_scope_ops,
         arch.program_logits) = theirs
