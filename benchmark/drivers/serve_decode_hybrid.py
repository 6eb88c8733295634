"""Driver `serve_decode_hybrid`: `serve_decode_arch`'s path, checks, window
and reduction (its `run`, unedited), for a decode artifact whose stack has
layers of more than one kind and whose head is TIED to its embedding.

Why it exists beside `serve_decode_arch.py`, which a PR that adds a
configuration may not edit.  Three of that driver's functions cannot carry
such a stack, and `run` reaches them by their module-level names:

  * `reference_rows` draws the head as a tensor `lm_head` of the artifact.
    A tied head is no tensor of the artifact (one table in memory): here the
    reference's `head` is handed the embedding table, drawn again from the
    seed as every other weight of the reference is.
  * `reference_rows` gives a position the reference's least router gap AT
    that position, and `program_logits` keeps the logits alone.  A conv layer
    hands a position's routing on to the positions after it: behind the four
    routed layers a position's logits hang on 16 router decisions (7, 5, 3
    and 1 positions of them: `cone`), one in ten of which the program, whose
    other matmuls round to bf16, decides otherwise, and such a position
    reads 0.1-2 where rounding reads under 0.085 (PERF.md section 6, PR 31:
    the cone tells the two apart at 831 of 832 positions).  Excusing those
    positions excuses a fault too, since a fault moves the picks as well.
    So here the program's chosen experts are kept beside its logits
    (`DecodeSession.last_picks`) and handed to the reference as a HINT,
    which it follows through a near-tie of its OWN scores and through
    nothing else (`reference.layer_hinted`, `tolerances.router_margin`;
    at the six steps whose cone still reaches the prompt the reference's
    scores are not the program's to rounding, and the hint is followed
    whatever the margin):
    the two stay one function of the later positions, every position past
    the cone's reach into the prompt (whose picks the program does not hand
    out) is held to `tolerances.logits` with no excuse, and a choice the
    reference's scores do not nearly tie on shows as the difference it is.
    The "gap" a position is given is a FLAG for `_judge`, unedited: 0.0
    (excusable as before) where its cone reaches the prompt, and on a
    served stream, of which no picks are kept; `AGREE` elsewhere.
  * `step_scope_ops` names the lane's step instructions under the `moe_ffn`
    scope alone.  Here every scope the configuration lists under
    `trace_scopes` is named, for the readers of the layers this stack adds
    (`short_conv_ms_per_trip`).

So `run` here puts its three functions in their place for the one call of
`serve_decode_arch.run` a process makes, and takes them out again.  What
decides `correct` (`_judge`, `_precision`, `check_against_reference`,
`check_served`, the tolerances' defaults) is `serve_decode_arch`'s own.
PERF.md section 7 says which edits of that file make this one go.

A program that cannot describe such a stack fails here at once, before a
byte of the 10.8 GB of weights is drawn.
"""

import numpy as np

from benchmark.drivers import serve_decode_arch as arch


# the "gap" of a position the reference followed the program to: over any
# `tolerances.router_gap`, so never excused (finite: the run's log is JSON)
AGREE = 1e9


def cone(kinds, taps):
    """{routed layer's index among the routed: how many positions of it,
    ending with the position itself, a position's logits hang on}: every
    conv layer behind a routed FFN reaches `taps - 1` positions further
    back.  An attention layer behind one would hand a decision on to EVERY
    later position, and no cone would tell a flip from a fault there."""
    routed = [i for i, (_, ffn) in enumerate(kinds) if ffn == "moe_swiglu"]
    if any(op == "attention" for op, _ in kinds[routed[0] + 1:]):
        raise ValueError("serve_decode_hybrid: an attention layer behind a "
                         "routed FFN: a router's near-tie reaches every "
                         "later position")
    reach, out = 1, {}
    for r in reversed(range(len(routed))):
        out[r] = reach
        reach += (taps - 1) * (kinds[routed[r]][0] == "conv")
    return out


def program_logits(ctx, pred, meta):
    """`serve_decode_arch.program_logits`, which also keeps each step's
    chosen experts (`DecodeSession.last_picks`) by sequence, for
    `reference_rows`."""
    chk = ctx.config["reference_check"]
    lens, steps = [int(n) for n in chk["prompt_tokens"]], int(chk["steps"])
    rng = np.random.default_rng([int(ctx.seed), 3])
    prompts = [rng.integers(1, meta["vocab_size"], n, dtype=np.int32)
               for n in lens]
    sess = pred.new_session(len(prompts))
    seqs = [list(p) + [sess.prefill(i, p)] for i, p in enumerate(prompts)]
    got, picks = [], []
    for _ in range(steps):
        toks, logits = sess.decode_logits()
        got.append(logits)
        picks.append(np.sort(sess.last_picks, axis=-1))
        for i, s in enumerate(seqs):
            s.append(int(toks[i]))
    for i in range(len(prompts)):
        sess.free(i)
    # [routed layers, steps, k] a sequence, each step's picks ascending
    ctx._program_picks = {tuple(s): (lens[i], np.stack(picks)[:, :, i]
                                     .transpose(1, 0, 2))
                          for i, s in enumerate(seqs)}
    return lens, seqs, got


def reference_rows(ctx, meta, seqs, rows, pad, dtype="float32"):
    """`serve_decode_arch.reference_rows` for a tied head and a stack whose
    conv layers carry a routing decision forward: the reference's logits at
    the positions `rows[i]` of each sequence `seqs[i]`, computed with the
    program's picks as hints where `program_logits` kept them, and the flag
    of each position (the module's docstring); one layer's weights on the
    device at a time, the head given the embedding table.  What the hints
    did is logged (`routing_check`)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference.decode import layer_kinds
    ref = ctx.reference
    model = {k: meta[k] for k in sorted(meta)}
    shapes = ref.tensor_shapes(meta)
    margin = float(ctx.config.get("tolerances", {}).get("router_margin", 0))
    reach = max(cone(layer_kinds(meta), int(meta["conv_kernel"])).values())
    k = int(meta["experts_per_token"])
    kept = getattr(ctx, "_program_picks", {})

    def table():
        return ref.draw_tensor("embed", shapes["embed"], ctx.seed, dtype)
    embedding, xs, hints, flags, margins = table(), [], [], [], []
    for s, at in zip(seqs, rows):
        tokens = np.zeros(pad, np.int32)
        tokens[:len(s)] = s
        xs.append(ref.embed(embedding, jnp.asarray(tokens)))
        n, mine = kept.get(tuple(s), (0, None))
        hint = np.full((0 if mine is None else len(mine), pad, k), -1,
                       np.int32)
        flag = np.zeros(len(range(*at.indices(pad))), np.float32)
        within = np.full(pad, margin, np.float32)
        if mine is not None:    # step t sat at position n + t
            hint[:, n:n + mine.shape[1]] = mine
            flag[[j for j, pos in enumerate(range(*at.indices(pad)))
                  if pos - n - reach + 1 >= 0]] = AGREE
            # a step whose cone reaches the prompt sees a state the hints
            # do not cover: its scores are not the program's to rounding,
            # so its picks are followed whatever the margin
            within[n:n + reach - 1] = np.inf
        hints.append(hint)
        flags.append(flag)
        margins.append(within)
    del embedding
    fns = getattr(ctx, "_hybrid_reference_fns", None)
    if fns is None:                 # one trace a kind of layer, both checks
        fns = ctx._hybrid_reference_fns = (
            jax.jit(lambda x, w, hint, within: ref.layer_hinted(
                x, w, model, hint, within)),
            jax.jit(lambda x, g, t: ref.head(x, g, t, model)))
    layer, head = fns
    routed, hinted, differ, followed = 0, 0, 0, 0
    short = {True: [0.0], False: [0.0]}     # by "held to the margin"
    for i in range(int(meta["n_layers"])):
        w = ref.layer_weights(meta, ctx.seed, i, dtype)
        for j, x in enumerate(xs):
            hint = hints[j][routed] if "router" in w and len(hints[j]) \
                else None
            xs[j], _gap, used, lag = layer(x, w, hint, margins[j])
            if hint is not None:
                at = hint[:, 0] >= 0
                lag = np.asarray(lag)[at]
                mine = (np.asarray(used)[at] == hint[at]).all(axis=-1)
                hinted += int(at.sum())
                differ += int((lag > 0).sum())
                followed += int(((lag > 0) & mine).sum())
                for held in (True, False):
                    short[held] += [float(v) for v in lag[
                        (lag > 0) & (np.isfinite(margins[j][at]) == held)]]
        routed += "router" in w
        del w
    if hinted:
        ctx.log(phase="routing_check", dtype=str(dtype), decisions=hinted,
                program_chose_otherwise=differ, followed=followed,
                not_followed=differ - followed, router_margin=margin,
                margin_largest_where_held=max(short[True]),
                margin_median_where_held=sorted(short[True])[
                    len(short[True]) // 2],
                margin_largest_near_the_prompt=max(short[False]))
    lnf = ref.draw_tensor("lnf_g", shapes["lnf_g"], ctx.seed, dtype)
    tied = table()
    return ([np.asarray(head(x[rows[j]], lnf, tied), np.float32)
             for j, x in enumerate(xs)], flags)


def step_scope_ops(pred, n_slots, cfg):
    """{scope: names of the lane's step executable's instructions under it}
    for every scope of the configuration's `trace_scopes` (`moe_ffn` also by
    `kernel_trace_match.moe_ffn`, as in `serve_decode_arch`)."""
    import jax
    from benchmark import moe_trace
    fn = pred.step_fn(n_slots)
    if not hasattr(fn, "as_text"):
        state = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for n, v in pred._state.items()}
        fn = fn.lower(state, *pred._step_specs(n_slots)).compile()
    text, match = fn.as_text(), cfg.get("kernel_trace_match", {})
    return {scope: sorted(moe_trace.scope_instruction_names(
        text, scope, match.get(scope) if scope == "moe_ffn" else None))
        for scope in cfg.get("trace_scopes", ("moe_ffn",))}


def run(ctx):
    from paddle_tpu.inference import decode
    described = dict(decode.BLOCK_DEFAULTS)
    missing = [k for k in ("layer_types", "n_kv_heads", "router", "head")
               if k in ctx.config["model"] and k not in described]
    if missing:
        raise SystemExit("serve_decode_hybrid: this program's decode meta "
                         "cannot describe %s" % ", ".join(missing))
    theirs = arch.reference_rows, arch.step_scope_ops, arch.program_logits
    arch.reference_rows, arch.step_scope_ops, arch.program_logits = (
        reference_rows, step_scope_ops, program_logits)
    try:
        return arch.run(ctx)
    finally:
        arch.reference_rows, arch.step_scope_ops, arch.program_logits = \
            theirs
