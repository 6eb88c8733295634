"""Driver `serve_decode_ssm`: `serve_decode_arch`'s path, checks, window and
reduction (its `run`, unedited), for a decode artifact whose layers run a
STATE-SPACE mixer beside their attention and whose vocabulary is too large
to draw whole.

Why it exists beside `serve_decode_arch.py`, which a PR that adds a
configuration may not edit.  Three of that driver's functions cannot carry
the stack, and `run` reaches them by their module-level names:

  * `reference_rows` draws `embed` and `lm_head` WHOLE in float32: at
    261,120 rows of 5,120 that is 5.35 GB each, beside 8.8 GB of the
    program's weights and the check's session.  Here the reference gathers
    only the rows it embeds and computes the head in blocks of the
    vocabulary (`reference.embed_tokens`, `reference.head_blocked`: the
    table is the concatenation of blocks that each have a key of their own,
    so a block drawn alone is the block of the table drawn whole), all
    sequences' rows through one pass over the blocks.
  * `state_to_host` calls `draw_tensor(name, shape, seed)`: this
    reference's matrices are scaled by the meta's multipliers
    (`reference.weight_std`), so it is handed the meta too.
  * `step_scope_ops` names the lane's step instructions under the `moe_ffn`
    scope alone.  Here every scope the configuration lists under
    `trace_scopes` is named from the STEP executable
    (`serve_decode_hybrid.step_scope_ops`), and those under
    `prefill_trace_scopes` from each PREFILL executable of the
    configuration's buckets, as `<scope>@<bucket>` (a prefill's
    instructions are found inside that bucket's own
    `serving/prefill_compute` spans: `ssm_scan_ms_per_prefill`).

So `run` here puts its three functions in their place for the one call of
`serve_decode_arch.run` a process makes, and takes them out again.  What
decides `correct` (`_judge`, `_precision`, `check_against_reference`,
`check_served`, the tolerances' defaults) is `serve_decode_arch`'s own.
PERF.md section 7 says which edits of that file make this one go.

The profiled sub-window of a `--trace 1` run is the configuration's
`trace_seconds` where it gives one (`serve_decode_latent` says why a cell
may need more than run.py's 3 s); the cap at half the window is run.py's.

A program that cannot describe the stack fails in
`serve_decode_arch._run`'s `block_of`, at once, with a typed error that
names the key, before a byte of the 8.8 GB of weights is drawn.
"""

import numpy as np

from benchmark.drivers import serve_decode_arch as arch
from benchmark.drivers import serve_decode_hybrid as hybrid


def state_to_host(ctx, meta):
    """The artifact's weight dict as numpy arrays: each tensor drawn on the
    device by the reference module from (seed, name, the meta's
    multipliers) and copied out before the next is drawn."""
    ref = ctx.reference
    return {n: np.asarray(ref.draw_tensor(n, s, ctx.seed, None, meta))
            for n, s in ref.tensor_shapes(meta).items()}


def reference_rows(ctx, meta, seqs, rows, pad, dtype="float32"):
    """`serve_decode_arch.reference_rows` without the vocabulary's two
    tables whole: the reference's logits at the positions `rows[i]` (a
    slice) of each sequence `seqs[i]`, every sequence padded to `pad`
    positions (causal: a pad changes nothing before it), one layer's
    weights on the device at a time, the embedding's rows gathered and the
    head computed block by block.  No router anywhere, so no position is a
    near-tie (`reference.NO_ROUTER`)."""
    import jax
    import jax.numpy as jnp
    ref = ctx.reference
    model = {k: meta[k] for k in sorted(meta)}
    tokens = np.zeros((len(seqs), pad), np.int32)
    for j, s in enumerate(seqs):
        tokens[j, :len(s)] = s
    embedded = ref.embed_tokens(model, ctx.seed, tokens.reshape(-1), dtype)
    xs = [embedded[j * pad:(j + 1) * pad] for j in range(len(seqs))]
    del embedded
    layer = getattr(ctx, "_ssm_reference_layer", None)
    if layer is None:               # one trace for both comparisons
        layer = ctx._ssm_reference_layer = jax.jit(
            lambda x, w: ref.layer(x, w, model)[0])
    for i in range(int(meta["n_layers"])):
        w = ref.layer_weights(model, ctx.seed, i, dtype)
        xs = [layer(x, w) for x in xs]
        del w
    at = np.cumsum([0] + [len(range(*r.indices(pad))) for r in rows])
    logits = ref.head_blocked(
        model, ctx.seed, jnp.concatenate([x[r] for x, r in zip(xs, rows)]),
        dtype)
    return ([logits[a:b] for a, b in zip(at, at[1:])],
            [np.full(b - a, ref.NO_ROUTER, np.float32)
             for a, b in zip(at, at[1:])])


def step_scope_ops(pred, n_slots, cfg):
    """{scope: names of the lane's step executable's instructions under it}
    for the configuration's `trace_scopes`, and {"<scope>@<bucket>": names
    of that bucket's prefill executable's} for its `prefill_trace_scopes`.
    Lowered and compiled after the window: the same jitted callables and
    shapes, so compile-cache hits."""
    import jax
    from benchmark import moe_trace
    ops = hybrid.step_scope_ops(pred, n_slots, cfg)
    state = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for n, v in pred._state.items()}
    for bucket in pred.prefill_buckets():
        fn = pred.prefill_fn(bucket)
        if not hasattr(fn, "as_text"):
            fn = fn.lower(state,
                          jax.ShapeDtypeStruct((1, bucket), np.int32),
                          jax.ShapeDtypeStruct((), np.int32)).compile()
        text = fn.as_text()
        for scope in cfg.get("prefill_trace_scopes", ()):
            ops["%s@%d" % (scope, bucket)] = sorted(
                moe_trace.scope_instruction_names(text, scope))
    return ops


def run(ctx):
    if ctx.config.get("trace_seconds"):
        ctx.trace_seconds = min(float(ctx.config["trace_seconds"]),
                                ctx.seconds / 2.0)
    theirs = arch.state_to_host, arch.reference_rows, arch.step_scope_ops
    arch.state_to_host, arch.reference_rows, arch.step_scope_ops = (
        state_to_host, reference_rows, step_scope_ops)
    try:
        return arch.run(ctx)
    finally:
        arch.state_to_host, arch.reference_rows, arch.step_scope_ops = theirs
