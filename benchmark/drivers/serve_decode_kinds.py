"""Driver `serve_decode_kinds`: `serve_decode_arch`'s path, checks, window
and reduction (its `run`, unedited), for a decode artifact whose attending
layers are of two kinds with their OWN geometries (window layers of one K/V
head count, full layers of another, K rows wider than V rows) and whose
answers are thousands of tokens long.

Why it exists beside `serve_decode_arch.py`, which a PR that adds a
configuration may not edit, and beside `serve_decode_window.py`, which
carries the same two kinds of layer.  Two of that driver's functions cannot
carry the cell, `run` reaches them by their module-level names, one of the
server's settings has to be another, and a cold set-up has to fit the
driver's time limit:

  * `step_scope_ops` names the lane's step instructions under the `moe_ffn`
    scope alone; this stack's readers need those under `window_attention`
    and `full_attention`, in the step and in each bucket's prefill.
    `serve_decode_ssm.step_scope_ops` names both, from the configuration's
    `trace_scopes` and `prefill_trace_scopes` (as `serve_decode_window`
    takes it).
  * `check_pad` pads both comparisons to the longest check prompt and its
    steps.  This cell's served streams are a prompt of up to 1,024 tokens
    and 1,024-2,560 tokens of answer: under that pad (1,152) the replay
    after the window would find NO served stream short enough to hold to
    the reference and would pass without looking.  The configuration's
    `reference_check.pad` (2,304 positions, which a prompt's median and
    over half the answers fit) is the ONE padded length of both
    comparisons: one trace of the reference a kind of layer and precision,
    as before, and streams the replay can read.
  * the server clamps a request's `max_new_tokens` to its flag
    `serving_max_new_tokens` (128 by default): the configuration's
    `deployment.max_new_tokens_cap` (2,560) is set for the one call of
    `serve_decode_arch.run` a process makes, before the server starts, and
    the flag is put back, as `serve_decode_sparse` does.  It is the
    server's existing ceiling, no new setting.  `serve_decode_window`
    raises no clamp, which is why this file is not that one.

  * a cold run's set-up paid the reference's eight compiles (a float32
    layer at "highest" 12-14 s of the chip's compiler, three kinds of
    layer) inside its check, one after the other: `compile_reference_ahead`
    starts them beside the draw and the write of the weights, on the
    host's idle cores, and the check waits for them where it asks for its
    pad.  The functions, their shapes and what they compute are
    `serve_decode_arch.reference_rows`'s own.

Everything else is `serve_decode_arch`'s own, the reference included
(`reference_rows` asks the reference module for `tensor_shapes`,
`draw_tensor`, `layer_weights`, `embed`, `layer -> (x, gap)` and `head`, all
of which reference/mimo_v2_flash.py gives); what decides `correct`
(`_judge`, `_precision`, `check_against_reference`, `check_served`, the
tolerances' defaults) is that file's.

The profiled sub-window of a `--trace 1` run is the configuration's
`trace_seconds` where it gives one; the cap at half the window is run.py's.

A program that cannot describe the stack fails at once, before a byte of
the 4.5 GB of weights is drawn: here, where its `BLOCK_DEFAULTS` lacks a key
the configuration's `model` names (`block_of` reads the keys it knows and
passes over the rest, so a program without `window_kv_heads` would build
another model under this one's name and fail on the weights' shapes only
after drawing them), or in `serve_decode_arch._run`'s `block_of`, with a
typed error that names the key.
"""

import concurrent.futures
import time
import types

from benchmark.drivers import serve_decode_arch as arch
from benchmark.drivers.serve_decode_ssm import step_scope_ops


_check_pad = arch.check_pad
# what a decode meta says besides its block (`decode.BLOCK_DEFAULTS`)
META_KEYS = ("vocab_size", "d_model", "n_heads", "n_layers", "max_seq_len",
             "eos_id", "prefill_buckets", "dtype", "kv_cache_dtype")


def check_pad(ctx, pred):
    """The configuration's `reference_check.pad` where it gives one (whole
    tiles of 128, inside the cache), `serve_decode_arch.check_pad`'s
    otherwise; never under that one's."""
    pad = int(ctx.config["reference_check"].get("pad") or 0)
    return max(_check_pad(ctx, pred),
               min(-(-pad // 128) * 128, pred.max_seq_len))


def compile_reference_ahead(ctx, meta):
    """Start the reference's compiles NOW, beside the draw and the write of
    the weights, and return what waits for them: a float32 program at
    "highest" costs the chip's compiler 12-14 s a kind of layer (three
    kinds, and the head; the bfloat16 ones 2-3 s), a cold run's set-up paid
    them one after the other inside its check, and the 80 s in which the
    weights are drawn and written leave most of the host's cores idle.

    The two jitted functions are `serve_decode_arch.reference_rows`'s own
    (that function takes them from the context where it finds them: its
    "one trace for both comparisons"), lowered for the shapes that function
    will hand them (`layer_weights`'s own shapes and dtypes, the padded
    length, the rows of a check prompt) and compiled: jax keeps what a
    jitted function was lowered and compiled for, so the calls in the check
    compile nothing (tests hold that).  Nothing runs and nothing is held on
    the device; a compile that fails here is logged and the check compiles
    as before."""
    import jax
    ref = ctx.reference
    model = {k: meta[k] for k in sorted(meta)}
    ctx._arch_reference_fns = layer, head = (
        jax.jit(lambda x, w: ref.layer(x, w, model)),
        jax.jit(lambda x, g, h: ref.head(x, g, h, model)))
    chk = ctx.config["reference_check"]
    pad = check_pad(ctx, types.SimpleNamespace(
        max_seq_len=int(meta["max_seq_len"])))
    D, V = int(meta["d_model"]), int(meta["vocab_size"])
    jobs = []
    for dtype in ("float32", "bfloat16"):
        x = jax.ShapeDtypeStruct((pad, D), dtype)
        kinds = {}
        for i in range(int(meta["n_layers"])):
            w = jax.eval_shape(
                lambda i=i: ref.layer_weights(meta, ctx.seed, i, dtype))
            kinds.setdefault(str(sorted(w.items())), w)
        jobs += [(layer, (x, w)) for w in kinds.values()]
        jobs.append((head, (
            jax.ShapeDtypeStruct((int(chk["steps"]) + 1, D), dtype),
            jax.ShapeDtypeStruct((D,), dtype),
            jax.ShapeDtypeStruct((D, V), dtype))))

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
    from paddle_tpu.flags import FLAGS, set_flags
    from paddle_tpu.inference import decode
    # `block_of` reads the keys it knows and no others: a program that
    # lacks one of this stack's would build another model under its name
    described = dict(decode.BLOCK_DEFAULTS)
    missing = [k for k in ctx.config["model"]
               if k not in described and k not in META_KEYS]
    if missing:
        raise SystemExit("serve_decode_kinds: this program's decode meta "
                         "cannot describe %s" % ", ".join(sorted(missing)))
    if ctx.config.get("trace_seconds"):
        ctx.trace_seconds = min(float(ctx.config["trace_seconds"]),
                                ctx.seconds / 2.0)
    waits = [compile_reference_ahead(ctx, dict(ctx.config["model"]))]

    def check_pad_once_compiled(ctx, pred):
        # the check asks for its pad when the program's part is done and
        # the reference's begins: the compiles started above end here
        while waits:
            waits.pop()()
        return check_pad(ctx, pred)
    cap = FLAGS.serving_max_new_tokens
    theirs = (arch.step_scope_ops, arch.check_pad)
    arch.step_scope_ops, arch.check_pad = (step_scope_ops,
                                           check_pad_once_compiled)
    set_flags({"serving_max_new_tokens": int(
        ctx.config["deployment"]["max_new_tokens_cap"])})
    try:
        return arch.run(ctx)
    finally:
        set_flags({"serving_max_new_tokens": cap})
        arch.step_scope_ops, arch.check_pad = theirs
        while waits:                # a run that failed before its check
            waits.pop()()
