"""Driver `serve_decode_window`: `serve_decode_arch`'s path, checks, window
and reduction (its `run`, unedited), for a decode artifact whose attending
layers are of TWO kinds, window and full, beside a chip's share of a
routed-expert layer.

Why it exists beside `serve_decode_arch.py`, which a PR that adds a
configuration may not edit, and beside the three thin drivers that are
there.  ONE of that driver's functions cannot carry the stack, and `run`
reaches it by its module-level name:

  * `step_scope_ops` names the lane's step instructions under the `moe_ffn`
    scope alone.  This stack's readers need those under `window_attention`
    and `full_attention` in the STEP (`*_attention_ms_per_trip`) and in each
    PREFILL executable of the configuration's buckets, as `<scope>@<bucket>`
    (`prefill_attention_ms_per_prefill`: a prefill's instructions are found
    inside that bucket's own `serving/prefill_compute` spans).
    `serve_decode_ssm.step_scope_ops` names both, from the configuration's
    `trace_scopes` and `prefill_trace_scopes`; it is put in
    `serve_decode_arch.step_scope_ops`'s place for the one call of
    `serve_decode_arch.run` a process makes, and taken out again.

No driver that is there does that and no more: `serve_decode_latent` names
the step's scopes alone; `serve_decode_ssm` also replaces `reference_rows`
by one that knows no router (`NO_ROUTER` at every position), and this stack
routes: its comparison needs the reference's gaps; `serve_decode_hybrid`
hints the reference with the program's picks, which a stack without a
recurrent layer behind a router neither needs nor hands out.

Everything else is `serve_decode_arch`'s own, the reference included: its
`reference_rows` asks the reference module for `tensor_shapes`,
`draw_tensor`, `layer_weights`, `embed`, `layer -> (x, gap)` and `head(x,
lnf_g, lm_head)`, all of which this model gives (a window layer's weights
say that it is one: reference/k_exaone_236b_a23b.py); at 19,200 rows the
vocabulary's two tables are 0.47 GB each in float32 and are drawn whole.
What decides `correct` is that file's.  PERF.md section 7 says which edit of
it makes this file go (`step_scope_ops` reading `trace_scopes` and
`prefill_trace_scopes`).

The profiled sub-window of a `--trace 1` run is the configuration's
`trace_seconds` where it gives one (`serve_decode_latent` says why a cell
may need more than run.py's 3 s); the cap at half the window is run.py's.

A program that cannot describe the stack fails in `serve_decode_arch._run`'s
`block_of`, at once, with a typed error that names the key, before a byte of
the 5.0 GB of weights is drawn.
"""

from benchmark.drivers import serve_decode_arch as arch
from benchmark.drivers.serve_decode_ssm import step_scope_ops


def run(ctx):
    if ctx.config.get("trace_seconds"):
        ctx.trace_seconds = min(float(ctx.config["trace_seconds"]),
                                ctx.seconds / 2.0)
    theirs = arch.step_scope_ops
    arch.step_scope_ops = step_scope_ops
    try:
        return arch.run(ctx)
    finally:
        arch.step_scope_ops = theirs
