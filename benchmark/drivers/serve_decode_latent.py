"""Driver `serve_decode_latent`: `serve_decode_arch`'s path, checks, window
and reduction (its `run`, unedited), for a decode artifact whose layers are
LATENT attention beside a chip's share of a routed-expert layer.

Why it exists beside `serve_decode_arch.py`, which a PR that adds a
configuration may not edit.  ONE of that driver's functions cannot carry
the stack, and `run` reaches it by its module-level name:

  * `step_scope_ops` names the lane's step instructions under the `moe_ffn`
    scope alone.  The readers of the layers this stack adds
    (`mla_proj_ms_per_trip`; `mla_attention_*` where the kernel's event is
    found by its scope) need those under `mla_proj` and `mla_attention`: the
    device plane names an event by its HLO instruction without metadata, so
    only the compiled module says which instructions a scope holds
    (benchmark/moe_trace.py).  `serve_decode_hybrid.step_scope_ops` already
    names every scope a configuration lists under `trace_scopes`; it is
    put in `serve_decode_arch.step_scope_ops`'s place for the one call of
    `serve_decode_arch.run` a process makes, and taken out again.

Everything else is `serve_decode_arch`'s own, the reference included: its
`reference_rows` asks the reference module for `tensor_shapes`,
`draw_tensor`, `layer_weights`, `embed`, `layer -> (x, gap)` and `head(x,
lnf_g, lm_head)`, all of which this model gives; its head is untied, and no
recurrent layer hands a routing decision on.  What decides `correct` is that
file's.  PERF.md section 7 says which edit of it makes this file go
(`step_scope_ops` reading `trace_scopes`).

The profiled sub-window of a `--trace 1` run is the configuration's
`trace_seconds` here (6 s) where `benchmark/run.py` gives every cell 3: this
cell's 64 streams end in one dispatch, the lane then prefills the 64 that
wait, ~3.05 s with no decode dispatch in them, and decodes for ~2.1 s: a
3 s sub-window that opens as a wave ends holds no whole dispatch, and every
reader of `phase=step` (the five this stack adds among them) has nothing to
read; one that opens a little earlier holds the wave's last, one-trip
dispatch alone and reads `moe_ffn_ms_per_round` 3.7 where a wave's mean is
29-31 (PERF.md sections 5 and 6, PR 35).  Six seconds hold a whole wave (5.2 s)
wherever they open.  The cap at half the measured window is run.py's.

A program that cannot describe the stack fails in `serve_decode_arch._run`'s
`block_of`, at once, before a byte of the 6.8 GB of weights is drawn.
"""

from benchmark.drivers import serve_decode_arch as arch
from benchmark.drivers.serve_decode_hybrid import step_scope_ops


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
