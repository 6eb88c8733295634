"""Autoregressive generation: decode artifacts, prefill/decode phase
split, and the slot-table KV cache the serving layer batches over.

The one-shot Predictor serves classifier-shaped programs: fixed-shape
in, fixed-shape out, stateless between calls.  Generation breaks that
contract — each request carries growing state (the KV cache) across
many tiny steps, and the chip idles unless many requests decode
TOGETHER.  This module is the inference-side half of the answer
(SERVING.md "Continuous batching & streaming" is the serving half):

* a **decode artifact** (`save_decode_model` / `build_tiny_decode_model`)
  — a directory holding a causal-transformer LM's weights plus a meta
  record (vocab, layers, heads, max_seq_len, eos id, prefill buckets)
  in the typed wire format, detected by `decode_meta.bin` the way the
  AOT predictor is detected by `aot_meta.bin`.  The meta also DESCRIBES
  the decoder stack (`BLOCK_DEFAULTS`: LayerNorm | RMSNorm, learned |
  rotary positions, qk-norm over the projection or per head, multi-head
  | grouped-query | latent attention, a head size of its own
  (`head_dim`), a layer's operator attention | a gated short convolution
  | attention IN PARALLEL with a Mamba-2 state-space mixer | attention
  over the last `sliding_window` positions only, rotated alone where
  `rope_layers` says so (`layer_types`), fixed muP multipliers, leading dense SwiGLU layers,
  ReLU MLP | a dense SwiGLU in every layer | dropless routed SwiGLU
  experts under a softmax or a sigmoid
  router, all of them or the run of them a member of an expert-parallel
  deployment holds, a shared expert beside them, a norm after each
  sublayer too, a head of its own | tied, matmul weights float32 |
  bfloat16 at rest); an artifact that names none of those keys is the
  GPT-2-shaped block in every layer.
  A PHASE is: embed the tokens at their positions, run ONE per-layer
  function (`GenerativePredictor._block`) once a layer with the phase's
  own `attend(q, k, v)`, `convolve(z, taps)` and `scan(xs, B, C, dt, A)`,
  apply the head.
  `_block` is the only spelling of a decoder layer and every phase
  runs every stack it can (prefill, the step and its fused window for
  all; the speculative verify and a rollback for stacks without a
  recurrent layer); a phase owns only what `attend` does with K and V,
  where `convolve` finds a position's earlier inputs and where `scan`
  keeps a state-space mixer's state (a prefill scans the prompt in
  chunks, `ssd_chunked_scan`; a step advances the slots' state by one
  position).  Rows
  are written to a K/V slot table by ONE scatter (`_land`) and cleared
  by ONE scatter of zeros (`_clear_rows`).  What a placement or a phase
  cannot hold is refused by a typed error that names the meta key (the
  tensor-parallel lane any block but the default: its grammar has no
  rule for sharding experts; a mesh, a rollback, the speculative
  phases and an int8 cache every kind of slot state whose record has
  no rule for them: `slot_state.KINDS`, `GenerativePredictor._require`);
* a **prefill / decode phase split** (`GenerativePredictor`): prefill
  runs the whole prompt through the causal forward once per padded
  *prompt bucket* (each bucket's executable rides the persistent
  compile cache, COMPILE_CACHE.md, so a warm boot deserializes instead
  of retracing), emitting the prompt's K/V and the first generated
  token; decode is ONE fixed-shape step function over the WHOLE slot
  table — XLA compiles it exactly once per (n_slots) geometry, and
  every later step, whatever mix of requests occupies the slots, reuses
  that executable;
* **slot-indexed state** (`DecodeSession`) of the kinds the stack's
  layers keep (K/V rows, latent rows, conv state, scanned state, K/V
  rings), each kind ONE record of `slot_state.KINDS` that names its
  leaves, shapes its table and says what it has a rule for; resident
  on the session's device, ONE buffer a leaf that every write updates
  in place (a step's rows, an admission, a release: each call is given
  the table donated and the session keeps the result; SERVING.md "The
  slot table is ONE buffer").  A request owns one slot from prefill to
  finish; freeing a slot ZEROES its cache lines before reuse (no cross-request KV
  leakage — pinned by tests/test_decode_serving.py), and the decode
  step's cache writes are gated by the active mask so a dead slot
  stays zero.  Per-slot math is independent by construction, which is
  what makes batched decode bit-exact vs a single-request session:
  requests joining or leaving the running batch cannot move another
  request's tokens by one bit.

**Quantized KV cache** (QUANTIZE.md "Quantized KV cache"): decode is
HBM-bound and the slot table is its dominant byte stream — every step
re-reads the whole cache.  `kv_cache_dtype="int8"` (a `load_model` /
`decode_meta` knob, default FLAGS.serving_kv_cache_dtype) stores K/V
slots as int8 with per-(layer, head) symmetric fp32 scales calibrated
once per artifact from a deterministic probe prefill: cache WRITES
quantize in-graph (prefill, step, and verify all land
`clip(round(x / scale))` rows), and the decode kernel streams
int8 tiles dequantized in-register (`ops/pallas_kernels.
decode_attention` — float KV never materializes in HBM), cutting cache
bytes 4x at equal slots.  The scales are baked constants of the traced
phases, and `kv_cache_dtype` is a compile-cache fingerprint field, so
fp32/int8 executables never collide.  Greedy int8 streams are
bit-stable against themselves (every row quantizes identically in
every path — the slot-reuse / rollback / spec-verify contracts all
survive unchanged); vs the fp32 cache they agree to quantization
error, not bit-exactly.

Decode attention gathers K/V from the slot cache through the Pallas
decode kernel (`ops/pallas_kernels.decode_attention` — block geometry
from the shared kernel-tuning registry); sampling is greedy argmax
(deterministic — the parity contract above is exact equality, not
"close").

**Speculative decoding** (`SpeculativeDecodeSession`, SERVING.md
"Speculative decoding"): a cheap *draft* GenerativePredictor (the int8
twin of the same artifact, or any vocab-compatible decode artifact)
autoregressively proposes k tokens per round, and the fp32 *target*
scores all k+1 positions in ONE fixed-shape batched verify step (its
executable is one new compile-cache fingerprint per (n_slots, k)).
The longest greedily-agreeing prefix commits to the target's KV slot
cache; rejected suffixes roll the slot's length pointer back with the
stale KV rows cleared in-graph.  Greedy acceptance keeps the committed
stream BIT-IDENTICAL to the fp32-only plain-step stream: every emitted
token is a target argmax, and the verify step attends through the
plain step's own `decode_attention` call (once per chunk position,
under that position's length), so verify logits round
like sequential step logits.  A draft failure mid-round
degrades the session to target-only plain decode within that same
step (`degraded`), never wedging or corrupting a stream.

**The step is a window** (SERVING.md "Fused multi-step decode"): every
decode dispatch costs the host a launch, a fetch and one wake-up per
stream, and on the chip that, not the device's work, bounds the token
rate.  So the step executable, `step_fn(n_slots)`, IS up to
`STEP_WINDOW` decode steps: a `lax.while_loop` carrying {cache, last
tokens, the token block} through step + argmax + KV write per trip,
with per-slot budgets and the trip count RUNTIME arguments (`budget`,
`max_trips`), so one executable per slot count serves a one-trip round
and a full window alike.  A slot STOPS in-graph with the trip in which
it emits EOS, meets its budget or fills its cache, and sits out the
rest of the window as a slot that does not run (nothing of its state is
written again); the window ends at `max_trips` or with the trip in
which its LAST running slot stops.  A slot that stops at trip j of W
idles W - j slot-trips, which the benchmark's `slots_busy_share` reads.
`DecodeSession.decode()` is one trip of it, `decode_fused` a window;
the serving lane picks the trips of each dispatch from its own slot
table (`DecodeBatcher._lane_iter`: the largest live budget).  The
speculative path rides the same discipline: `fused_spec_fn` runs k
draft steps + batched verify
+ in-graph accept/rollback/catch-up as one dispatch
(`SpeculativeDecodeSession.step(fused=True)`).  Because the per-trip
body is one `_step_core` and per-slot math is independent, a window's
streams are those of one-trip dispatches token for token: a window
moves slot joins/leaves to its boundaries without moving a token.
"""

import collections
import contextlib
import functools
import hashlib
import json
import math
import os
import threading
import time
import warnings

import numpy as np

from paddle_tpu.inference import slot_state
from paddle_tpu.inference.slot_state import (  # noqa: F401  (re-exported)
    _TPU_PHASE_OPTIONS, _clear_rows, _land, _pad_rows, _slot_writers,
    head_dim as _head_dim, latent_row, ssm_widths as _ssm_widths)
from paddle_tpu.obs import tracing as obs_tracing

__all__ = ["GenerativePredictor", "DecodeSession", "DecodeSessionDead",
           "SpeculativeDecodeSession", "save_decode_model",
           "build_tiny_decode_model", "load_decode_predictor",
           "greedy_decode", "set_draft_poison", "normalize_kv_dtype",
           "STEP_WINDOW",
           "DECODE_META"]

DECODE_META = "decode_meta.bin"
# the most decode steps ONE dispatch of the step executable runs
# (`GenerativePredictor.step_fn`); how many a dispatch does run is a
# runtime argument.  Chosen on the chip (PERF.md section 6, PR 30: over
# 4, 8 gave +13% and +8% in the two saturated cells, 16 over 8 +4.7% and
# +3.9%, under the 5% asked of it); a stream receives its tokens in
# blocks of up to this many.
STEP_WINDOW = 8
_DECODE_STATE = "decode_state.bin"

# chaos hook (tools/chaos.py spec-fallback scenario): once armed, the
# draft side of every SpeculativeDecodeSession raises after the given
# number of further draft steps — the in-process stand-in for a dead /
# poisoned draft predictor.  The session must degrade to target-only
# decode within the same round, bit-exact and un-wedged.
_DRAFT_POISON = {"after": None, "steps": 0}


def set_draft_poison(after_steps=0):
    """Arm (int: poison fires once `after_steps` more draft steps have
    run) or disarm (None) the draft-failure chaos injection."""
    _DRAFT_POISON["after"] = None if after_steps is None \
        else int(after_steps)
    _DRAFT_POISON["steps"] = 0


def _check_draft_poison():
    after = _DRAFT_POISON["after"]
    if after is None:
        return
    _DRAFT_POISON["steps"] += 1
    if _DRAFT_POISON["steps"] > after:
        raise RuntimeError("chaos: draft predictor poisoned "
                           "(set_draft_poison)")


class DecodeSessionDead(RuntimeError):
    """A `DecodeSession` whose slot table is gone: a phase call raised
    AFTER its table had been donated to it, so the session holds deleted
    arrays and every stream in it is lost.  Raised on the next use of
    the session, naming the call that failed; there is nothing to retry
    with."""


def _nbytes(leaves):
    return sum(int(np.asarray(a).nbytes) for a in leaves)


def _host_nbytes(leaves):
    """Bytes of the leaves an executable call has to upload itself:
    every one that is not a jax.Array yet."""
    import jax
    return sum(int(np.asarray(a).nbytes) for a in leaves
               if not isinstance(a, jax.Array))


def normalize_kv_dtype(value):
    """Canonical KV-cache dtype: ''/None/'fp32'/'f32'/'float32' ->
    'float32', 'int8' -> 'int8'; anything else is a typed error (the
    serving wire validates through this too)."""
    v = str(value or "").strip().lower()
    if v in ("", "fp32", "f32", "float32"):
        return "float32"
    if v == "int8":
        return "int8"
    raise ValueError(
        "unsupported kv_cache_dtype %r (expected float32|int8)"
        % (value,))


def _default_prefill_buckets(max_seq_len):
    """Powers of two up to max_seq_len (min 8): the prompt-length
    buckets prefill compiles for.  Deterministic by prompt length, so
    two decodes of the same prompt always ride the same executable —
    the bit-exactness contract leans on this."""
    buckets, b = [], 8
    while b < max_seq_len:
        buckets.append(b)
        b *= 2
    buckets.append(int(max_seq_len))
    return buckets


# The decoder stack an artifact's meta describes.  An artifact that names
# none of these keys is the GPT-2-shaped block this module began with
# (LayerNorm, learned absolute positions, MHA, ReLU MLP, its own head) in
# every layer, so every artifact written before the keys existed opens
# unchanged.
BLOCK_DEFAULTS = (
    ("norm", "layernorm"),        # | "rmsnorm" (gain only, no bias)
    ("norm_eps", 1e-5),
    ("position", "learned"),      # | "rope" (half-split rotary over the
    ("rope_theta", 10000.0),      #   whole head, no position table) | "none"
                                  #   (no table and no rotation: causality
                                  #   and the recurrent layers order it)
    ("qk_norm", False),           # True: RMSNorm of the whole q / k
                                  # projection | "head": of each head (a
                                  # gain of the head size), before RoPE
    ("ffn", "relu_mlp"),          # | "moe_swiglu" (dropless routed experts)
    ("n_experts", 0),
    ("experts_per_token", 0),
    ("expert_width", 0),
    ("norm_topk_prob", False),    # renormalise the kept router weights
    # a layer's OPERATOR, layer by layer: "attention" | "conv" (a gated
    # short convolution: depthwise, causal, `conv_kernel` taps, whose slot
    # state is its last conv_kernel - 1 inputs); () = attention everywhere
    ("layer_types", ()),
    ("conv_kernel", 0),
    ("n_kv_heads", 0),            # K/V heads (grouped-query: query head a
                                  # reads K/V head a // group); 0 = n_heads
    ("n_dense_layers", 0),        # the first layers' FFN is ONE dense
    ("dense_width", 0),           # SwiGLU of this width, the rest are `ffn`
    ("router", "softmax"),        # | "sigmoid_bias": sigmoid scores, top-k
                                  # of score + a per-expert bias, weights
                                  # from the unbiased scores | "sigmoid":
                                  # sigmoid scores, top-k of the scores
    ("head", "untied"),           # | "tied": logits = norm(x) @ embed.T
    # layer_types "mla": latent attention.  The slot state is ONE row a
    # position, [kv_lora_rank | qk_rope_head_dim] (the normed latent and
    # the one rotated key every head shares); query heads are
    # qk_nope_head_dim + qk_rope_head_dim wide (rotary over the rope lanes
    # alone), value heads v_head_dim, the query goes through a latent of
    # q_lora_rank.  A prefill EXPANDS the rows to per-head K and V, a
    # decode step ABSORBS the up-projection into the query and the output
    # and attends over the rows themselves (`_mla_expanded`, `_mla_absorbed`)
    ("q_lora_rank", 0),
    ("kv_lora_rank", 0),
    ("qk_nope_head_dim", 0),
    ("qk_rope_head_dim", 0),
    ("v_head_dim", 0),
    ("sandwich_norm", False),     # a norm AFTER each sublayer too, before
                                  # its result joins the residual stream
    ("routed_scaling", 1.0),      # the kept router weights times this
    ("n_shared_experts", 0),      # SwiGLU experts every token takes, beside
                                  # the routed ones (one matrix set of
                                  # n_shared_experts * expert_width)
    # the chip's share of the experts (expert parallelism, one member of
    # it): () = all n_experts live here; (first, count) = the contiguous
    # run first .. first + count - 1 does, w_gate / w_up / w_down are
    # [count, ..], the router keeps its n_experts outputs and its top-k,
    # and a pair routed to an expert held elsewhere adds nothing here
    ("experts_held", ()),
    ("weight_dtype", "float32"),  # | "bfloat16": the matmul weights at rest
                                  # (`_bf16_at_rest`, `_contract`); gains,
                                  # the router and every activation fp32
    ("head_dim", 0),              # a head's size; 0 = d_model // n_heads
    # layer_types "attention+ssm": TWO mixers on the same normed input,
    # summed into one residual: grouped-query attention and a Mamba-2
    # (SSD) state-space mixer of `ssm_heads` heads of `ssm_head_dim`, a
    # state of `ssm_state` values a head feature, B and C shared by
    # `ssm_groups` groups of heads, a causal depthwise conv of
    # `ssm_conv_kernel` taps (with a bias) in front.  Its slot state is of
    # three kinds: K/V rows, the conv's last inputs, and the SCANNED state
    # [ssm_heads, ssm_head_dim, ssm_state] fp32, which every token decays
    # and adds to (`GenerativePredictor._ssm`).  A prefill scans a prompt
    # in chunks of `ssm_chunk` positions
    ("ssm_heads", 0),
    ("ssm_head_dim", 0),
    ("ssm_state", 0),
    ("ssm_groups", 1),
    ("ssm_conv_kernel", 0),
    ("ssm_chunk", 128),
    # fixed multipliers (muP), plain numbers; 1 = none
    ("embedding_multiplier", 1.0),    # the embedding's rows
    ("lm_head_multiplier", 1.0),      # the logits
    ("attention_in_multiplier", 1.0),     # the input of wq / wk / wv
    ("key_multiplier", 1.0),              # k, before it is rotated
    ("attention_out_multiplier", 1.0),    # wo's result
    ("ssm_in_multiplier", 1.0),           # the input of ssm_in
    ("ssm_out_multiplier", 1.0),          # ssm_out's result
    # ssm_in's result by segment, [z | x | B | C | dt]; () = none
    ("ssm_multipliers", ()),
    # a dense gated FFN's (gate pre-activation, down's result); () = none
    ("mlp_multipliers", ()),
    # layer_types "window_attention": grouped-query attention over the last
    # `sliding_window` positions only, the token's own among them (key j is
    # seen from position t iff t - sliding_window < j <= t).  Its slot state
    # is a RING of `sliding_window` K/V rows a slot (`slot_state.KINDS`):
    # position p's row lies at p % sliding_window, beside the full layers'
    # rows of every position.  0 = no window layer
    ("sliding_window", 0),
    # which attending layers turn q and k under position=rope: "all" |
    # "window": the window layers alone, the full ones see no position
    # signal (a stack that has both kinds) | "linear": the linear_attention
    # layers alone, the sparse ones see none
    ("rope_layers", "all"),
    # layer_types "linear_attention": attention without a softmax, a
    # RECURRENCE a head: S_t = decay_h S_{t-1} + v_t (outer) k_t, o_t = S_t
    # q_t / sqrt(ssm_state), over `ssm_heads` heads with values of
    # `ssm_head_dim` and keys of `ssm_state` features (`ssm_groups` =
    # `ssm_heads`: a head's k and q are its own), exp(`linear_log_decay`[h])
    # the fixed decay of head h.  Its slot state [ssm_heads, ssm_head_dim,
    # ssm_state] fp32 IS the kind `ssm` (a decayed running sum, read and
    # rewritten whole by every token: `slot_state.KINDS`), its step
    # `pallas_kernels.ssm_update` and its prefill `ssd_chunked_scan` with
    # dt = 1, through the same `scan` callback as a state-space mixer's;
    # no conv, no dt projection, no D
    ("linear_log_decay", ()),
    # layer_types "sparse_attention": grouped-query attention over the
    # SELECTED blocks of `sparse_block` positions only.  Beside its K/V
    # rows (the kind `kv`, as an attention layer's) it keeps an INDEXER's
    # cache (the kind `index`): compressed key j of a K/V head is the mean
    # of keys `sparse_kernel_stride` * j .. + `sparse_kernel_size` - 1,
    # seen once the last of them is cached.  A position scores the seen
    # compressed keys (softmax a query head, summed over the K/V head's
    # group), a block takes the max over the compressed keys that overlap
    # it, block 0 .. `sparse_init_blocks` - 1 and the blocks that hold the
    # last `sparse_window` positions are forced, and the `sparse_topk`
    # highest are attended over (`_sparse_select`).  0 = no sparse layer
    ("sparse_block", 0),
    ("sparse_topk", 0),
    ("sparse_init_blocks", 0),
    ("sparse_window", 0),
    ("sparse_kernel_size", 0),
    ("sparse_kernel_stride", 0),
    # a mixer's (sparse_attention, linear_attention) output times
    # sigmoid(h @ wg) before its output projection, and a linear_attention
    # layer's output RMS-normed a head (gain `on_g`) before that gate
    ("output_gate", False),
    ("output_norm", False),
    # a prefill runs the prompt in CHUNKS of this many positions inside its
    # one executable (`_prefill_chunks`: the layers' temporaries are a
    # chunk's, whatever the bucket; what it carries from chunk to chunk is
    # the slot state itself: the K/V rows and compressed keys written so
    # far, the linear layers' states); 0 = the bucket whole.  A stack of
    # sparse_attention / linear_attention layers
    ("prefill_chunk", 0),
    # an attending layer's geometry BY KIND and BY LEAF (none named: the one
    # geometry above).  `window_kv_heads`: the window_attention layers' own
    # K/V head count (0 = n_kv_heads), so the rings' rows are not the full
    # tables'.  A VALUE head of `v_head_dim` lanes beside a key head of
    # `head_dim` (0 = head_dim; the key an mla stack reads for itself): wv
    # and the V rows are K/V heads x v_head_dim wide, wo takes n_heads x
    # v_head_dim, the scale stays 1/sqrt(head_dim).  `rotary_dim`: the first
    # that many lanes of a q / k head turn (half-split among themselves),
    # the rest pass through (0 = the whole head).  `window_rope_theta`: the
    # window layers' own theta (0 = rope_theta).  `value_scale`: v times
    # this, before it is cached.  `window_sink`: a window layer's softmax
    # has one learned logit a query head more in its denominator, with no
    # value (weight `sink` [n_heads] f32): p_j = exp(a_j - m) / (exp(s_h -
    # m) + sum_j exp(a_j - m))
    ("window_kv_heads", 0),
    ("rotary_dim", 0),
    ("window_rope_theta", 0.0),
    ("value_scale", 1.0),
    ("window_sink", False),
    # layer_types "ssm": a state-space (Mamba-2) mixer as the layer's ONLY
    # operator (`GenerativePredictor._ssm`, the one an attention+ssm layer
    # runs beside its attention; the `ssm_*` keys above size it): its slot
    # state is the conv's last inputs and the scanned state, no K/V rows.
    # `attention_multiplier`: the softmax scale of the attending layers, q . k
    # times this (0 = 1/sqrt(head_dim)).  `residual_multiplier`: what every
    # sublayer's result is multiplied by as it joins the residual stream
    # (1 = none)
    ("attention_multiplier", 0.0),
    ("residual_multiplier", 1.0),
)
# the keys added since the phases' rev 12, with their defaults
# (`GenerativePredictor._fingerprint`), BY NAME: a key appended to
# BLOCK_DEFAULTS after them joins this tuple or bumps the rev, and either
# way moves none of these out
_LATER_KEYS = {k: dict(BLOCK_DEFAULTS)[k] for k in (
    "window_kv_heads", "rotary_dim", "window_rope_theta", "value_scale",
    "window_sink", "attention_multiplier", "residual_multiplier")}
_BLOCK_CHOICES = {"norm": ("layernorm", "rmsnorm"),
                  "position": ("learned", "rope", "none"),
                  "qk_norm": (False, True, "head"),
                  # "swiglu": ONE dense gated FFN of `dense_width` in every
                  # layer
                  "ffn": ("relu_mlp", "moe_swiglu", "swiglu"),
                  "router": ("softmax", "sigmoid_bias", "sigmoid"),
                  "head": ("untied", "tied"),
                  "weight_dtype": ("float32", "bfloat16"),
                  "rope_layers": ("all", "window", "linear")}
# a layer's operators, each with the kinds of slot state it keeps
_LAYER_TYPES = tuple(slot_state.HOLDS)
_SSM_DIMS = ("ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups")
_MLA_DIMS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim")
_SPARSE_DIMS = ("sparse_block", "sparse_topk", "sparse_init_blocks",
                "sparse_window", "sparse_kernel_size",
                "sparse_kernel_stride")
# the operators of a stack that prefills in chunks (`_prefill_chunks`)
_CHUNKED_OPS = ("sparse_attention", "linear_attention")


def _chunk_unit(blk):
    """What a prefill chunk of a stack of `_CHUNKED_OPS` layers is whole
    numbers of: a sparse block and a linear layer's scan chunk."""
    return int(np.lcm(blk["sparse_block"] or 1, blk["ssm_chunk"]
                      if "linear_attention" in blk["layer_types"] else 1))


def block_of(meta):
    """The stack description of a decode artifact's meta: every key of
    BLOCK_DEFAULTS, defaulted where the meta is silent, typed and
    checked.  A value this module has no math for is a typed error that
    names the key."""
    out = {}
    for key, default in BLOCK_DEFAULTS:
        v = meta.get(key, default)
        if key == "qk_norm":
            v = bool(v) if isinstance(v, (bool, int)) else v
        elif key == "layer_types":
            v = tuple(str(t) for t in v)
        elif key == "experts_held":
            v = tuple(int(t) for t in v)
        elif key in ("ssm_multipliers", "mlp_multipliers",
                     "linear_log_decay"):
            v = tuple(float(t) for t in v)
        else:
            v = type(default)(v)
        if key in _BLOCK_CHOICES and v not in _BLOCK_CHOICES[key]:
            raise ValueError("decode meta %s=%r is not one of %s"
                             % (key, v, "|".join(
                                 str(c) for c in _BLOCK_CHOICES[key])))
        out[key] = v
    if out["ffn"] == "moe_swiglu" and not (
            1 <= out["experts_per_token"] <= out["n_experts"]
            and out["expert_width"] >= 1):
        raise ValueError(
            "decode meta ffn=moe_swiglu needs 1 <= experts_per_token (%d) "
            "<= n_experts (%d) and expert_width (%d) >= 1"
            % (out["experts_per_token"], out["n_experts"],
               out["expert_width"]))
    n_layers, n_heads = int(meta["n_layers"]), int(meta["n_heads"])
    kinds = out["layer_types"]
    if out["head_dim"] < 0 or (out["head_dim"] and "mla" in kinds):
        raise ValueError(
            "decode meta head_dim=%d: a head's size is >= 1 (0 = d_model // "
            "n_heads), and an mla layer's heads are sized by its own keys"
            % out["head_dim"])
    if out["position"] == "rope" and "mla" not in kinds and (
            out["head_dim"] or int(meta["d_model"]) // n_heads) % 2:
        raise ValueError("decode meta position=rope needs an even "
                         "head size")
    if kinds and (len(kinds) != n_layers
                  or any(t not in _LAYER_TYPES for t in kinds)
                  or not {"attention", "mla", "attention+ssm",
                          "sparse_attention"} & set(kinds)):
        raise ValueError(
            "decode meta layer_types=%r needs one of %s for each of the %d "
            "layers, and an attention layer among them"
            % (list(kinds), "|".join(_LAYER_TYPES), n_layers))
    windowed = "window_attention" in kinds
    if out["sliding_window"] < 0 or windowed != bool(out["sliding_window"]):
        raise ValueError(
            "decode meta sliding_window=%d: >= 1 where layer_types has a "
            "window_attention layer, which attends over that many "
            "positions, and 0 where it has none (layer_types=%r)"
            % (out["sliding_window"], list(kinds)))
    if windowed and not {"attention", "attention+ssm"} & set(kinds):
        raise ValueError(
            "decode meta layer_types=%r: window_attention layers go beside "
            "layers that attend over every position (a session's length "
            "and room are its full-length table's; mla keeps no rows a ring "
            "could hold)" % (list(kinds),))
    if out["rope_layers"] == "window" and not (
            windowed and out["position"] == "rope"):
        raise ValueError(
            "decode meta rope_layers=window goes with position=rope (%r) "
            "and a stack with window_attention layers beside its full ones "
            "(layer_types=%r)" % (out["position"], list(kinds)))
    chunked = set(kinds) & set(_CHUNKED_OPS)
    if chunked and not set(kinds) <= set(_CHUNKED_OPS):
        raise ValueError(
            "decode meta layer_types=%r mixes %s with other operators: a "
            "stack with such layers prefills in chunks, which carries the "
            "state of these two alone" % (list(kinds), "|".join(sorted(
                chunked))))
    sparse = "sparse_attention" in kinds
    if not (all(out[k] >= 1 for k in _SPARSE_DIMS) if sparse
            else all(out[k] == 0 for k in _SPARSE_DIMS)):
        raise ValueError(
            "decode meta %s: each >= 1 where layer_types has a "
            "sparse_attention layer and 0 where it has none "
            "(layer_types=%r)" % (", ".join(
                "%s=%d" % (k, out[k]) for k in _SPARSE_DIMS), list(kinds)))
    if sparse and (out["sparse_kernel_size"] % out["sparse_kernel_stride"]
                   or out["sparse_block"] % out["sparse_kernel_stride"]
                   or int(meta["max_seq_len"]) % out["sparse_block"]
                   or out["sparse_topk"] * out["sparse_block"]
                   < out["sparse_window"] + 2 * out["sparse_block"]):
        raise ValueError(
            "decode meta sparse_kernel_size=%d and sparse_block=%d are "
            "whole strides (sparse_kernel_stride=%d), max_seq_len %d whole "
            "blocks, and sparse_topk=%d blocks hold the init block and the "
            "sparse_window=%d forced positions"
            % (out["sparse_kernel_size"], out["sparse_block"],
               out["sparse_kernel_stride"], int(meta["max_seq_len"]),
               out["sparse_topk"], out["sparse_window"]))
    linear = "linear_attention" in kinds
    if linear:
        for key in _SSM_DIMS:
            if out[key] < 1:
                raise ValueError("decode meta %s=%d: a linear_attention "
                                 "layer needs it >= 1" % (key, out[key]))
        if out["ssm_groups"] != out["ssm_heads"] or out["ssm_conv_kernel"]:
            raise ValueError(
                "decode meta ssm_groups=%d, ssm_conv_kernel=%d: a "
                "linear_attention layer's k and q are a head's own "
                "(ssm_groups = ssm_heads %d) and it has no conv in front"
                % (out["ssm_groups"], out["ssm_conv_kernel"],
                   out["ssm_heads"]))
        if len(out["linear_log_decay"]) != out["ssm_heads"] or any(
                a > 0.0 for a in out["linear_log_decay"]):
            raise ValueError(
                "decode meta linear_log_decay=%r is not one number <= 0 "
                "(the log of its fixed decay) for each of the %d heads"
                % (list(out["linear_log_decay"]), out["ssm_heads"]))
        if out["position"] == "rope" and out["ssm_state"] % 2:
            raise ValueError("decode meta position=rope needs an even "
                             "ssm_state (a linear head's key size)")
    elif out["linear_log_decay"] or out["output_norm"]:
        raise ValueError("decode meta linear_log_decay / output_norm go "
                         "with layer_types linear_attention")
    if out["rope_layers"] == "linear" and not (
            linear and out["position"] == "rope"):
        raise ValueError(
            "decode meta rope_layers=linear goes with position=rope (%r) "
            "and a stack with linear_attention layers (layer_types=%r)"
            % (out["position"], list(kinds)))
    if out["output_gate"] and not chunked:
        raise ValueError("decode meta output_gate goes with layer_types "
                         "sparse_attention|linear_attention")
    chunk = out["prefill_chunk"]
    if chunk < 0 or (chunk and not chunked):
        raise ValueError(
            "decode meta prefill_chunk=%d: >= 1 for a stack of "
            "sparse_attention / linear_attention layers, which prefills "
            "in chunks, 0 = whole (layer_types=%r)" % (chunk, list(kinds)))
    if chunked:
        # what a chunk has to be whole numbers of
        unit = _chunk_unit(out)
        # max_seq_len among them: a prompt past every bucket prefills in the
        # next whole number of chunks (`prompt_bucket`), which the cache holds
        buckets = [int(b) for b in meta.get("prefill_buckets") or (
            _default_prefill_buckets(int(meta["max_seq_len"])))]
        if chunk % unit or any(b % (chunk or unit) for b in buckets + [
                int(meta["max_seq_len"])]):
            raise ValueError(
                "decode meta prefill_chunk=%d, prefill_buckets=%r, "
                "max_seq_len=%d: a bucket and the cache are whole chunks "
                "and a chunk whole sparse blocks and scan chunks (%d "
                "positions)" % (chunk, buckets, int(meta["max_seq_len"]),
                                unit))
    if "mla" in kinds:
        if set(kinds) != {"mla"}:
            raise ValueError(
                "decode meta layer_types=%r mixes mla with other layers: a "
                "session holds a latent table or K/V tables, not both"
                % (list(kinds),))
        for key in _MLA_DIMS:
            if out[key] < 1:
                raise ValueError("decode meta %s=%d: an mla layer needs it "
                                 ">= 1" % (key, out[key]))
        if out["qk_rope_head_dim"] % 2 or out["position"] != "rope":
            raise ValueError(
                "decode meta qk_rope_head_dim=%d, position=%r: an mla "
                "layer turns an even number of rope lanes (position=rope)"
                % (out["qk_rope_head_dim"], out["position"]))
        for key in ("qk_norm", "n_kv_heads"):
            if out[key]:
                raise ValueError(
                    "decode meta %s=%r does not go with layer_types mla "
                    "(its one shared row has no heads to norm or group)"
                    % (key, out[key]))
    mixers = "|".join(t for t in slot_state.SSM_OPS if t in kinds)
    if mixers:
        if "conv" in kinds:
            raise ValueError(
                "decode meta layer_types=%r mixes conv with %s "
                "layers: a session's conv-state table has one width"
                % (list(kinds), mixers))
        for key in _SSM_DIMS + ("ssm_chunk",):
            if out[key] < 1:
                raise ValueError("decode meta %s=%d: an %s layer "
                                 "needs it >= 1" % (key, out[key], mixers))
        if out["ssm_conv_kernel"] < 2:
            raise ValueError("decode meta ssm_conv_kernel=%d: an "
                             "%s layer's conv needs at least 2 "
                             "taps" % (out["ssm_conv_kernel"], mixers))
        if out["ssm_heads"] % out["ssm_groups"]:
            raise ValueError("decode meta ssm_groups=%d does not divide "
                             "ssm_heads %d" % (out["ssm_groups"],
                                               out["ssm_heads"]))
        if out["ssm_multipliers"] and len(out["ssm_multipliers"]) != 5:
            raise ValueError(
                "decode meta ssm_multipliers=%r is not one number for each "
                "of the segments z, x, B, C, dt"
                % (list(out["ssm_multipliers"]),))
        if linear:
            raise ValueError(
                "decode meta layer_types=%r mixes %s with "
                "linear_attention layers: a session's scanned-state table "
                "has one shape" % (list(kinds), mixers))
    elif out["ssm_multipliers"] or any(
            out[k] != 1.0 for k in ("ssm_in_multiplier",
                                    "ssm_out_multiplier")):
        raise ValueError("decode meta ssm_*multiplier* goes with "
                         "layer_types attention+ssm|ssm")
    if out["attention_multiplier"] < 0.0 or (
            out["attention_multiplier"] and ("mla" in kinds or chunked)):
        raise ValueError(
            "decode meta attention_multiplier=%r: the softmax scale of "
            "attention | attention+ssm | window_attention layers, > 0 (0 = "
            "1/sqrt(head_dim)); mla, sparse_attention and linear_attention "
            "layers scale their scores themselves (layer_types=%r)"
            % (out["attention_multiplier"], list(kinds)))
    if out["residual_multiplier"] != 1.0 and out["ffn"] == "relu_mlp":
        raise ValueError(
            "decode meta residual_multiplier=%r goes with ffn=swiglu|"
            "moe_swiglu (the GPT-2-shaped block's MLP joins the residual "
            "stream with its bias, unscaled)" % out["residual_multiplier"])
    if out["ffn"] == "swiglu" and (out["dense_width"] < 1
                                   or out["n_dense_layers"]):
        raise ValueError(
            "decode meta ffn=swiglu needs dense_width (%d) >= 1 and no "
            "n_dense_layers (%d): every layer's FFN is that one"
            % (out["dense_width"], out["n_dense_layers"]))
    if out["mlp_multipliers"] and (len(out["mlp_multipliers"]) != 2
                                   or out["ffn"] != "swiglu"):
        raise ValueError(
            "decode meta mlp_multipliers=%r is (gate, down) of ffn=swiglu"
            % (list(out["mlp_multipliers"]),))
    if "mla" in kinds and any(
            out[k] != 1.0 for k in ("attention_in_multiplier",
                                    "key_multiplier",
                                    "attention_out_multiplier")):
        raise ValueError("decode meta attention/key multipliers do not go "
                         "with layer_types mla")
    if "conv" in kinds and out["conv_kernel"] < 2:
        raise ValueError("decode meta conv_kernel=%d: a conv layer needs "
                         "at least 2 taps" % out["conv_kernel"])
    for key in ("n_kv_heads", "window_kv_heads"):
        if out[key] and (out[key] < 0 or n_heads % out[key]):
            raise ValueError("decode meta %s=%d does not divide n_heads %d"
                             % (key, out[key], n_heads))
    for key in ("window_kv_heads", "window_rope_theta", "window_sink"):
        if out[key] and not windowed:
            raise ValueError(
                "decode meta %s=%r goes with layer_types window_attention "
                "(layer_types=%r)" % (key, out[key], list(kinds)))
    _, dk, dv = slot_state.attention_geometry(meta, out)
    if "mla" not in kinds and (out["v_head_dim"] < 0 or (
            dv != dk and "sparse_attention" in kinds)):
        raise ValueError(
            "decode meta v_head_dim=%d: a value head's size is >= 1 (0 = "
            "head_dim), and a sparse_attention layer's kernels take values "
            "as wide as keys (head_dim %d)" % (out["v_head_dim"], dk))
    if "mla" not in kinds and dv == dk:
        out["v_head_dim"] = 0       # said or not, one width: one description
    rotary = out["rotary_dim"]
    if rotary and (rotary < 0 or rotary % 2 or rotary > dk
                   or out["position"] != "rope"
                   or {"mla", "linear_attention"} & set(kinds)):
        raise ValueError(
            "decode meta rotary_dim=%d: an even number of an attention "
            "head's %d lanes under position=rope (%r); mla and "
            "linear_attention layers size their rotated lanes themselves "
            "(layer_types=%r)" % (rotary, dk, out["position"], list(kinds)))
    if out["window_rope_theta"] and (out["window_rope_theta"] < 0.0
                                     or out["position"] != "rope"
                                     or out["rope_layers"] == "linear"):
        raise ValueError(
            "decode meta window_rope_theta=%r: the window layers' theta "
            "under position=rope (%r) with rope_layers all|window (%r)"
            % (out["window_rope_theta"], out["position"],
               out["rope_layers"]))
    if out["value_scale"] != 1.0 and "mla" in kinds:
        raise ValueError("decode meta value_scale=%r does not go with "
                         "layer_types mla (its values are its latent rows)"
                         % out["value_scale"])
    if not 0 <= out["n_dense_layers"] <= n_layers or (
            out["n_dense_layers"] and out["dense_width"] < 1):
        raise ValueError(
            "decode meta n_dense_layers=%d needs 0 <= it <= n_layers (%d) "
            "and dense_width (%d) >= 1"
            % (out["n_dense_layers"], n_layers, out["dense_width"]))
    if out["router"] != "softmax" and out["ffn"] != "moe_swiglu":
        raise ValueError("decode meta router=%r goes with ffn=moe_swiglu"
                         % out["router"])
    for key in ("n_shared_experts", "experts_held", "sandwich_norm"):
        if out[key] and out["ffn"] != "moe_swiglu":
            raise ValueError("decode meta %s=%r goes with ffn=moe_swiglu"
                             % (key, out[key]))
    if out["routed_scaling"] != 1.0 and out["router"] not in (
            "sigmoid", "sigmoid_bias"):
        raise ValueError("decode meta routed_scaling=%r goes with "
                         "router=sigmoid|sigmoid_bias"
                         % out["routed_scaling"])
    held = out["experts_held"]
    if held and (len(held) != 2 or held[0] < 0 or held[1] < 1
                 or held[0] + held[1] > out["n_experts"]):
        raise ValueError(
            "decode meta experts_held=%r is not (first, count) of a run "
            "inside the %d experts" % (list(held), out["n_experts"]))
    if out["n_shared_experts"] < 0:
        raise ValueError("decode meta n_shared_experts=%d"
                         % out["n_shared_experts"])
    return out


def layer_kinds(meta, blk=None):
    """(operator, FFN) of every layer of the stack `meta` describes
    (`blk`: its `block_of`, where the caller has it): operator
    "attention" | "conv" | "mla" | "attention+ssm" | "window_attention" |
    "sparse_attention" | "linear_attention" | "ssm", FFN "dense_swiglu"
    (the first `n_dense_layers`; every layer under ffn=swiglu) or the
    meta's `ffn`."""
    blk = blk or block_of(meta)
    n = int(meta["n_layers"])
    ops = blk["layer_types"] or ("attention",) * n
    dense = n if blk["ffn"] == "swiglu" else blk["n_dense_layers"]
    return [(ops[i], "dense_swiglu" if i < dense else blk["ffn"])
            for i in range(n)]


def slot_state_shapes(meta, n_slots, device):
    """(K/V table shape (an MLA stack: its latent table's), conv-state
    table shape or None, scanned-state table shape or None): three of
    `slot_state.kind_shapes`, for the callers that unpack three."""
    held = slot_state.kind_shapes(meta, block_of(meta), n_slots, device)
    return (held.get("kv") or held.get("latent"), held.get("conv"),
            held.get("ssm"))


def window_state_shape(meta, n_slots):
    """[window layers, N, W, Hc * Dh], the K (or V) ring of an `n_slots`
    session (`slot_state.kind_shapes`); None for a stack with none."""
    return slot_state.kind_shapes(meta, block_of(meta), n_slots,
                                  None).get("ring")


def decode_state_shapes(meta):
    """{weight name: shape} of the state a decode artifact with this
    meta holds — what `save_decode_model` checks a state against."""
    blk = block_of(meta)
    V, D, H, S = (int(meta[k]) for k in
                  ("vocab_size", "d_model", "n_heads", "max_seq_len"))
    Dh = _head_dim(meta, blk)
    norm_bias = blk["norm"] == "layernorm"
    norms = ("ln1", "ln2") + (("ln1p", "ln2p") if blk["sandwich_norm"]
                              else ())
    shapes = {"embed": (V, D), "lnf_g": (D,)}
    if blk["head"] == "untied":
        shapes["lm_head"] = (D, V)
    if norm_bias:
        shapes["lnf_b"] = (D,)
    if blk["position"] == "learned":
        shapes["pos"] = (S, D)
    for i, (op, ffn) in enumerate(layer_kinds(meta, blk)):
        p = "l%d_" % i
        for n in norms:
            shapes[p + n + "_g"] = (D,)
            if norm_bias:
                shapes[p + n + "_b"] = (D,)
        if op == "mla":
            rq, rkv, dn, dr, dv = (blk[k] for k in _MLA_DIMS)
            shapes[p + "wq_a"], shapes[p + "q_a_g"] = (D, rq), (rq,)
            shapes[p + "wq_b"] = (rq, H * (dn + dr))
            shapes[p + "wkv_a"], shapes[p + "kv_a_g"] = (D, rkv + dr), (rkv,)
            shapes[p + "wkv_b"] = (rkv, H * (dn + dv))
            shapes[p + "wo"] = (H * dv, D)
        elif op == "conv":
            shapes[p + "conv_in"] = (D, 3 * D)
            shapes[p + "conv_w"] = (D, blk["conv_kernel"])
            shapes[p + "conv_out"] = (D, D)
        elif op == "linear_attention":
            Hs, P, Ns = (blk[k] for k in _SSM_DIMS[:3])
            shapes[p + "wq"] = shapes[p + "wk"] = (D, Hs * Ns)
            shapes[p + "wv"], shapes[p + "wo"] = (D, Hs * P), (Hs * P, D)
            if blk["qk_norm"] == "head":
                shapes[p + "qn_g"] = shapes[p + "kn_g"] = (Ns,)
            elif blk["qk_norm"]:
                shapes[p + "qn_g"] = shapes[p + "kn_g"] = (Hs * Ns,)
            if blk["output_norm"]:
                shapes[p + "on_g"] = (Hs * P,)
            if blk["output_gate"]:
                shapes[p + "wg"] = (D, Hs * P)
        elif op != "ssm":
            # the layer's own geometry: K/V heads by kind, K and V rows
            # by leaf
            Hc, _, Dv = slot_state.attention_geometry(
                meta, blk, window=op == "window_attention")
            shapes[p + "wq"], shapes[p + "wo"] = (D, H * Dh), (H * Dv, D)
            shapes[p + "wk"], shapes[p + "wv"] = (D, Hc * Dh), (D, Hc * Dv)
            if blk["qk_norm"] == "head":
                shapes[p + "qn_g"] = shapes[p + "kn_g"] = (Dh,)
            elif blk["qk_norm"]:
                shapes[p + "qn_g"], shapes[p + "kn_g"] = (H * Dh,), (
                    Hc * Dh,)
            if blk["output_gate"]:
                shapes[p + "wg"] = (D, H * Dv)
            if blk["window_sink"] and op == "window_attention":
                shapes[p + "sink"] = (H,)
        if op in slot_state.SSM_OPS:
            d_ssm, conv, wide = _ssm_widths(blk)
            Hs = blk["ssm_heads"]
            shapes[p + "ssm_in"], shapes[p + "ssm_out"] = (D, wide), (d_ssm,
                                                                      D)
            shapes[p + "ssm_conv_w"] = (conv, blk["ssm_conv_kernel"])
            shapes[p + "ssm_conv_b"] = (conv,)
            shapes[p + "ssm_dt_bias"] = shapes[p + "ssm_A_log"] = \
                shapes[p + "ssm_D"] = (Hs,)
            shapes[p + "ssm_norm_g"] = (d_ssm,)
        if ffn == "dense_swiglu":
            F = blk["dense_width"]
            shapes[p + "ffn_gate"] = shapes[p + "ffn_up"] = (D, F)
            shapes[p + "ffn_down"] = (F, D)
        elif ffn == "moe_swiglu":
            E, F = blk["n_experts"], blk["expert_width"]
            shapes[p + "router"] = (D, E)
            if blk["router"] == "sigmoid_bias":
                shapes[p + "expert_bias"] = (E,)
            if blk["experts_held"]:
                E = blk["experts_held"][1]
            shapes[p + "w_gate"] = shapes[p + "w_up"] = (E, D, F)
            shapes[p + "w_down"] = (E, F, D)
            if blk["n_shared_experts"]:
                Fs = blk["n_shared_experts"] * F
                shapes[p + "shared_gate"] = shapes[p + "shared_up"] = (D, Fs)
                shapes[p + "shared_down"] = (Fs, D)
        else:
            shapes[p + "w1"], shapes[p + "b1"] = (D, 4 * D), (4 * D,)
            shapes[p + "w2"], shapes[p + "b2"] = (4 * D, D), (D,)
    return shapes


def _bf16_at_rest(name, value):
    """Under meta weight_dtype=bfloat16: whether the weight `name` is kept
    in bfloat16 at rest.  The matmul weights are (attention, dense, shared
    and routed FFN, the SSM's in and out projections, embedding, head):
    every matrix but a router's, which is read at "highest" precision
    (`moe_ffn`), and an attention+ssm layer's depthwise taps (`ssm_conv_w`
    [channels, taps]: two axes but no matmul's operand, 82 KB a layer,
    multiplied into a float32 window element by element); gains, biases
    and the SSM's vectors (`ssm_dt_bias`, `ssm_A_log`, `ssm_D`) are not."""
    return np.ndim(value) >= 2 and not name.endswith(("_router",
                                                      "_ssm_conv_w"))


def save_decode_model(dirname, state, meta):
    """Write a decode artifact: `meta` (vocab_size, d_model, n_heads,
    n_layers, max_seq_len, eos_id, dtype, prefill_buckets, and the
    decoder block's keys — BLOCK_DEFAULTS; an artifact that names none
    is the GPT-2-shaped block) + `state` (the weight dict) in the typed
    wire format — no pickle, same discipline as save_aot.  A state that
    lacks a weight the described block reads, or holds one of another
    shape, is refused here and not at the first trace.  Under meta
    weight_dtype=bfloat16 the matmul weights are written, and held by the
    predictor that opens them, in bfloat16 (`_bf16_at_rest`; a state that
    brings them in bfloat16 is written as it is)."""
    from paddle_tpu.native import wire
    os.makedirs(dirname, exist_ok=True)
    meta = dict(meta)
    for name, shape in decode_state_shapes(meta).items():
        if name not in state or tuple(np.shape(state[name])) != shape:
            raise ValueError(
                "decode state %r: the meta's block needs shape %s, the "
                "state has %s" % (name, shape, np.shape(state[name])
                                  if name in state else "no such weight"))
    meta.setdefault("arch", "causal_lm")
    meta.setdefault("version", 1)
    meta.setdefault("dtype", "float32")
    # the per-artifact KV-cache dtype pin (QUANTIZE.md "Quantized KV
    # cache"); load_model's kv_cache_dtype knob overrides per load,
    # and an artifact with NO pin defers to FLAGS.serving_kv_cache_dtype
    # at open time — so only normalize a pin the caller actually set
    if meta.get("kv_cache_dtype"):
        meta["kv_cache_dtype"] = normalize_kv_dtype(
            meta["kv_cache_dtype"])
    meta.setdefault("prefill_buckets",
                    _default_prefill_buckets(meta["max_seq_len"]))
    state = {n: np.asarray(v) for n, v in state.items()}
    if block_of(meta)["weight_dtype"] == "bfloat16":
        import ml_dtypes
        state = {n: v.astype(ml_dtypes.bfloat16, copy=False)
                 if _bf16_at_rest(n, v)
                 else v for n, v in state.items()}
    with open(os.path.join(dirname, _DECODE_STATE), "wb") as f:
        f.write(wire.encode(state))
    with open(os.path.join(dirname, DECODE_META), "wb") as f:
        f.write(wire.encode(meta))
    return dirname


def build_tiny_decode_model(dirname, vocab_size=32, d_model=16,
                            n_heads=2, n_layers=2, max_seq_len=64,
                            eos_id=0, seed=7, prefill_buckets=None,
                            block=None):
    """Deterministic random-weight tiny causal LM — the CPU-smoke /
    test fixture (the decode analogue of bench_serving's `fc` model).
    Same seed -> bit-identical artifact.  `prefill_buckets` pins the
    artifact's prompt buckets (default: powers of two up to
    max_seq_len) — every bucket is one warm-up compile.  `block` names
    the decoder block's meta keys (BLOCK_DEFAULTS; None: the GPT-2-shaped
    one): its weights are drawn in name order, matrices normal(0,
    1/sqrt(fan_in)) (a conv layer's taps fan in over the taps), gains 1,
    biases 0, a router's expert bias normal(0, 0.05) (zero would make
    selection by biased score the selection by score), an attention+ssm
    layer's `ssm_dt_bias`, `ssm_A_log`, `ssm_D` and `ssm_conv_b`
    normal(0, 0.5)."""
    if d_model % n_heads and not (block or {}).get("head_dim"):
        raise ValueError("d_model %d not divisible by n_heads %d"
                         % (d_model, n_heads))
    rng = np.random.RandomState(seed)
    if block:
        meta = dict(block, vocab_size=int(vocab_size),
                    d_model=int(d_model), n_heads=int(n_heads),
                    n_layers=int(n_layers), max_seq_len=int(max_seq_len),
                    eos_id=int(eos_id))
        if prefill_buckets:
            meta["prefill_buckets"] = sorted(int(b)
                                             for b in prefill_buckets)
        state = {}
        for name, shape in sorted(decode_state_shapes(meta).items()):
            if name.endswith("_expert_bias"):
                state[name] = (0.05 * rng.randn(*shape)).astype(np.float32)
            elif name.endswith(("_ssm_dt_bias", "_ssm_A_log", "_ssm_D",
                                "_ssm_conv_b")):
                # an SSM's vectors: at zero the decay, the skip and the
                # conv's bias would go untested
                state[name] = (0.5 * rng.randn(*shape)).astype(np.float32)
            elif len(shape) == 1:
                state[name] = (np.ones if name.endswith("_g")
                               else np.zeros)(shape, np.float32)
            else:
                fan_in = shape[-1 if name.endswith("_conv_w") else -2]
                state[name] = (rng.randn(*shape) / np.sqrt(
                    fan_in)).astype(np.float32)
        return save_decode_model(dirname, state, meta)
    scale = 1.0 / np.sqrt(d_model)

    def w(*shape):
        return (rng.randn(*shape) * scale).astype(np.float32)

    state = {"embed": w(vocab_size, d_model),
             "pos": w(max_seq_len, d_model),
             "lnf_g": np.ones(d_model, np.float32),
             "lnf_b": np.zeros(d_model, np.float32),
             "lm_head": w(d_model, vocab_size)}
    for i in range(n_layers):
        p = "l%d_" % i
        state[p + "ln1_g"] = np.ones(d_model, np.float32)
        state[p + "ln1_b"] = np.zeros(d_model, np.float32)
        state[p + "wq"] = w(d_model, d_model)
        state[p + "wk"] = w(d_model, d_model)
        state[p + "wv"] = w(d_model, d_model)
        state[p + "wo"] = w(d_model, d_model)
        state[p + "ln2_g"] = np.ones(d_model, np.float32)
        state[p + "ln2_b"] = np.zeros(d_model, np.float32)
        state[p + "w1"] = w(d_model, 4 * d_model)
        state[p + "b1"] = np.zeros(4 * d_model, np.float32)
        state[p + "w2"] = w(4 * d_model, d_model)
        state[p + "b2"] = np.zeros(d_model, np.float32)
    meta = {"vocab_size": int(vocab_size), "d_model": int(d_model),
            "n_heads": int(n_heads), "n_layers": int(n_layers),
            "max_seq_len": int(max_seq_len), "eos_id": int(eos_id)}
    if prefill_buckets:
        meta["prefill_buckets"] = sorted(int(b) for b in prefill_buckets)
    return save_decode_model(dirname, state, meta)


def _ln(x, g, b, eps=1e-5):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _rms(x, g, eps):
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rope(x, positions, theta, lanes=0):
    """Rotary position embedding over the whole head, half-split
    convention: x [..., H, Dh] at `positions` [...] (one per leading
    index) -> x * cos + concat(-x2, x1) * sin with the angles
    position * theta^(-2i/Dh) repeated over both halves.  With `lanes`
    (meta rotary_dim) the head's first `lanes` lanes turn, as a head of
    that size would, and the rest pass through."""
    import jax.numpy as jnp
    if lanes and lanes < x.shape[-1]:
        return jnp.concatenate(
            [_rope(x[..., :lanes], positions, theta), x[..., lanes:]],
            axis=-1)
    half = x.shape[-1] // 2
    inv = jnp.float32(theta) ** (
        jnp.arange(half, dtype=jnp.float32) * (-2.0 / x.shape[-1]))
    ang = jnp.asarray(positions).astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)              # [..., 1, half]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _contract(x, w, contract):
    """`contract(x, w, **how)` for a weight `w` at rest in float32 or in
    bfloat16 (meta weight_dtype), result float32.  A float32 weight is
    contracted as it always was (`how` empty).  A bfloat16 weight on the
    TPU takes the activation rounded to bfloat16 and accumulates in
    float32: the very numbers the default precision gives a float32 copy
    of that weight (it rounds both operands to bf16), from half the bytes
    and with no float32 copy of the weight anywhere.  Off the TPU, where a
    float32 contraction rounds nothing, the weight is widened instead, so
    that there too bf16 storage computes what fp32 storage of the same
    values computes, bit for bit."""
    import jax.numpy as jnp
    if w.dtype != jnp.bfloat16:
        return contract(x, w)
    from paddle_tpu.ops.pallas_kernels import lowering_for_tpu
    if lowering_for_tpu():
        return contract(x.astype(jnp.bfloat16), w,
                        preferred_element_type=jnp.float32)
    return contract(x, w.astype(jnp.float32))


def _mm(x, w):
    """x @ w, float32, for a weight at rest in either dtype
    (`_contract`)."""
    import jax.numpy as jnp
    return _contract(x, w, jnp.matmul)


def _gated_group_norm(y, z, g, groups, eps):
    """rms(y * silu(z)) over each of `groups` equal groups of the last
    axis, times the gain g: a state-space mixer's gated norm, the gate
    BEFORE the norm."""
    import jax
    import jax.numpy as jnp
    y = (y * jax.nn.silu(z)).reshape(y.shape[:-1] + (groups, -1))
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + eps)
    return y.reshape(z.shape) * g


def _swiglu(h, gate, up, down, gate_by=1.0, down_by=1.0):
    """(silu(h gate) * (h up)) down: a dense SwiGLU FFN; the gate's
    pre-activation times `gate_by` and the result times `down_by` (meta
    mlp_multipliers) where they are not 1."""
    import jax
    g = _mm(h, gate)
    y = _mm(jax.nn.silu(g if gate_by == 1.0 else g * gate_by) * _mm(h, up),
            down)
    return y if down_by == 1.0 else y * down_by


def moe_ffn(h, router, w_gate, w_up, w_down, k, norm_topk_prob=False,
            live=None, expert_bias=None, picks=None, sigmoid=False,
            scaling=1.0, held=None):
    """Dropless, exact top-k routed SwiGLU experts: h [T, D], router
    [D, E], w_gate / w_up [E, D, F], w_down [E, F, D] ->
    (sum over each token's k experts of p_e * ((silu(h @ w_gate[e]) *
    (h @ w_up[e])) @ w_down[e]) [T, D], facts [3] i32, [4] with `held`).  No
    capacity: every (token, expert) pair is computed, whatever the routing.

    ONE form for both regimes: the T * k pairs are sorted by expert and
    the three expert matmuls run as grouped matmuls over the sorted rows
    (`jax.lax.ragged_dot`; on the TPU a grouped-matmul kernel that visits
    only the groups that hold rows).  A decode step (a few tokens, most
    experts untouched) then streams the touched experts' weights and no
    others; a prefill (every expert gets tens of rows) does k / E of the
    dense formula's FLOPs.

    The router (matmul, softmax, top-k) runs in fp32 at "highest"
    precision: D x E is free, and a bf16-rounded router logit flips the
    k-th against the (k+1)-th expert on a near-tie, which is a different
    function and not a rounding.  `norm_topk_prob` renormalises the kept
    weights to sum to 1; without it they are the softmax's own values.
    With `expert_bias` [E] (meta router=sigmoid_bias) the scores are
    sigmoids, the k experts are those of the largest score + bias, their
    weights the UNBIASED scores, renormalised over (their sum + 1e-6)
    and multiplied by `scaling` where that is not 1.
    With `sigmoid` (meta router=sigmoid) the scores are sigmoids, the k
    experts those of the largest scores, renormalised over (their sum +
    1e-20) and multiplied by `scaling`.

    `held` = (first, count) (meta experts_held): w_gate / w_up / w_down
    hold the experts first .. first + count - 1 of the router's E and no
    others.  The router and its top-k are those of all E; a pair routed to
    an expert held elsewhere leaves BEFORE the sort (it joins no group of
    the grouped matmuls) and adds nothing to its token's sum: the result
    is this member's PART of the layer's.  The device's work follows the
    rows that STAY: the grouped matmuls run over the first `held_cap`
    sorted rows, where the pairs that stay lie, and each pair reads its row
    of that result; only a call whose routing crowds more pairs than that
    onto this member runs them over every pair's row (a `cond`; the fourth
    fact says which branch ran; none is built, and every call runs every
    row, where `held_cap` finds that fewer would not pay), with the same
    numbers: bit for bit where a
    grouped matmul's rows do not depend on how many it is given (the CPU),
    to its tiles' rounding on the TPU.

    facts = (experts HELD HERE that received a token, most tokens one of
    them received, the (token, expert) pairs that STAYED here: all T * k
    without `held`), counted over the tokens `live` [T] marks (all if None):
    a dead slot's or a pad position's row is computed but not counted; with
    `held` a fourth, 1 where the call ran full size (more pairs stayed,
    counted or not, than `held_cap` rows).
    A list given as `picks` receives the chosen experts [T, k] i32 (at
    trace time): what a comparison with a reference needs to tell the
    router's near-ties from a fault (`_step_logits`).

    Traced under `_prompts_share_experts` and a `vmap` over prompts (a
    group's prefill), the router, the weights and the first three facts
    stay a prompt's own and the sort, the grouped matmuls, the cap and the
    fourth fact are the GROUP's: one `ragged_dot` a projection over all the
    prompts' pairs."""
    import jax
    import jax.numpy as jnp
    T, E, k = h.shape[0], router.shape[1], int(k)
    with jax.named_scope("moe_ffn"):
        with jax.named_scope("moe_router"):
            logits = jnp.dot(h.astype(jnp.float32), router,
                             precision=jax.lax.Precision.HIGHEST)
            if sigmoid:
                w, idx = jax.lax.top_k(jax.nn.sigmoid(logits), k)
                if norm_topk_prob:
                    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
                w = w * scaling
            elif expert_bias is None:
                w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
                if norm_topk_prob:
                    w = w / jnp.sum(w, axis=-1, keepdims=True)
            else:
                p = jax.nn.sigmoid(logits)
                _, idx = jax.lax.top_k(p + expert_bias, k)      # [T, k]
                w = jnp.take_along_axis(p, idx, axis=-1)
                if norm_topk_prob:
                    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
                if scaling != 1.0:
                    w = w * scaling
        if picks is not None:
            picks.append(idx.astype(jnp.int32))
        flat = idx.reshape(T * k)
        if held is not None:
            # experts by their place HERE; a pair whose expert lives
            # elsewhere gets the place past the last: sorted behind every
            # group, a member of none, and weighted 0
            first, E = held
            here = (idx >= first) & (idx < first + E)
            flat = jnp.where(here, idx - first, E).reshape(T * k)
            w = jnp.where(here, w, 0.0)
        onehot = flat[:, None] == jnp.arange(E)[None]           # [T*k, E]
        sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)        # [E]
        counted = sizes if live is None else jnp.sum(
            onehot & jnp.repeat(live, k)[:, None], axis=0,
            dtype=jnp.int32)
        facts = jnp.stack([jnp.sum(counted > 0, dtype=jnp.int32),
                           jnp.max(counted), jnp.sum(counted)])

        def sorted_pairs(h, flat, sizes, w_gate, w_up, w_down):
            """(the pairs' order by expert; experts(m): the sorted pairs'
            rows, the first m of them or all if None, through their
            experts -> [m, D])."""
            order = jnp.argsort(flat)       # stable: pairs by expert

            def grouped(x, w):
                return _contract(x, w, functools.partial(
                    jax.lax.ragged_dot, group_sizes=sizes))

            def experts(m=None):
                at = order // k
                rows = h[at if m is None else at[:m]]           # [m, D]
                act = jax.nn.silu(grouped(rows, w_gate)) \
                    * grouped(rows, w_up)
                return grouped(act, w_down)                     # [m, D]
            return order, experts

        def through_experts(h, flat, sizes, w_gate, w_up, w_down):
            """Each pair's row through its expert -> [T * k, D], token t's
            j-th pair at t * k + j."""
            order, experts = sorted_pairs(h, flat, sizes, w_gate, w_up,
                                          w_down)
            # back to token order by a gather (the inverse permutation)
            return experts()[jnp.argsort(order)]

        def through_held(h, flat, sizes, w, w_gate, w_up, w_down):
            """This member's part of each token's sum -> ([T, D], whether
            the call ran full size).  The pairs that stay sort FIRST, and
            the grouped matmuls run over the first `cap` sorted rows
            (`held_cap`: twice the member's expected share, or every row
            where fewer would not pay); token t's j-th pair then reads its
            row of that [cap, D] result by its place in the sorted order,
            the one [pairs, D] gather of this branch.  A call whose routing
            crowds more than `cap` pairs onto this member takes the
            `cond`'s other branch, the grouped matmuls over every pair's
            row: dropless and exact either way, and a live row's number is
            the same in both."""
            order, experts = sorted_pairs(h, flat, sizes, w_gate, w_up,
                                          w_down)
            stay, pairs = jnp.sum(sizes), flat.shape[0]
            cap = held_cap(pairs, E, router.shape[1])
            pos = jnp.argsort(order)        # a pair's place in the order
            if cap == pairs:
                out, full_size = experts()[pos], jnp.int32(0)
            else:
                fits = stay <= cap
                out = jax.lax.cond(
                    fits, lambda: experts(cap)[jnp.minimum(pos, cap - 1)],
                    lambda: experts()[pos])
                full_size = 1 - fits.astype(jnp.int32)
            # (behind the `cond`, not in its branches: there Granite's
            # cell read 3.7% more tokens/s and MiMo's prefill held 27 / 73
            # MB more temporaries than its bound, PERF.md section 6, PR 57)
            # a row behind the last group is no expert's: whatever the
            # grouped matmul left there, it is nothing ...
            out = jnp.where((pos < stay)[:, None], out, 0.0)
            # ... then a fixed-order sum over each token's k experts
            return jnp.sum(out.reshape(w.shape + (-1,)) * w[:, :, None],
                           axis=1), full_size

        def for_a_group(fn, own):
            """`fn(h, flat, sizes, ...)` whose first `own` arguments are a
            prompt's and whose others are the experts' weights: under the
            `vmap` over a group's prompts
            (`GenerativePredictor._prefill_group_math`) the prompts' pairs
            are sorted TOGETHER into one grouped matmul: an expert's
            weights are read once a group, and a `ragged_dot` a prompt,
            which is what the primitive's own rule leaves, reads them once
            a prompt."""
            if not getattr(_TRACE, "prompts_share_experts", False):
                return fn
            fn = jax.custom_batching.custom_vmap(fn)

            @fn.def_vmap
            def _(n, batched, h, flat, sizes, *rest):
                if not all(batched[:own]) or any(batched[own:]):
                    raise NotImplementedError(
                        "moe_ffn under a vmap over the tokens alone, the "
                        "experts' weights shared: got %r" % (batched,))
                # (h, flat and what else is a prompt's: the prompts' rows
                # in one run; the groups' sizes add up)
                h, flat, *more = [t.reshape((-1,) + t.shape[2:])
                                  for t in (h, flat) + rest[:own - 3]]
                out = fn(h, flat, jnp.sum(sizes, axis=0), *more,
                         *rest[own - 3:])
                # the rows go back to their prompts; a fact of the call is
                # every prompt's
                return jax.tree.map(
                    lambda t: t.reshape((n, -1) + t.shape[1:])
                    if t.ndim else jnp.broadcast_to(t, (n,)), out), \
                    jax.tree.map(lambda t: True, out)
            return fn

        if held is None:
            # ... then a fixed-order sum over each token's k experts
            out = for_a_group(through_experts, 3)(
                h, flat, sizes, w_gate, w_up, w_down).reshape(T, k, -1)
            return jnp.sum(out * w[:, :, None], axis=1), facts
        out, full_size = for_a_group(through_held, 4)(
            h, flat, sizes, w, w_gate, w_up, w_down)
        return out, jnp.concatenate([facts, full_size[None]])


def _row_tile(rows):
    """The row tile the TPU's compiler gives a grouped matmul of `rows`
    rows: the largest power of two, 512 at most, that divides them
    (`ragged_dot_tiling` in the compiled text; pinned by
    tests/test_tpu_compile.py)."""
    return min(512, rows & -rows)


def held_cap(pairs, count, of):
    """The sorted rows `moe_ffn`'s grouped matmuls run over where a member
    holds `count` of a router's `of` experts, of `pairs` (token, expert)
    pairs: TWICE the member's expected share m = pairs * count / of (m + 6
    sqrt(m) where that is more: a uniform router keeps within sqrt(m) of m,
    but a prompt's positions crowd, and of the prefills of Granite's and
    K-EXAONE's cells a quarter to a half kept more than 1.25 m, one in
    sixty more than 1.5 m, none of 1,270 more than 2 m), in whole row
    tiles; or `pairs`, and then no `cond` is built, where fewer rows would
    not pay.  A call that keeps more than the cap takes the full-size
    branch (`moe_cap_overflows` counts them), so a margin too small costs
    time and never a result.

    What fewer rows buy is their ROW TILE (`_row_tile`): the kernel visits
    every (row tile, expert) pair that holds a live row, about m / tile +
    count of them, and each visit reads the expert's matrix and multiplies
    the whole tile; the tiles behind the live rows are nearly free.  Few
    rows a tile re-read the weights, many multiply dead rows (a tile of 512
    costs three weight reads, `resources.rows_a_weight_read`).  So the cap
    is an ODD number of tiles of 64, 128 or 256 rows, whichever makes the
    visits cheapest, and it stands only where they are cheaper than at the
    tile of all `pairs`: Granite's decode trip (960 pairs, tiles of 64
    either way) keeps every row and its parent's step (my chip runs, PR 57:
    PERF.md section 6)."""
    from paddle_tpu.analysis.resources import rows_a_weight_read
    m = -(-pairs * count // of)
    rows = math.ceil(max(2.0 * m, m + 6.0 * math.sqrt(m)))
    a_read = rows_a_weight_read()

    def visits(tile):
        return (m / tile + count) * (a_read + tile)
    tile = min((64, 128, 256), key=visits)
    cap = tile * (-(-rows // tile) | 1)
    return cap if cap < pairs and visits(tile) < visits(_row_tile(pairs)) \
        else pairs


def prefill_group(width, waiting):
    """How many of `waiting` prompts of one bucket the next prefill call
    takes, the bucket's group executable being `width` prompts wide
    (`GenerativePredictor.prefill_width`): a whole group; or what is left,
    padded with dead rows, where that is more than half of one (the padded
    call computes `width` prompts' rows whatever it holds, a call a prompt
    reads the weights each time); else one prompt, through the one-prompt
    executable."""
    n = min(int(waiting), int(width))
    return n if 2 * n > width else 1


_TRACE = threading.local()


@contextlib.contextmanager
def _prompts_share_experts():
    """Around the trace of a group's prefill, in this thread: `moe_ffn`
    gives its grouped matmuls the batching rule that sorts the prompts of
    the `vmap` together.  Every other trace (a step, a one-prompt prefill)
    stays the jaxpr it was."""
    _TRACE.prompts_share_experts = True
    try:
        yield
    finally:
        _TRACE.prompts_share_experts = False


def _pack_routing(tokens, facts):
    """A routed-expert phase's first result: its tokens, then each
    ROUTED layer's (experts touched, most tokens on one expert, pairs that
    stayed on this member; a layer with a dense FFN has `None` for its
    facts), as ONE int32
    vector, so the routing facts ride the fetch that brings the tokens
    (`DecodeSession._fetch` splits them off again)."""
    import jax.numpy as jnp
    return jnp.concatenate([
        tokens.reshape(-1).astype(jnp.int32),
        jnp.stack([f for f in facts if f is not None]).reshape(-1)])


def _mark_dead(phase, exc, *sessions):
    """A phase call that was given `sessions`' slot tables, donated,
    raised `exc`: a session whose table the call had consumed by then
    holds deleted arrays; mark it dead, so that its next use says so
    (`DecodeSessionDead`) and not "Array has been deleted" from some
    later round.  A call that raises before it donates (a poison check,
    a bad argument) leaves the session as it was."""
    for sess in sessions:
        if any(t.is_deleted() for t in sess._tables()):
            sess._dead = (phase, "%s: %s" % (type(exc).__name__, exc))


def _causal_attention(q, k, v, scale, sink=None):
    """Prefill attention oracle: [B, T, H, D] causal, same finite-mask
    convention as the kernels; v [B, T, H, Dv] -> [B, T, H, Dv].  `sink`
    [H] f32: a logit a head that joins the softmax's denominator and
    carries no value (`pallas_kernels.decode_attention`)."""
    import jax.numpy as jnp
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.arange(T)[None, :] < jnp.arange(T)[:, None] + 1
    s = jnp.where(mask[None, None], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        sink = sink.astype(jnp.float32)[None, :, None, None]
        m = jnp.maximum(m, sink)
    p = jnp.exp(s - m)
    o = jnp.einsum("bhqk,bkhe->bqhe", p, v.astype(jnp.float32))
    l = jnp.sum(p, axis=-1)
    if sink is not None:
        l = l + jnp.exp(sink - m)[..., 0]
    return o / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]


# Queries a prefill of a stack with window layers attends at a time
# (`_blocked_attention`): its scores are [H, block, keys] and never [H, B,
# B], which at 64 heads and a bucket of 4,096 is 4.3 GB in float32.
PREFILL_QUERY_BLOCK = 512

# Prompt positions ONE prefill call takes (`GenerativePredictor.
# prefill_width`): the same-bucket prompts of an admission run as one call
# over tokens [P, bucket], P = min(8, this // bucket) of them, so that a
# layer's weights and the vocabulary head are read once a group and not once
# a prompt.  A group's temporaries are P times a prompt's.  Settled on the
# chip (PERF.md section 6, PR 55): a call is compute-bound from about a
# thousand rows on, so 2,048 shared nothing more and filled its wider groups
# less often (Falcon-H1's cell +8.2% at 1,024, +4.3% at 2,048), and at 4,096
# a group's temporaries did not fit beside two cells' weights.
PREFILL_GROUP_TOKENS = 1024


def _blocked_attention(q, k, v, scale, window=0, sink=None):
    """Prefill attention by BLOCKS of queries, a stack with window layers'
    form of `_causal_attention`: q [1, B, H, Dh], k [1, B, Hc, Dh], v [1,
    B, Hc, Dv] (grouped-query: query head a reads K/V head a // (H / Hc),
    no repeat of K or V) -> [1, B, H, Dv], the oracle's finite-mask
    convention and its softmax (with its `sink` [H] f32, a logit a head in
    the denominator), a block of at most `PREFILL_QUERY_BLOCK` queries at
    a time (`lax.map`: one block's scores live at a time).

    `window` 0: a FULL layer, every block against all B keys under the
    causal mask, scores [H, block, B].  `window` W >= 1: a WINDOW layer,
    key j seen from query t iff t - W < j <= t; a block's scores are a
    BAND, its own keys and the W before them, [H, block, block + W]."""
    import jax
    import jax.numpy as jnp
    _, B, H, Dh = q.shape
    Hc = k.shape[2]
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(Hc, H // Hc, 1, 1)
    n = -(-B // int(PREFILL_QUERY_BLOCK))
    Q = -(-B // n)
    pad = n * Q - B
    qb = jnp.pad(q[0].astype(jnp.float32).reshape(B, Hc, H // Hc, Dh),
                 ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
                     n, Q, Hc, H // Hc, Dh)
    kf, vf = k[0].astype(jnp.float32), v[0].astype(jnp.float32)
    if window:
        # block i's keys are rows i * Q .. i * Q + Q + window - 1 of K with
        # `window` rows in front of it (positions below 0: masked)
        kf, vf = (jnp.pad(t, ((window, pad), (0, 0), (0, 0)))
                  for t in (kf, vf))

    def one(block):
        i, qi = block                           # qi [Q, Hc, G, Dh]
        qpos = (i * Q + jnp.arange(Q))[:, None]
        if window:
            kk, vv = (jax.lax.dynamic_slice_in_dim(t, i * Q, Q + window)
                      for t in (kf, vf))
            kpos = (i * Q - window + jnp.arange(Q + window))[None]
            mask = (kpos <= qpos) & (kpos > qpos - window) & (kpos >= 0)
        else:
            kk, vv = kf, vf
            mask = jnp.arange(B)[None] <= qpos
        s = jnp.einsum("qhgd,khd->hgqk", qi, kk) * scale
        s = jnp.where(mask[None, None], s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)
        if sink is not None:
            m = jnp.maximum(m, sink)
        p = jnp.exp(s - m)
        o = jnp.einsum("hgqk,khe->qhge", p, vv)
        l = jnp.sum(p, axis=-1)
        if sink is not None:
            l = l + jnp.exp(sink - m)[..., 0]
        return o / jnp.maximum(l, 1e-20).transpose(2, 0, 1)[..., None]

    out = jax.lax.map(one, (jnp.arange(n), qb))
    return out.reshape(n * Q, H, -1)[:B][None]


def _ring_rows(rows, true_len, window):
    """A prompt's rows of the window layers [L, 1, B, Hc, Dh] as the ring
    holds them after it, [L, 1, window, Hc, Dh]: ring row r is the row of
    the LAST position p < true_len with p % window == r (the prompt's last
    min(true_len, window) positions, each at its own p % window, so the
    steps that follow overwrite the oldest first), zeros where the prompt
    has no such position.  Positions at or past `true_len` land nothing."""
    import jax.numpy as jnp
    r = jnp.arange(window)
    have = r < true_len
    p = jnp.where(have, r + window * ((true_len - 1 - r) // window), 0)
    return jnp.where(have[None, None, :, None, None],
                     jnp.take(rows, p, axis=2), 0.0)


def _zero_pad_positions(ks, vs, true_len):
    """A prefill's per-layer K and V [1, B, H, Dh], stacked [L, 1, B, H,
    Dh] with the positions at or past `true_len` zeroed: the slot cache
    must hold exact zeros past the live length (free() zeroes, writes are
    length-gated — this keeps prefill on the same contract)."""
    import jax.numpy as jnp
    live = (jnp.arange(ks[0].shape[1])[None, :, None, None]
            < true_len)[None]            # [1, 1, B, 1, 1]
    return (jnp.where(live, jnp.stack(ks), 0.0),
            jnp.where(live, jnp.stack(vs), 0.0))


def _sparse_select(s, t, blk, n_blocks):
    """Stage 1 of a sparse_attention layer, THE selection rule, for the
    step and the prefill alike: s [..., G, J] the scaled scores of a K/V
    head's G query heads against its compressed keys 0 .. J - 1, for the
    query at position t [...] -> (ids [..., k] i32 the blocks chosen,
    highest score first; count [...] i32 how many of them are in sight;
    scores [..., n_blocks]; the chosen ones' scores [..., k]), k =
    min(sparse_topk, n_blocks).

    Compressed key j is SEEN once its last position stride * j + size - 1
    is cached (<= t).  p = softmax over the seen keys a query head, summed
    over the G heads; block b (positions block * b .. + block - 1) scores
    the max of p over the compressed keys that overlap it (an unseen one
    counts 0); the first `sparse_init_blocks` blocks and those that hold
    one of the last `sparse_window` positions score +inf, a block past t
    -1 (never chosen before one in sight); `lax.top_k` takes the highest,
    ties to the lower index.  All in float32: the caller computes `s` at
    "highest" precision, as a router's logits are."""
    import jax
    import jax.numpy as jnp
    size, stride, block = (blk[k] for k in (
        "sparse_kernel_size", "sparse_kernel_stride", "sparse_block"))
    r, m = block // stride, size // stride
    J = s.shape[-1]
    t = jnp.asarray(t)
    seen = stride * jnp.arange(J) + size - 1 <= t[..., None, None]
    s = jnp.where(seen, s, -1e30)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)) * seen
    p = jnp.sum(e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-20),
                axis=-2)                                    # [..., J]
    # block b reads compressed keys r * b - (m - 1) .. r * b + r - 1:
    # entries r * b .. r * b + r + m - 2 of p with m - 1 zeros in front
    pp = jnp.pad(p, [(0, 0)] * (p.ndim - 1)
                 + [(m - 1, r * n_blocks - J)])
    score = jnp.max(pp[..., :r * n_blocks].reshape(
        p.shape[:-1] + (n_blocks, r)), axis=-1)
    for a in range(r, r + m - 1):
        score = jnp.maximum(score, pp[..., a::r][..., :n_blocks])
    first = block * jnp.arange(n_blocks)
    tb = t[..., None]
    forced = (jnp.arange(n_blocks) < blk["sparse_init_blocks"]) | (
        first + block - 1 >= tb - (blk["sparse_window"] - 1))
    score = jnp.where(first <= tb, jnp.where(forced, jnp.inf, score), -1.0)
    vals, ids = jax.lax.top_k(score, min(blk["sparse_topk"], n_blocks))
    return (ids.astype(jnp.int32),
            jnp.sum(vals >= 0, axis=-1).astype(jnp.int32), score, vals)


def _compressed_keys(rows, blk):
    """rows [..., n * stride, W], n >= m = size / stride: the means of every
    `sparse_kernel_size` consecutive rows, a stride apart -> [..., n - m +
    1, W].  A stride's rows are summed first and m such sums added, the
    step's one key and a prefill chunk's run of them alike."""
    import jax.numpy as jnp
    size, stride = blk["sparse_kernel_size"], blk["sparse_kernel_stride"]
    m = size // stride
    n = rows.shape[-2] // stride
    sums = jnp.sum(rows.reshape(rows.shape[:-2] + (n, stride)
                                + rows.shape[-1:]), axis=-2)
    out = sums[..., 0:n - m + 1, :]
    for a in range(1, m):
        out = out + sums[..., a:a + n - m + 1, :]
    return out * np.float32(1.0 / size)


def ssd_chunked_scan(xs, Bm, Cm, dt, A, chunk, state=None):
    """The state-space recurrence of a Mamba-2 (SSD) mixer over a run of
    positions, in CHUNKS: xs [T, Hs, P] inputs, Bm / Cm [T, G, N] (head h
    reads group h // (Hs // G)), dt [T, Hs] >= 0, A [Hs] < 0, from the
    state `state` [Hs, P, N] (zeros if None) ->
    (y [T, Hs, P], the state after position T - 1), where

        S_t = exp(dt_t A) S_{t-1} + dt_t xs_t (outer) B_t
        y_t = S_t . C_t

    Within a chunk of `chunk` positions the quadratic form (position t
    reads s <= t through C_t . B_s decayed by exp(sum_{s<r<=t} dt_r A)),
    across chunks the carried state: one [Hs, P, N] state lives at a time
    and no [T, Hs, P, N] intermediate exists.  A position with dt = 0
    neither decays nor adds to the state: how a prefill keeps the pad
    positions of its bucket out of it.  T is padded up to whole chunks
    with such positions.  The contractions run at "highest" precision:
    they are a hundredth of a prefill's FLOPs, and the state they build is
    what every later token of the stream reads."""
    import jax
    import jax.numpy as jnp
    T, Hs, P = xs.shape
    G, N = Bm.shape[1:]
    Q, k = min(int(chunk), T), Hs // G
    pad = -T % Q
    if pad:
        xs, Bm, Cm, dt = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                          for t in (xs, Bm, Cm, dt))
    n = (T + pad) // Q
    xs, Bm, Cm, dt = (t.reshape((n, Q) + t.shape[1:])
                      for t in (xs, Bm, Cm, dt))
    causal = jnp.tril(jnp.ones((Q, Q), bool))[:, :, None]
    hi = jax.lax.Precision.HIGHEST

    def one(S, c):
        x, b, cc, d = c            # [Q, Hs, P], [Q, G, N] x 2, [Q, Hs]
        cum = jnp.cumsum(d * A, axis=0)                     # [Q, Hs], <= 0
        # position t from position s <= t of this chunk
        cb = jnp.einsum("tgn,sgn->tsg", cc, b, precision=hi)
        reach = jnp.exp(jnp.where(causal, cum[:, None] - cum[None],
                                  -jnp.inf)) * d[None]      # [t, s, Hs]
        y = jnp.einsum("tsh,shp->thp",
                       jnp.repeat(cb, k, axis=2) * reach, x, precision=hi)
        # ... and from the state the chunk began with
        y = y + jnp.einsum(
            "tgn,gkpn->tgkp", cc, S.reshape(G, k, P, N),
            precision=hi).reshape(Q, Hs, P) * jnp.exp(cum)[:, :, None]
        # the state the chunk ends with
        w = jnp.exp(cum[-1][None] - cum) * d                # [Q, Hs]
        S = jnp.exp(cum[-1])[:, None, None] * S + jnp.einsum(
            "sgkp,sgn->gkpn", (x * w[:, :, None]).reshape(Q, G, k, P), b,
            precision=hi).reshape(Hs, P, N)
        return S, y

    if state is None:
        state = jnp.zeros((Hs, P, N), jnp.float32)
    state, y = jax.lax.scan(one, state, (xs, Bm, Cm, dt))
    return y.reshape((n * Q, Hs, P))[:T], state


class _TPContext:
    """Trace-time handle threaded through the phase math when the
    program lowers TENSOR-PARALLEL over a mesh replica (`FLAGS.mesh_tp`,
    SERVING.md "Tensor-parallel compute").  Inside the shard_map'd body
    every weight/KV operand is this member's LOCAL shard; the context
    carries the axis grammar plus the handful of collectives the
    Megatron split needs — one psum per column->row pair, one logits
    all_gather, the exact masked-gather+psum embedding lookup.  Off a
    mesh the math runs under `_OFF_MESH`, the context of ONE member,
    so it is written once and never asks where it runs."""

    __slots__ = ("axis", "size")

    def __init__(self, size, axis):
        self.size = int(size)
        self.axis = axis

    def index(self):
        import jax
        return jax.lax.axis_index(self.axis)

    def psum(self, x):
        """Close one column->row-parallel pair: sum the members' partial
        products.  THE tolerance point of the TP contract — reduction
        order moves across members, so downstream activations agree
        with the single-device oracle at float tolerance, not
        bit-exactly (tests/test_mesh_tp.py pins top-1 agreement)."""
        import jax
        return jax.lax.psum(x, self.axis)

    def all_gather(self, x, axis):
        """Tiled all_gather (exact — pure data movement): reassembles
        the vocab-sharded logits for the replicated argmax."""
        import jax
        return jax.lax.all_gather(x, self.axis, axis=axis, tiled=True)

    def head_scales(self, scales, n_local):
        """This member's head block of a BAKED full-table kv-scale
        constant [..., H, 1] (sliced on axis -2 at a traced offset):
        the int8 quantize/dequant stays local to the resident heads."""
        import jax
        import jax.numpy as jnp
        full = jnp.asarray(scales, jnp.float32)
        off = self.index() * jnp.int32(n_local)
        return jax.lax.dynamic_slice_in_dim(full, off, int(n_local),
                                            axis=full.ndim - 2)

    def embed_lookup(self, embed_local, ids):
        """EXACT embedding gather over the vocab-row-sharded table
        (parallel/sharded_embedding.py's convention): each member
        gathers the ids it owns, contributes true zeros for the rest,
        and the psum adds exactly one nonzero term per row — 0 + v is
        exact in float, so no tolerance demotion here."""
        import jax.numpy as jnp
        vl = int(embed_local.shape[0])
        off = self.index() * jnp.int32(vl)
        local = ids - off
        ok = (local >= 0) & (local < vl)
        rows = embed_local[jnp.clip(local, 0, vl - 1)]
        return self.psum(jnp.where(ok[..., None], rows, 0.0))


class _OneMember(_TPContext):
    """The context of a program that is not partitioned: every weight
    and table is whole, and each collective is the identity and traces
    to nothing, so a phase's module is what its math alone would be."""

    __slots__ = ()

    def psum(self, x):
        return x

    def all_gather(self, x, axis):
        return x

    def head_scales(self, scales, n_local):
        return scales

    def embed_lookup(self, embed, ids):
        return embed[ids]


_OFF_MESH = _OneMember(1, None)


class GenerativePredictor:
    """A decode artifact opened for serving: weights + meta + the two
    compiled phases (per-bucket prefill, one fixed-shape decode step
    per slot-table size).  `device` pins state and compute to one
    jax.Device — the serving registry's replica placement; the default
    (`device=None`) keeps the weights RESIDENT on jax's default device
    but uncommitted (placed once at open, pinned nowhere); `clone_to`
    shares the artifact read and the in-process export map so N
    same-device-kind replicas deserialize ONE executable each
    (COMPILE_CACHE.md).

    `kv_cache_dtype` picks the slot-table cache numerics per OPEN
    (explicit arg > the artifact's decode_meta pin >
    FLAGS.serving_kv_cache_dtype > float32); 'int8' calibrates
    per-(layer, head) scales once and every session this predictor
    vends quantizes its cache writes in-graph."""

    def __init__(self, dirname, device=None, kv_cache_dtype=None,
                 _clone_of=None):
        from paddle_tpu.native import wire
        if _clone_of is not None:
            src = _clone_of
            self.meta = src.meta
            self._block_meta = src._block_meta
            self._state_host = src._state_host
            self._shared_exports = src._shared_exports
            self._shared_lock = src._shared_lock
            self._model_fp = src._model_fp
            self._kv_dtype = src._kv_dtype
            self._kv_scales = src._kv_scales
        else:
            with open(os.path.join(dirname, DECODE_META), "rb") as f:
                self.meta = wire.decode(f.read())
            # the decoder block this artifact describes (BLOCK_DEFAULTS)
            self._block_meta = block_of(self.meta)
            with open(os.path.join(dirname, _DECODE_STATE), "rb") as f:
                raw_state = f.read()
            self._state_host = wire.decode(raw_state)
            # (device_kind, phase-key) -> jitted call, shared BY
            # REFERENCE across clone_to replicas
            self._shared_exports = {}
            self._shared_lock = threading.Lock()
            # the fingerprint must cover the WEIGHTS, not just the
            # meta: the int8 phases bake the weight-derived kv scales
            # as trace constants, so two same-shape artifacts with
            # different weights must never resolve each other's
            # persisted executables (a meta-only fingerprint let a
            # stale ("step", n) int8 blob quantize with another
            # model's scales)
            self._model_fp = hashlib.sha256(json.dumps(
                {k: self.meta[k] for k in sorted(self.meta)},
                sort_keys=True, default=str).encode()
                + hashlib.sha256(raw_state).digest()).hexdigest()
            if kv_cache_dtype is not None:
                self._kv_dtype = normalize_kv_dtype(kv_cache_dtype)
            elif self.meta.get("kv_cache_dtype"):
                self._kv_dtype = normalize_kv_dtype(
                    self.meta["kv_cache_dtype"])
            else:
                from paddle_tpu.flags import FLAGS
                self._kv_dtype = normalize_kv_dtype(
                    FLAGS.serving_kv_cache_dtype)
            if self._kv_dtype == "int8":
                # the scales are per (layer, head) of an all-attention
                # multi-head table, calibrated through a prefill of it
                self._require("an int8 KV cache", "int8")
                if self._kv_heads() != self._dims()[1]:
                    raise NotImplementedError(
                        "an int8 KV cache is written for a multi-head "
                        "table, and this artifact's meta has n_kv_heads=%d "
                        "under n_heads=%d"
                        % (self._kv_heads(), self._dims()[1]))
            # per-(layer, head) symmetric fp32 scales [2, L, H, 1]
            # (K row 0, V row 1), a deterministic function of the
            # weights — baked into the traced phases as constants
            # (kv_cache_dtype is a compile-cache fingerprint field)
            self._kv_scales = self._calibrate_kv_scales() \
                if self._kv_dtype == "int8" else None
        self._device = device
        # tensor-parallel compute (SERVING.md "Tensor-parallel
        # compute"): on a MeshGroup with FLAGS.mesh_tp and evenly
        # dividing dims, the phases lower as ONE shard_map'd partitioned
        # executable and the state is placed by the TP axis grammar.
        # Read ONCE here — a registry fault-in / hot-swap rebuild
        # re-reads the flag; live sessions keep their build's mode.
        self._tp_size = 0
        self._tp_prefill_seq = 0
        group = None
        if device is not None:
            from paddle_tpu.parallel.mesh import (as_mesh_group,
                                                  tp_supported)
            group = as_mesh_group(device)
        if group is not None:
            from paddle_tpu.flags import FLAGS
            # a mesh shards the K/V tables by heads at rest
            self._require("a mesh placement", "mesh")
            if FLAGS.mesh_tp:
                # no fall-back to the gather path for a block the TP
                # grammar cannot split: it was asked for by name
                self._require_default_block(
                    "tensor-parallel compute (FLAGS.mesh_tp)")
                _, H, _, D = self._dims()
                if tp_supported(group.mesh_size, H, D,
                                self.vocab_size, 4 * D):
                    self._tp_size = group.mesh_size
                    self._tp_prefill_seq = max(
                        1, int(FLAGS.mesh_tp_prefill_seq))
                else:
                    warnings.warn(
                        "FLAGS.mesh_tp requested but model dims "
                        "(heads=%d d_model=%d vocab=%d) do not divide "
                        "the %d-member mesh — falling back to the "
                        "shard-at-rest gather path"
                        % (self._dims()[1], self._dims()[3],
                           self.vocab_size, group.mesh_size),
                        RuntimeWarning, stacklevel=2)
        # the weights go to a device ONCE, here, under every placement
        # (`_state_host` stays numpy for fingerprints, specs and clones)
        if self._tp_size:
            from paddle_tpu.inference.predictor import _put_state_tp
            self._state = _put_state_tp(self._state_host, group)
        else:
            from paddle_tpu.inference.predictor import _put_state
            # MeshGroup: every param sharded at rest over the mesh
            # (SERVING.md "Mesh replicas"); plain device: the single-chip
            # pin; None: jax's default device, uncommitted
            self._state = _put_state(self._state_host, device)
        self._state_host_nbytes = None
        self._fns = {}          # per-instance resolved callables
        self._lock = threading.Lock()
        # prompt lengths past every configured prefill bucket that have
        # already warned (once per size, under _lock — the Predictor
        # batch-bucket overflow parity)
        self._overflow_warned = set()

    # -- meta surface ---------------------------------------------------

    @property
    def device(self):
        return self._device

    @property
    def vocab_size(self):
        return int(self.meta["vocab_size"])

    @property
    def max_seq_len(self):
        return int(self.meta["max_seq_len"])

    @property
    def eos_id(self):
        return int(self.meta["eos_id"])

    @property
    def is_decode(self):
        return True

    @property
    def block(self):
        """The decoder block's description (a copy): every key of
        BLOCK_DEFAULTS, as the artifact's meta gives or defaults it."""
        return dict(self._block_meta)

    @functools.cached_property
    def layer_kinds(self):
        """[(operator, FFN)] of the stack, layer by layer (module-level
        `layer_kinds`; worked out once: the lane asks on every
        dispatch)."""
        return layer_kinds(self.meta, self._block_meta)

    @functools.cached_property
    def _kinds(self):
        """((kind, the layers that hold it), ...): the records of the slot
        state this stack holds, in order (`slot_state.kinds_held`)."""
        return slot_state.kinds_held(self.meta, self._block_meta)

    def _table_layer(self, i, kind="kv"):
        """Where layer i's slot state of `kind` (a `slot_state.KINDS`
        name) lies in the table of that kind: its rank among the layers
        that keep such state (i None: how many layers do)."""
        return sum(kind in slot_state.HOLDS[op]
                   for op, _ in self.layer_kinds[:i])

    @functools.cached_property
    def latent(self):
        """Whether the stack's layers are MLA: its slot state is ONE
        latent table (`slot_state.KINDS`) where another stack holds a K
        and a V table."""
        return bool(self._table_layer(None, "latent"))

    @functools.cached_property
    def conv_layers(self):
        """Layers with a row in the conv-state table: those whose
        operator is a gated short convolution, or holds a state-space
        mixer (whose conv runs in front of its scan)."""
        return self._table_layer(None, "conv")

    @functools.cached_property
    def ssm_layers(self):
        """Layers with a row in the scanned-state table: those whose
        operator holds a state-space mixer (attention+ssm)."""
        return self._table_layer(None, "ssm")

    @functools.cached_property
    def window_layers(self):
        """Layers whose K/V rows are a ring: those whose operator is
        window_attention."""
        return self._table_layer(None, "ring")

    @property
    def routed_layers(self):
        """Layers with a routed-expert FFN (under ffn=moe_swiglu, those
        past the leading dense ones).  The step and the prefill of such
        an artifact return their routing facts behind their tokens."""
        return [ffn for _, ffn in self.layer_kinds].count("moe_swiglu")

    @property
    def _routing_facts(self):
        """How many facts a routed layer hands out (`moe_ffn`): a member
        that holds some of the experts has a fourth, whether the call ran
        its grouped matmuls full size."""
        return 4 if self._block_meta["experts_held"] else 3

    @property
    def _step_picks(self):
        """Whether `step_logits_fn` hands out the layers' picks: a stack
        with conv layers and routed FFNs (the routed layers' chosen
        experts), or one with sparse_attention layers (the blocks each
        selected, [sparse layers, N, K/V heads, k], -1 past a slot's
        count): there too a near-tie of the selection reaches every later
        position, through the K/V rows and states the layers behind it
        write."""
        return bool(self.conv_layers and self.routed_layers) or bool(
            self._table_layer(None, "index"))

    @property
    def _prefill_picks(self):
        """Whether a prefill hands out the experts its routed layers chose
        at EVERY position of the bucket ([routed layers, B, k] i32, behind
        its first token: `DecodeSession.last_prefill_picks`): a stack with
        routed FFNs behind state-space layers.  A scanned state carries a
        prompt position's routing to every later position with no horizon
        (a conv layer's taps end after K - 1), so a comparison with a
        reference can tell a near-tie at a prompt's position from a fault
        only by the prefill's own picks there."""
        return bool(self.ssm_layers and self.routed_layers)

    def _require(self, what, capability):
        """Raise for `what` (a placement, a phase) that needs a
        `capability` (of `slot_state.CAPABILITIES`) a kind of slot state
        this stack holds has no rule for, with the kind's own sentence of
        why not and the meta key that asks for the kind."""
        for kind, _ in self._kinds:
            if capability not in kind.rules:
                raise NotImplementedError(
                    "%s has no rule for %s, and this artifact's meta has "
                    "layer_types=%r (%s)"
                    % (what, kind.noun,
                       list(self._block_meta["layer_types"]),
                       kind.why_not % self._block_meta))
        blk = self._block_meta
        if capability in ("mesh", "int8") and blk["v_head_dim"] \
                and "mla" not in blk["layer_types"]:
            raise NotImplementedError(
                "%s has no rule for K and V rows of two widths, and this "
                "artifact's meta has v_head_dim=%d beside its key heads' "
                "size (a mesh shards, and an int8 cache scales, rows whose "
                "heads are one size)" % (what, blk["v_head_dim"]))

    def _require_default_block(self, what):
        """Raise for a placement that can hold only the GPT-2-shaped
        block, naming the first meta key that asks for another.  Every
        PHASE runs every block (`_block`); the TP lane is the one
        caller, because its grammar (`parallel/mesh.py`) has no rule
        for sharding qk-norm gains or experts."""
        for key, default in BLOCK_DEFAULTS:
            if self._block_meta[key] != default:
                raise NotImplementedError(
                    "%s implements only the default decoder block, and "
                    "this artifact's meta has %s=%r (every other "
                    "placement runs it)"
                    % (what, key, self._block_meta[key]))

    @property
    def kv_cache_dtype(self):
        """'float32' or 'int8' — the slot-table cache numerics every
        session of this predictor allocates and the serving layer
        reports (SERVING.md kv_cache_dtype rows)."""
        return self._kv_dtype

    @property
    def _kv_quant(self):
        return self._kv_dtype == "int8"

    @property
    def tp_active(self):
        """True when this predictor's phases compute TENSOR-PARALLEL
        over its mesh group (FLAGS.mesh_tp at build + evenly dividing
        dims) — the serving stats / serving_top TP marker reads this."""
        return bool(self._tp_size)

    @property
    def tp_size(self):
        """Members the partitioned program shards over (0 when compute
        is not tensor-parallel)."""
        return int(self._tp_size)

    def kv_scales(self):
        """The calibrated per-(layer, head) fp32 dequant scales
        [2, L, H] (K row 0, V row 1); None for a float32 cache."""
        if self._kv_scales is None:
            return None
        return np.asarray(self._kv_scales)[..., 0]

    def prefill_buckets(self):
        return tuple(int(b) for b in self.meta["prefill_buckets"])

    def batch_buckets(self):
        """Serving introspection parity with Predictor/AotPredictor:
        for a decode model the 'buckets' are the prompt-length prefill
        buckets."""
        return self.prefill_buckets()

    def prompt_bucket(self, prompt_len):
        """Smallest prefill bucket >= prompt_len (deterministic by
        length — the parity contract rides this).  A prompt past every
        configured bucket but still inside the cache falls through to
        an exact-length one-off prefill compile, warning ONCE per
        overflow size — the same contract as the Predictor batch-bucket
        overflow path (SERVING.md).  A stack that prefills in chunks
        (`_prefill_chunks`) runs WHOLE chunks: its overflow compile is
        the next whole number of them, the pads masked by the true
        length as a bucket's are; the cache is whole chunks too
        (`block_of`), so it holds the rows."""
        buckets = self.prefill_buckets()
        for b in buckets:
            if prompt_len <= b:
                return b
        if prompt_len > self.max_seq_len:
            raise ValueError(
                "prompt of %d tokens exceeds max_seq_len %d"
                % (prompt_len, self.max_seq_len))
        size = int(prompt_len)
        if self._chunked:
            blk = self._block_meta
            unit = blk["prefill_chunk"] or _chunk_unit(blk)
            size = -(-size // unit) * unit
        if size not in self._overflow_warned:
            with self._lock:
                # concurrent lanes racing the same overflow size must
                # produce exactly one warning (the PR 5 warn-once race)
                if size in self._overflow_warned:
                    return size
                self._overflow_warned.add(size)
            from paddle_tpu.inference.predictor import _device_label
            warnings.warn(
                "prompt of %d tokens exceeds every configured prefill "
                "bucket %s on replica device [%s] — falling through to "
                "an unbucketed exact-length prefill compile%s; extend "
                "prefill_buckets to avoid a compile per distinct "
                "overflow length"
                % (prompt_len, tuple(buckets), _device_label(self._device),
                   " (%d positions: whole prefill chunks)" % size
                   if size != prompt_len else ""), RuntimeWarning,
                stacklevel=3)
        return size

    def clone_to(self, device):
        return GenerativePredictor(None, device=device, _clone_of=self)

    # -- static byte accounting (ANALYSIS.md resource analysis) ---------

    def _slot_state(self, n_slots):
        """({leaf: (shape, dtype)} in the phases' argument order, {kind:
        bytes}, {total: bytes}) of an `n_slots` session of this predictor
        (`slot_state.slot_leaves`, `state_bytes`), asked once a slot count."""
        memo = self.__dict__.setdefault("_slot_states", {})
        n = int(n_slots)
        if n not in memo:
            of = (self.meta, self._block_meta, n, self._device,
                  self._kv_dtype)
            memo[n] = (slot_state.slot_leaves(*of),
                       *slot_state.state_bytes(*of))
        return memo[n]

    def _leaf_shape(self, leaf, n_slots):
        return self._slot_state(n_slots)[0].get(leaf, (None,))[0]

    def table_shape(self, n_slots):
        """The K (or V) slot table of an `n_slots` session of this
        predictor; for an MLA stack its one latent table."""
        return self._leaf_shape("kc", n_slots)

    def conv_state_shape(self, n_slots):
        """The conv-state table of an `n_slots` session; None for a stack
        with no layer that convolves."""
        return self._leaf_shape("cs", n_slots)

    def ssm_state_shape(self, n_slots):
        """The scanned-state table of an `n_slots` session; None for a
        stack with no attention+ssm layer."""
        return self._leaf_shape("ss", n_slots)

    def window_table_shape(self, n_slots):
        """The K (or V) ring of an `n_slots` session; None for a stack
        with no window_attention layer."""
        return self._leaf_shape("kw", n_slots)

    def kv_cache_bytes(self, n_slots):
        """Closed-form footprint of the slot state that bounds the decode
        slots (FLAGS.serving_decode_slots) of an `n_slots` session: the
        number the admission fit check adds per replica
        (`slot_state.state_bytes`, which analysis/resources.py reads
        too).  The conv layers' state is `conv_state_bytes`, apart."""
        return self._slot_state(n_slots)[2]["kv_cache_bytes"]

    def window_kv_bytes(self, n_slots):
        """Closed-form footprint of the window_attention layers' K and V
        rings for an `n_slots` session (0 for a stack with none)."""
        return self._slot_state(n_slots)[1].get("ring", 0)

    def ssm_state_bytes(self, n_slots):
        """Closed-form footprint of the scanned state for an `n_slots`
        session (0 for a stack with no attention+ssm layer)."""
        return self._slot_state(n_slots)[1].get("ssm", 0)

    def conv_state_bytes(self, n_slots):
        """Closed-form footprint of the conv layers' slot state for an
        `n_slots` session (0 for a stack with no conv layer)."""
        return self._slot_state(n_slots)[2]["conv_state_bytes"]

    def param_bytes(self):
        """Static weight footprint (host-state nbytes sum)."""
        return sum(int(np.asarray(v).nbytes)
                   for v in self._state_host.values())

    def state_host_bytes(self):
        """Bytes of the weights that are NOT on a device (leaves of
        `_state` that are no jax.Array): 0 under every placement, since
        the weights are placed once when the artifact is opened.  It
        feeds `h2d_bytes` of every `decode/launch` span, so a placement
        that left host leaves behind would show there.  The state is
        static, so this is counted once."""
        if self._state_host_nbytes is None:
            self._state_host_nbytes = _host_nbytes(self._state.values())
        return self._state_host_nbytes

    # -- model math -----------------------------------------------------

    def _dims(self):
        """(layers, query heads, a head's size, d_model)."""
        m = self.meta
        return (int(m["n_layers"]), int(m["n_heads"]),
                _head_dim(m, self._block_meta), int(m["d_model"]))

    def _kv_heads(self, op="attention"):
        """K/V heads of a layer whose operator is `op`: the window layers
        may have their own count (`slot_state.attention_geometry`)."""
        return slot_state.attention_geometry(
            self.meta, self._block_meta, window=op == "window_attention")[0]

    @functools.cached_property
    def _attention_scale(self):
        """What an attending layer's q . k is multiplied by: the meta's
        `attention_multiplier`, or 1/sqrt(head_dim)."""
        return self._block_meta["attention_multiplier"] \
            or 1.0 / np.sqrt(self._dims()[2])

    @functools.cached_property
    def _v_head_dim(self):
        """A value head's lanes (a key head's: `_dims`)."""
        return slot_state.attention_geometry(self.meta, self._block_meta)[2]

    # -- int8 KV cache: quantization epilogues --------------------------

    @staticmethod
    def _quantize_kv(x, scale):
        """Symmetric int8 quantization of fresh K/V rows against the
        calibrated per-head scale: clip(round(x / scale)) as EXACT
        integer values in fp32 (the caller casts to int8: `_write` for
        the step and the verify alike, which is what keeps their rows
        bit-identical and spec-decode acceptance at 1.0 under the
        quantized cache)."""
        import jax.numpy as jnp
        return jnp.clip(jnp.round(x / scale), -127.0, 127.0)

    def _calibrate_kv_scales(self):
        """Per-(layer, head) symmetric scales for the int8 KV cache:
        amax of |K| / |V| over a deterministic vocab-cycling probe
        prompt run through the fp32 prefill math eagerly on the host
        state, x1.25 headroom for decode-time rows the probe never
        saw, /127.  Deterministic by construction, so every clone /
        replica / reopen of the artifact quantizes identically (the
        bit-stability contract rides this).  Returns [2, L, H, 1]."""
        T = int(min(self.max_seq_len - 1, 64))
        vocab = max(self.vocab_size, 1)
        tokens = ((np.arange(T, dtype=np.int64) * 7 + 1)
                  % vocab).astype(np.int32).reshape(1, T)
        state = {n: np.asarray(v) for n, v in self._state_host.items()}
        _, kc, vc = self._prefill_core(state, tokens, np.int32(T))

        def sc(x):
            amax = np.abs(np.asarray(x)).max(axis=(1, 2, 4))   # [L, H]
            return (np.maximum(amax, 1e-6) * 1.25
                    / 127.0).astype(np.float32)

        return np.stack([sc(kc), sc(vc)])[..., None]

    def _prefill_math(self, state, tokens, true_len, tp=_OFF_MESH):
        """The traced prefill phase: `_prefill_core` with the rows it
        keeps a head apart (`slot_state.KINDS`' `per_head`: K and V, the
        window layers' rings) as a slot table holds a position, [layers,
        1, B, Hkv * Dh], one flat row, after the int8
        cache-write quantization epilogue (zeros quantize to exact
        int8 zeros, so the zero-slot contract is dtype-blind).  Under
        TP the K/V are this member's head shard, so the scale constant
        slices to the resident head block — same per-head scale, same
        quantized byte as the single-device write."""
        import jax.numpy as jnp
        first, *tables = self._prefill_core(state, tokens, true_len, tp=tp)
        held = dict(zip(self._table_names, tables))
        if self._kv_quant:
            # [2, L, Hl, 1]
            sc = tp.head_scales(self._kv_scales, held["kc"].shape[3])
            for j, leaf in enumerate(("kc", "vc")):
                held[leaf] = self._quantize_kv(
                    held[leaf], sc[j][:, None, None]).astype(jnp.int8)
        # (the last kind first: the order the stored programs fold in)
        for kind, _ in reversed(self._kinds):
            if kind.per_head:
                for leaf in kind.leaves:
                    t = held[leaf]
                    held[leaf] = t.reshape(t.shape[:3] + (-1,))
        return (first, *held.values())

    def _prefill_group_math(self, state, tokens, true_len):
        """The traced prefill phase of a GROUP of prompts of one bucket:
        tokens [P, B], true_len [P] -> `_prefill_math`'s results with a
        leading P, row p what `_prefill_math` makes of prompt p alone.  It
        is that function under a `vmap` over the prompts with the weights
        shared, so everything a prompt has of its own stays its own (its
        length, its masks, its pad positions, its rings, its conv tail, its
        scanned state, its routing facts) and every matmul against a weight
        takes the P * B rows as ONE operand: a layer's weights are read
        once a group, the head runs over the P last rows in one [P, D] x
        [D, V], and a routed layer sorts the group's tokens by expert
        together (`moe_ffn`)."""
        import jax
        with _prompts_share_experts():
            return jax.vmap(
                lambda t, n: self._prefill_math(state, t[None], n))(
                    tokens, true_len)

    def prefill_width(self, bucket):
        """Prompts ONE prefill call of `bucket` takes: min(8,
        `PREFILL_GROUP_TOKENS` // bucket), at least 1.  A stack that
        prefills in chunks runs a prompt a call (a chunk is its unit of
        work already: `_prefill_chunks`), and so do a lane on a mesh (its
        prefill is the partitioned or the sequence-parallel program) and a
        length past every configured bucket (its one-off executable
        compiles under traffic as it is)."""
        if self._chunked or self._mesh_group() is not None \
                or int(bucket) not in self.prefill_buckets():
            return 1
        return max(1, min(8, int(PREFILL_GROUP_TOKENS) // int(bucket)))

    def _tp_seq_parallel(self, bucket, tp):
        """Does this prompt bucket prefill SEQUENCE-parallel under TP?
        Long prompts at a bucket the mesh divides shard the sequence
        axis (ulysses reshard into head-parallel attention, per-layer
        weight all_gathers amortized over the bucket — bit-exact);
        short ones run head/column-parallel like decode (top-1
        contract, no per-layer gathers)."""
        return bool(tp.size > 1 and bucket % tp.size == 0
                    and bucket >= self._tp_prefill_seq)

    def _embed(self, state, tokens, positions, tp):
        """x [..., D] for `tokens` [...]: the embedding rows (the exact
        vocab-sharded lookup under TP), plus the position table's rows
        at `positions` where the block learns its positions (a rotary
        block turns q and k in `_block` instead).  `positions` indexes
        the table: an index array, or the slice a prefill's run of
        consecutive positions is."""
        x = tp.embed_lookup(state["embed"], tokens)
        if x.dtype != np.float32:       # a table bfloat16 at rest
            x = x.astype(np.float32)
        if self._block_meta["embedding_multiplier"] != 1.0:
            x = x * self._block_meta["embedding_multiplier"]
        if self._block_meta["position"] == "learned":
            x = x + state["pos"][positions]
        return x

    def _head(self, state, x, tp):
        """logits [..., vocab] of x [..., D]: the final norm and the
        `lm_head` (under head=tied the embedding table itself, read
        transposed: one table in memory); under TP the vocab-sharded
        logits reassemble (exact data movement) before the replicated
        argmax."""
        x = self._norm(x, state, "lnf")
        if self._block_meta["head"] == "tied":
            logits = _mm(x, state["embed"].T)
        else:
            logits = _mm(x, state["lm_head"])
            logits = tp.all_gather(logits, axis=logits.ndim - 1)
        if self._block_meta["lm_head_multiplier"] != 1.0:
            logits = logits * self._block_meta["lm_head_multiplier"]
        return logits

    def _first_token(self, state, x, true_len, tp):
        """A prefill's greedy token from its last layer's x [1, B, D]: the
        head over the ONE position `true_len - 1` (the row is taken before
        the head: at a 261,120-row vocabulary the head over a bucket of
        512 was 0.53 GB of logits and 1.4 TFLOP, all but one row of them
        dropped)."""
        import jax
        import jax.numpy as jnp
        row = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
        return jnp.argmax(self._head(state, row, tp)[0, 0],
                          axis=-1).astype(jnp.int32)

    def _prefill_core(self, state, tokens, true_len, tp=_OFF_MESH):
        """tokens [1, B] int32, true_len scalar int32 -> (first_token
        [] int32 (a stack with sparse_attention layers: an int32 vector,
        the token, then the blocks its last position selected [sparse
        layers, K/V heads, k], -1 past their count), then what the prompt
        leaves of each leaf of
        `_table_names`, one slot's block of its table: k/v [attention
        layers, 1, B, Hkv, Dh] fp32 with pad positions zeroed; an MLA
        stack's latent rows [mla layers, 1, B, R]; each convolving
        layer's last K-1 inputs before the TRUE prompt end, not the
        bucket's, zeros where the prompt is shorter; each attention+ssm
        layer's scanned state after position true_len - 1; the window
        layers' K and V rings as the prompt leaves them: `_ring_rows`).
        Under TP (inside shard_map) weights are local shards:
        the returned K/V carry this member's HEAD block [L, 1, B, H/m,
        Dh] (the cache's at-rest layout), attention is head-parallel
        (exact per head), and each column->row pair closes with one
        psum; long buckets divert to the bit-exact sequence-parallel
        body instead."""
        if self._tp_seq_parallel(tokens.shape[1], tp):
            return self._prefill_core_seqpar(state, tokens, true_len,
                                             tp)
        if self._chunked:
            import jax.numpy as jnp
            row, picks, tables = self._prefill_chunks(state, tokens,
                                                      true_len, tp)
            first = jnp.argmax(self._head(state, row, tp),
                               axis=-1).astype(jnp.int32)
            # the blocks the prompt's LAST position selected ride the fetch
            # that brings the token, as a routed stack's facts do
            # (`DecodeSession.last_prefill_picks`): a comparison holds the
            # prefill's selection to a reference's by them
            return (jnp.concatenate([first.reshape(1),
                                     picks.reshape(-1)]),) + tables
        picks = [] if self._prefill_picks else None
        x, facts, tables = self._prefill_layers(state, tokens, true_len,
                                                tp, picks=picks)
        first = self._first_token(state, x, true_len, tp)
        if picks:
            # [routed layers, B, k] behind the token, in front of the facts
            import jax.numpy as jnp
            first = jnp.concatenate([first.reshape(1),
                                     jnp.stack(picks).reshape(-1)])
        if self.routed_layers:
            first = _pack_routing(first, facts)
        return (first,) + tables

    def _prefill_layers(self, state, tokens, true_len, tp=_OFF_MESH,
                        picks=None):
        """`_prefill_core` up to the head: (the last layer's x [1, B, D],
        the layers' routing facts, the slot state the prompt leaves, as
        `_prefill_core` returns it).  A list given as `picks` receives each
        routed layer's chosen experts [B, k]."""
        import jax
        import jax.numpy as jnp
        L = self._dims()[0]
        B = tokens.shape[1]
        scale = self._attention_scale
        x = self._embed(state, tokens, slice(B), tp)
        positions = jnp.arange(B)[None]                     # [1, B]
        live = positions[0] < true_len
        facts, kept = [], {leaf: [] for leaf in self._table_names}
        group = self._dims()[1] // self._kv_heads()
        window = self._block_meta["sliding_window"]

        def latent(q_nope, q_rope, row, wkv_b):
            kept["kc"].append(row)
            return self._mla_expanded(q_nope, q_rope, row, wkv_b)

        def attend(q, k, v):
            kept["kc"].append(k)
            kept["vc"].append(v)
            if window:
                # beside window layers a full layer's scores are taken by
                # blocks of queries too: this stack's buckets are those a
                # whole [H, B, B] does not fit
                with jax.named_scope("full_attention"):
                    return _blocked_attention(q, k, v, scale)
            if group > 1:
                # query head a reads K/V head a // group
                k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
            return _causal_attention(q, k, v, scale)

        def attend_window(q, k, v, sink=None):
            kept["kw"].append(k)
            kept["vw"].append(v)
            with jax.named_scope("window_attention"):
                # (a stack with no sink calls it as it always was called:
                # the plants of benchmark/tests/test_kexaone_cell.py and
                # tests/test_decode_sliding.py's spy stand in for it with
                # the signature it had)
                return _blocked_attention(
                    q, k, v, scale, window=window,
                    **({} if sink is None else {"sink": sink}))

        def convolve(z, taps):
            # z [1, B, C], taps [C, K]: position t reads z[t - (K-1) .. t]
            K = taps.shape[1]
            zp = jnp.pad(z, ((0, 0), (K - 1, 0), (0, 0)))
            kept["cs"].append(jax.lax.dynamic_slice_in_dim(
                zp, true_len, K - 1, axis=1))
            return sum(taps[:, j] * zp[:, j:j + B] for j in range(K))

        def scan(xs, Bm, Cm, dt, A):
            # [1, B, ..]: a pad position's dt is 0, so the state after the
            # bucket is the state after position true_len - 1
            y, after = ssd_chunked_scan(
                xs[0], Bm[0], Cm[0], jnp.where(live[:, None], dt[0], 0.0),
                A, self._block_meta["ssm_chunk"])
            kept["ss"].append(after[None])
            return y[None]

        for i in range(L):
            x, f = self._block(
                state, i, x, positions, attend_window
                if self.layer_kinds[i][0] == "window_attention" else attend,
                live, tp=tp, convolve=convolve, picks=picks, latent=latent,
                ssm=("ssm_scan", scan))
            facts.append(f)
        # what the prompt leaves of each kind, from its layers' rows
        leaves = {
            "kv": lambda ks, vs: _zero_pad_positions(ks, vs, true_len),
            # the latent table's [mla layers, 1, B, R], pads zeroed
            "latent": lambda rows: (jnp.where(
                live[None, None, :, None], jnp.stack(rows), 0.0),),
            "conv": lambda t: (jnp.stack(t),),
            "ssm": lambda t: (jnp.stack(t),),
            "ring": lambda *kv: tuple(
                _ring_rows(jnp.stack(t), true_len, window) for t in kv)}
        return x, facts, sum(
            (leaves[kind.name](*(kept[leaf] for leaf in kind.leaves))
             for kind, _ in self._kinds), ())

    @functools.cached_property
    def _chunked(self):
        """Whether the stack prefills through `_prefill_chunks`: one of
        sparse_attention / linear_attention layers (`block_of`)."""
        return bool(set(self._block_meta["layer_types"]) & set(_CHUNKED_OPS))

    def prefill_chunks(self, prompt_len):
        """Chunks the prefill of a prompt of `prompt_len` tokens runs its
        bucket in (0: this stack's prefill is one whole pass)."""
        chunk = self._block_meta["prefill_chunk"]
        return self.prompt_bucket(prompt_len) // chunk if chunk else 0

    def _prefill_chunks(self, state, tokens, true_len, tp=_OFF_MESH):
        """`_prefill_layers` for a stack of sparse_attention and
        linear_attention layers, IN CHUNKS: a `lax.scan` over the bucket's
        chunks of `prefill_chunk` positions (the bucket whole where that
        is 0), each chunk through ALL layers, so that no temporary is
        larger than a chunk's whatever the bucket.  What a chunk hands the
        next is the slot state the prompt has left so far: the sparse
        layers' K and V rows and compressed keys (buffers a bucket long,
        written a chunk at a time), the linear layers' states
        (`ssd_chunked_scan` from the carried state) and the last layer's x
        at position true_len - 1 once a chunk holds it.  A chunk wholly
        past the prompt is skipped.  The result does not depend on the
        chunk: a position's compressed keys, selection and attention read
        the rows written so far, which are the rows a whole pass reads.
        -> (x [D] at the prompt's last position, the blocks that position
        selected in each sparse layer [sparse layers, K/V heads, k] i32, -1
        past their count, the slot state as `_prefill_core` returns it)."""
        import jax
        import jax.numpy as jnp
        blk = self._block_meta
        L, H, Dh, D = self._dims()
        B = tokens.shape[1]
        C = blk["prefill_chunk"] or B
        Hc = self._kv_heads()
        n_sparse = self._table_layer(None, "index")
        n_linear = self._table_layer(None, "ssm")
        stride, size, block = (blk[k] for k in (
            "sparse_kernel_stride", "sparse_kernel_size", "sparse_block"))
        # a compressed key reads `front` positions before its chunk: the K
        # buffer keeps that many rows of zeros before position 0, and the
        # key buffer's row r holds compressed key r - (m - 1)
        m = size // stride
        front = (m - 1) * stride
        carry = {"kc": jnp.zeros((n_sparse, front + B, Hc, Dh), jnp.float32),
                 "vc": jnp.zeros((n_sparse, B, Hc, Dh), jnp.float32),
                 "ki": jnp.zeros((n_sparse, B // stride, Hc * Dh),
                                 jnp.float32),
                 "row": jnp.zeros((D,), jnp.float32),
                 "picks": jnp.full(
                     (n_sparse, Hc, min(blk["sparse_topk"], B // block)),
                     -1, jnp.int32)}
        if n_linear:
            carry["ss"] = jnp.zeros((n_linear,) + tuple(
                blk[k] for k in _SSM_DIMS[:3]), jnp.float32)

        def chunk(carry, c):
            carry = dict(carry)
            s0 = c * C
            pos = s0 + jnp.arange(C)
            live = pos < true_len
            # where in this chunk the prompt's last position is, if it is
            last = true_len - 1 - s0
            here = (last >= 0) & (last < C)
            x = self._embed(state, jax.lax.dynamic_slice_in_dim(
                tokens, s0, C, axis=1), pos[None], tp)

            def attend(q, k, v, ls):
                k, v = (jnp.where(live[:, None, None], t[0], 0.0)
                        for t in (k, v))
                carry["kc"] = jax.lax.dynamic_update_slice(
                    carry["kc"], k[None], (ls, front + s0, 0, 0))
                carry["vc"] = jax.lax.dynamic_update_slice(
                    carry["vc"], v[None], (ls, s0, 0, 0))
                with jax.named_scope("sparse_select"):
                    # the compressed keys whose last position is in this
                    # chunk: stride * j + size - 1 in s0 .. s0 + C - 1
                    rows = jax.lax.dynamic_slice_in_dim(
                        carry["kc"][ls], s0, C + front, axis=0)
                    ck = _compressed_keys(rows.reshape(C + front, -1), blk)
                    j = s0 // stride - (m - 1) + jnp.arange(C // stride)
                    ck = jnp.where(((j >= 0) & (stride * j + size - 1
                                                < true_len))[:, None],
                                   ck, 0.0)
                    carry["ki"] = jax.lax.dynamic_update_slice(
                        carry["ki"], ck[None], (ls, s0 // stride, 0))
                out, picks = self._chunk_attention(
                    q[0], carry["kc"][ls, front:], carry["vc"][ls],
                    carry["ki"][ls, m - 1:], c, C, true_len)
                carry["picks"] = carry["picks"].at[ls].set(jnp.where(
                    here, jax.lax.dynamic_index_in_dim(
                        picks, jnp.clip(last, 0, C - 1), keepdims=False),
                    carry["picks"][ls]))
                return out[None]

            def scan(xs, Bm, Cm, dt, A, ll):
                y, after = ssd_chunked_scan(
                    xs[0], Bm[0], Cm[0],
                    jnp.where(live[:, None], dt[0], 0.0), A,
                    blk["ssm_chunk"], state=carry["ss"][ll])
                carry["ss"] = carry["ss"].at[ll].set(after)
                return y[None]

            for i in range(L):
                x, _ = self._block(
                    state, i, x, pos[None], functools.partial(
                        attend, ls=self._table_layer(i)), live, tp=tp,
                    ssm=("ssm_scan", functools.partial(
                        scan, ll=self._table_layer(i, "ssm"))))
            carry["row"] = jnp.where(
                here,
                jax.lax.dynamic_index_in_dim(
                    x[0], jnp.clip(last, 0, C - 1), keepdims=False),
                carry["row"])
            return carry

        def one(carry, c):
            return jax.lax.cond(c * C < true_len, chunk,
                                lambda carry, c: carry, carry, c), None

        carry, _ = jax.lax.scan(one, carry, jnp.arange(B // C))
        left = {"kv": (carry["kc"][:, None, front:], carry["vc"][:, None]),
                "index": (carry["ki"][:, None, m - 1:],)}
        if n_linear:
            left["ssm"] = (carry["ss"][:, None],)
        return carry["row"], carry["picks"], sum(
            (left[kind.name] for kind, _ in self._kinds), ())

    def _chunk_attention(self, q, k, v, ck, c, C, true_len):
        """A sparse_attention layer's attention of one prefill chunk: q [C,
        H, Dh] the queries at positions c * C .., k / v [B, Hc, Dh] the
        rows written so far (zeros past them), ck [J, Hc * Dh] the
        compressed keys so far -> ([C, H, Dh], the blocks each query
        selected [C, Hc, k] i32, -1 past their count).  Stage 1 by blocks of
        at most `PREFILL_QUERY_BLOCK` queries (`lax.map`), against the
        compressed keys at "highest" precision, and the selection
        (`_sparse_select`, the step's rule) under the scope
        `sparse_select`; stage 2 under `sparse_attention`, the softmax
        over the positions j <= t of the SELECTED blocks, which IS attention
        over the selected blocks, exactly: ONE flash body for the chunk
        (`pallas_kernels.sparse_prefill_attention`), whose scores stay in
        VMEM and whose key tiles end at each query block's causal frontier
        and at `true_len`."""
        import jax
        import jax.numpy as jnp
        from ..ops.pallas_kernels import sparse_prefill_attention
        blk = self._block_meta
        block = blk["sparse_block"]
        _, H, Dh = q.shape
        B, Hc = k.shape[:2]
        G = H // Hc
        Q = min(int(PREFILL_QUERY_BLOCK), C)
        while C % Q:
            Q -= 1
        scale = np.float32(1.0 / np.sqrt(Dh))
        ckh = ck.reshape(-1, Hc, Dh)

        def one(args):
            u, qi = args                            # qi [Q, Hc, G, Dh]
            qpos = c * C + u * Q + jnp.arange(Q)
            with jax.named_scope("sparse_select"):
                s1 = jnp.einsum("qhgd,jhd->qhgj", qi, ckh,
                                precision="highest") * scale
                ids, count, score, vals = _sparse_select(
                    s1, qpos[:, None], blk, B // block)
                picks = jnp.where(jnp.arange(ids.shape[-1])
                                  < count[..., None], ids, -1)
                # the chosen set as a mask over the blocks: the top-k ends
                # at (vals[-1], ids[-1]), ties having gone to lower indices
                sel = (score > vals[..., -1:]) | (
                    (score == vals[..., -1:])
                    & (jnp.arange(B // block) <= ids[..., -1:]))
            # the queries last: XLA then sorts (`top_k`) with the queries
            # on the lanes, 27 times faster on a v5e than along them (PERF.md
            # section 6, PR 50), and the kernel takes the blocks as rows
            return sel.transpose(1, 2, 0), picks

        q = q.astype(jnp.float32)
        sel, picks = jax.lax.map(one, (jnp.arange(C // Q), q.reshape(
            C // Q, Q, Hc, G, Dh)))
        with jax.named_scope("sparse_attention"):
            out = sparse_prefill_attention(
                q, k.reshape(B, Hc * Dh), v.reshape(B, Hc * Dh),
                sel.transpose(1, 2, 0, 3).reshape(sel.shape[1:3] + (C,)),
                c, true_len, block,
                scale=scale)
        return out, picks.reshape((C,) + picks.shape[2:])

    def _norm(self, x, state, name):
        """The block's norm over the last axis with the weights
        `name`_g (and `name`_b under layernorm)."""
        blk = self._block_meta
        if blk["norm"] == "rmsnorm":
            return _rms(x, state[name + "_g"], blk["norm_eps"])
        return _ln(x, state[name + "_g"], state[name + "_b"],
                   blk["norm_eps"])

    def _block(self, state, i, x, positions, attend, live, tp=_OFF_MESH,
               convolve=None, picks=None, latent=None, ssm=None):
        """Layer i of the stack, as the artifact's meta describes it
        (BLOCK_DEFAULTS), for every phase: x [..., D] with one position
        per leading index, weights `state["l<i>_" + name]`: the layer's
        operator on the normed x, then its FFN on the normed sum.

        With meta sandwich_norm a sublayer's result is normed once more
        (`ln1p`, `ln2p`) before it joins the residual stream.

        An MLA layer (`_mla`): `latent(q_nope, q_rope, row, wkv_b)` gets
        the heads' queries, the position's latent row and the
        up-projection, and returns the heads' attention output [..., H,
        v_head_dim]; whether it expands the rows or absorbs the
        up-projection, and where it keeps the row, is the phase's.

        An ATTENTION layer: `attend(q, k, v)` gets q [..., Hl, Dh], k
        [..., K/V heads, Dh] and v [..., K/V heads, Dv] (the layer's OWN
        K/V heads: a window layer may have its own count; normed and rotated
        where the block says so — the cache holds rotated K; under meta
        rope_layers=window a window_attention layer's alone are rotated; a
        window layer of meta window_sink also `sink=` its [Hl] logits) and
        returns the attention output [..., Hl, Dv]; what it does with k and
        v (collect
        them, write them to the slot table or, a WINDOW_ATTENTION layer's,
        to its ring) and which keys a position sees is the phase's, which
        hands a window layer its own `attend`.  A CONV layer (a
        gated short convolution): B, C, u = split3(h @ conv_in); y = C *
        `convolve(B * u, taps [D, K])` @ conv_out, where `convolve`
        returns, at each position, the taps' sum over that position's
        input and the K-1 before it; where those come from (the
        sequence, the slot's conv state) and what is kept of them is the
        phase's.  An ATTENTION+SSM layer: the attention above and a
        state-space mixer (`_ssm`) on the SAME normed input, summed into
        the residual stream together; `ssm` = (the phase's scope, its
        `scan(xs, Bm, Cm, dt, A)`: the recurrence's outputs at the
        positions, from wherever the phase keeps the scanned state), and
        `convolve` finds the mixer's conv its earlier inputs; an SSM layer
        is that mixer ALONE (no attention, no K/V rows).  With meta
        residual_multiplier every sublayer's result is multiplied by it as
        it joins the residual stream; with position=none nothing tells an
        attending layer a position but causality.  A
        LINEAR_ATTENTION layer (`_linear`) runs its recurrence through the
        same `ssm` callback; a SPARSE_ATTENTION layer is an attention layer
        whose phase hands it an `attend` that selects blocks.  `live`
        [tokens] marks the rows a routed FFN counts,
        and a list given as `picks` receives its chosen experts.
        Returns (x', routing facts [3] i32 or None).  Under TP each
        column->row pair closes with one psum."""
        import contextlib
        import jax
        import jax.numpy as jnp
        blk = self._block_meta
        _, H, Dh, D = self._dims()
        op, ffn = self.layer_kinds[i]
        p = "l%d_" % i
        Hl = H // tp.size
        lead = x.shape[:-1]
        h = self._norm(x, state, p + "ln1")

        def scaled(y):
            return y if blk["residual_multiplier"] == 1.0 \
                else y * blk["residual_multiplier"]

        def joins(y, name):
            # a sublayer's result on its way into the residual stream
            return scaled(self._norm(y, state, p + name)
                          if blk["sandwich_norm"] else y)

        def project(w, heads, gain=None, size=Dh):
            t = _mm(h if blk["attention_in_multiplier"] == 1.0
                    else h * blk["attention_in_multiplier"], state[p + w])
            if gain and blk["qk_norm"] is True:
                # over the whole projection, before the split into heads
                t = _rms(t, state[p + gain], blk["norm_eps"])
            t = t.reshape(lead + (heads, size))
            if gain and blk["qk_norm"] == "head":
                t = _rms(t, state[p + gain], blk["norm_eps"])
            return t

        if op == "mla":
            x = x + joins(self._mla(state, p, h, positions, latent), "ln1p")
        elif op == "conv":
            with jax.named_scope("short_conv"):
                b, c, u = jnp.split(_mm(h, state[p + "conv_in"]), 3,
                                    axis=-1)
                x = x + joins(_mm(c * convolve(b * u, state[p + "conv_w"]),
                                  state[p + "conv_out"]), "ln1p")
        elif op == "linear_attention":
            with jax.named_scope("linear_attention"):
                x = x + joins(self._linear(state, p, h, positions, project,
                                           *ssm), "ln1p")
        elif op == "ssm":
            x = x + joins(self._ssm(state, p, h, convolve, *ssm), "ln1p")
        else:
            Hkv = self._kv_heads(op) // tp.size
            Dv = self._v_head_dim
            with (jax.named_scope("gqa_attention") if Hkv != Hl
                  else contextlib.nullcontext()):
                q, k, v = (project("wq", Hl, "qn_g"),
                           project("wk", Hkv, "kn_g"),
                           project("wv", Hkv, size=Dv))
                if blk["key_multiplier"] != 1.0:
                    k = k * blk["key_multiplier"]
                if blk["value_scale"] != 1.0:
                    v = v * blk["value_scale"]
                if blk["position"] == "rope" and (
                        blk["rope_layers"] == "all"
                        or (blk["rope_layers"], op) == (
                            "window", "window_attention")):
                    # a theta by kind, over the head's rotated lanes
                    theta = (op == "window_attention"
                             and blk["window_rope_theta"]) \
                        or blk["rope_theta"]
                    # (`_rope` as it always was called where the whole
                    # head turns: K-EXAONE's plant replaces it)
                    turn = functools.partial(
                        _rope, lanes=blk["rotary_dim"]) \
                        if blk["rotary_dim"] else _rope
                    q, k = turn(q, positions, theta), turn(k, positions,
                                                           theta)
                # a window layer's learned sink, a logit a query head
                sink = {"sink": state[p + "sink"]} if (
                    blk["window_sink"] and op == "window_attention") else {}
                mixed = attend(q, k, v, **sink).reshape(lead + (Hl * Dv,))
                if blk["output_gate"]:
                    mixed = mixed * jax.nn.sigmoid(_mm(h, state[p + "wg"]))
                att = tp.psum(_mm(mixed, state[p + "wo"]))
                if blk["attention_out_multiplier"] != 1.0:
                    att = att * blk["attention_out_multiplier"]
                x = x + joins(att, "ln1p")
            if op == "attention+ssm":
                x = x + scaled(self._ssm(state, p, h, convolve, *ssm))
        h2 = self._norm(x, state, p + "ln2")
        if ffn == "dense_swiglu":
            with jax.named_scope("dense_ffn"):
                return x + joins(_swiglu(
                    h2, state[p + "ffn_gate"], state[p + "ffn_up"],
                    state[p + "ffn_down"], *blk["mlp_multipliers"]),
                    "ln2p"), None
        if ffn == "moe_swiglu":
            y, facts = moe_ffn(
                h2.reshape(-1, D), state[p + "router"],
                state[p + "w_gate"], state[p + "w_up"],
                state[p + "w_down"], blk["experts_per_token"],
                blk["norm_topk_prob"], live,
                expert_bias=state[p + "expert_bias"]
                if blk["router"] == "sigmoid_bias" else None, picks=picks,
                sigmoid=blk["router"] == "sigmoid",
                scaling=blk["routed_scaling"],
                held=blk["experts_held"] or None)
            y = y.reshape(x.shape)
            if blk["n_shared_experts"]:
                # beside `moe_ffn`'s scope, whose readers count the
                # routed experts' work
                with jax.named_scope("shared_expert"):
                    y = y + _swiglu(h2, state[p + "shared_gate"],
                                    state[p + "shared_up"],
                                    state[p + "shared_down"])
            return x + joins(y, "ln2p"), facts
        mlp = jnp.maximum(h2 @ state[p + "w1"] + state[p + "b1"],
                          0.0) @ state[p + "w2"]
        return x + tp.psum(mlp) + state[p + "b2"], None

    def _ssm(self, state, p, h, convolve, scope, scan):
        """An attention+ssm or an ssm layer's state-space mixer (Mamba-2 /
        SSD) on the normed input h [..., D] (weights `state[p + "ssm_" +
        name]`)
        -> [..., D], before the residual sum:

            [z | xBC | dt] = ((h * ssm_in_multiplier) ssm_in) * the
                segments' ssm_multipliers            (z, x, B, C, dt)
            xBC = silu(conv(xBC; conv_w [C, K]) + conv_b)   causal,
                depthwise: `convolve`, the phase's, finds the K - 1
                earlier inputs and keeps the last ones (PRE-activation)
            xs, B, C = split(xBC) -> [Hs, P], [G, N], [G, N]
            dt = softplus(dt + dt_bias) [Hs];  A = -exp(A_log) [Hs]
            y = scan(xs, B, C, dt, A) + D xs:  S_t = exp(dt_t A) S_{t-1}
                + dt_t xs_t (outer) B_t,  y_t = S_t . C_t   (head h reads
                group h // (Hs / G))
            y = rms(y * silu(z)) by group of d_ssm / G, times norm_g
                (the gate BEFORE the norm)
            result = (y ssm_out) * ssm_out_multiplier

        `scan` is the phase's: a chunked scan over a prompt
        (`ssd_chunked_scan`), one step of the recurrence on the slots'
        scanned state.  Scopes: `ssm_proj` around the two projections,
        the gate and the norm; `scope` (`ssm_scan` | `ssm_update`) around
        the conv and the recurrence."""
        import jax
        import jax.numpy as jnp
        blk = self._block_meta
        Hs, P, N, G = (blk[k] for k in _SSM_DIMS)
        d_ssm, conv, _ = _ssm_widths(blk)
        lead = h.shape[:-1]
        w = {n: state[p + "ssm_" + n] for n in (
            "in", "out", "conv_w", "conv_b", "dt_bias", "A_log", "D",
            "norm_g")}
        with jax.named_scope("ssm_proj"):
            proj = _mm(h if blk["ssm_in_multiplier"] == 1.0
                       else h * blk["ssm_in_multiplier"], w["in"])
            if blk["ssm_multipliers"]:
                mz, mx, mb, mc, mdt = blk["ssm_multipliers"]
                proj = proj * np.repeat(
                    np.float32([mz, mx, mb, mc, mdt]),
                    [d_ssm, d_ssm, G * N, G * N, Hs])
            z, xBC, dt = jnp.split(proj, [d_ssm, d_ssm + conv], axis=-1)
        with jax.named_scope(scope):
            dt = jax.nn.softplus(dt + w["dt_bias"])
            xBC = jax.nn.silu(convolve(xBC, w["conv_w"]) + w["conv_b"])
            xs, Bm, Cm = jnp.split(xBC, [d_ssm, d_ssm + G * N], axis=-1)
            xs = xs.reshape(lead + (Hs, P))
            y = scan(xs, Bm.reshape(lead + (G, N)),
                     Cm.reshape(lead + (G, N)), dt, -jnp.exp(w["A_log"]))
            y = y + w["D"][:, None] * xs
        with jax.named_scope("ssm_proj"):
            y = _gated_group_norm(y.reshape(lead + (d_ssm,)), z,
                                  w["norm_g"], G, blk["norm_eps"])
            out = _mm(y, w["out"])
            return out if blk["ssm_out_multiplier"] == 1.0 \
                else out * blk["ssm_out_multiplier"]

    def _linear(self, state, p, h, positions, project, scope, scan):
        """A linear_attention layer's operator on the normed input h [...,
        D] (weights `state[p + name]`) -> [..., D], before the residual
        sum, under the scope `linear_attention`:

            q, k = (h wq, h wk) -> [Hs, Ns];  v = h wv -> [Hs, P]
            q, k = rms a head (qk_norm), rotated (rope_layers all|linear)
            o = scan(v, k, q / sqrt(Ns), dt = 1, A = linear_log_decay):
                S_t = exp(A) S_{t-1} + v_t (outer) k_t,  o_t = S_t . q_t
            o = rms(o) a head times on_g (output_norm), times
                sigmoid(h wg) (output_gate)
            result = (o wo) * attention_out_multiplier

        `project` is `_block`'s (a projection split into heads, normed as
        `qk_norm` says); `scan` is the phase's, the one a state-space
        mixer's recurrence goes through (`_ssm`): a step of
        `pallas_kernels.ssm_update` on the slots' scanned state,
        `ssd_chunked_scan` over a prompt's chunk from the carried state;
        `scope` (`ssm_update` | `ssm_scan`) is around the recurrence
        alone."""
        import jax
        import jax.numpy as jnp
        blk = self._block_meta
        Hs, P, N, _ = (blk[k] for k in _SSM_DIMS)
        lead = h.shape[:-1]
        q, k, v = (project("wq", Hs, "qn_g", N), project("wk", Hs, "kn_g", N),
                   project("wv", Hs, size=P))
        if blk["position"] == "rope" and blk["rope_layers"] in ("all",
                                                                "linear"):
            q = _rope(q, positions, blk["rope_theta"])
            k = _rope(k, positions, blk["rope_theta"])
        with jax.named_scope(scope):
            o = scan(v, k, q * np.float32(1.0 / np.sqrt(N)),
                     jnp.ones(lead + (Hs,), jnp.float32),
                     jnp.asarray(blk["linear_log_decay"], jnp.float32))
        if blk["output_norm"]:
            o = _rms(o, state[p + "on_g"].reshape(Hs, P), blk["norm_eps"])
        o = o.reshape(lead + (Hs * P,))
        if blk["output_gate"]:
            o = o * jax.nn.sigmoid(_mm(h, state[p + "wg"]))
        out = _mm(o, state[p + "wo"])
        return out if blk["attention_out_multiplier"] == 1.0 \
            else out * blk["attention_out_multiplier"]

    def _mla(self, state, p, h, positions, latent):
        """An MLA layer's operator on the normed input h [..., D] (weights
        `state[p + name]`) -> [..., D], before the residual sum:

            c_q = rms(h wq_a; q_a_g);  q = c_q wq_b -> [H, nope | rope]
            [c_kv | k_rope] = h wkv_a;  c_kv = rms(c_kv; kv_a_g)
            q_rope, k_rope turned by the position (half-split, over the
            rope lanes alone; ONE k_rope a position, shared by the heads)
            row = [c_kv | k_rope]: all the slot table keeps of a position
            a = latent(q_nope, q_rope, row, wkv_b [rank, H, nope | v])
            result = concat_h(a) wo

        `latent` is the phase's (`_block`): `_mla_expanded` over a
        prompt's own rows, `_mla_absorbed` over the slot table.  Scopes:
        `mla_proj` here (and the up-projection or its absorption there),
        `mla_prefill` / `mla_attention` around the attention itself."""
        import jax
        import jax.numpy as jnp
        blk = self._block_meta
        H = self._dims()[1]
        _, rkv, dn, dr, dv = (blk[k] for k in _MLA_DIMS)
        eps, theta = blk["norm_eps"], blk["rope_theta"]
        lead = h.shape[:-1]
        with jax.named_scope("mla_proj"):
            c_q = _rms(_mm(h, state[p + "wq_a"]), state[p + "q_a_g"], eps)
            q = _mm(c_q, state[p + "wq_b"]).reshape(lead + (H, dn + dr))
            kv = _mm(h, state[p + "wkv_a"])
            row = jnp.concatenate([
                _rms(kv[..., :rkv], state[p + "kv_a_g"], eps),
                _rope(kv[..., None, rkv:], positions, theta)[..., 0, :]],
                axis=-1)
            q_rope = _rope(q[..., dn:], positions, theta)
        a = latent(q[..., :dn], q_rope, row,
                   state[p + "wkv_b"].reshape(rkv, H, dn + dv))
        with jax.named_scope("mla_proj"):
            return _mm(a.reshape(lead + (H * dv,)), state[p + "wo"])

    def _mla_scale(self):
        blk = self._block_meta
        return 1.0 / np.sqrt(blk["qk_nope_head_dim"]
                             + blk["qk_rope_head_dim"])

    def _mla_expanded(self, q_nope, q_rope, rows, wkv_b):
        """The EXPANDED path, a prefill's: every row [1, B, R] of the
        prompt goes up to its heads' keys and values, [k_nope_h | v_h] =
        c_kv wkv_b, a head's key is [k_nope_h | k_rope], and the attention
        is the causal oracle over them -> [1, B, H, v].  Compute-bound and
        in the prompt's own arrays: nothing of it is kept but the rows.
        The scores are whole, [H, B, B] fp32 (0.54 GB at 128 heads and a
        bucket of 1024), so an MLA artifact's prefill buckets stop where
        that fits."""
        import jax
        import jax.numpy as jnp
        dn, rkv = q_nope.shape[-1], wkv_b.shape[0]
        with jax.named_scope("mla_proj"):
            kv = _contract(rows[..., :rkv], wkv_b, functools.partial(
                jnp.einsum, "...r,rhd->...hd"))
        with jax.named_scope("mla_prefill"):
            k_rope = jnp.broadcast_to(
                rows[..., None, rkv:],
                kv.shape[:-1] + (rows.shape[-1] - rkv,))
            return _causal_attention(
                jnp.concatenate([q_nope, q_rope], axis=-1),
                jnp.concatenate([kv[..., :dn], k_rope], axis=-1),
                kv[..., dn:], self._mla_scale())

    def _mla_absorbed(self, q_nope, q_rope, table, seen, at, wkv_b):
        """The ABSORBED path, a decode step's: q_nope . k_nope_h(s) =
        (q_nope wkv_b[k part, h]^T) . c_kv(s), so each head's query goes
        DOWN to the rows' space once, [N, H, rank | rope], the kernel
        attends over layer `at` of the latent table itself under `seen`
        [N] positions (128 heads on one row, read once), and the weighted
        latents go up to values afterwards: u_h wkv_b[v part, h] -> [N, H,
        v].  No per-head key or value of a cached position is ever
        formed."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas_kernels import latent_decode_attention
        dn, rkv = q_nope.shape[-1], wkv_b.shape[0]
        with jax.named_scope("mla_proj"):
            q = jnp.concatenate([
                _contract(q_nope, wkv_b[..., :dn], functools.partial(
                    jnp.einsum, "nhd,rhd->nhr")), q_rope], axis=-1)
            q = _pad_rows(q, table.shape[3:])
        with jax.named_scope("mla_attention"):
            u = latent_decode_attention(q, table, seen, rkv,
                                        self._mla_scale(), layer=at)
        with jax.named_scope("mla_proj"):
            return _contract(u, wkv_b[..., dn:], functools.partial(
                jnp.einsum, "nhr,rhd->nhd"))

    def _prefill_core_seqpar(self, state, tokens, true_len, tp):
        """SEQUENCE-parallel TP prefill (parallel/ulysses.py's scheme):
        each member owns B/m prompt positions; per layer the sharded
        weights all_gather back whole (exact data movement, amortized
        over the long bucket — prefill is compute-bound, unlike
        decode) and `_block` runs on them as off a mesh, its attention
        the ulysses seq<->heads all_to_all pair around the SAME
        `_causal_attention` oracle, whose head-sharded K/V are the
        cache's at-rest layout.  Every position's math runs with FULL
        weights in the single-device reduction order, so this path is
        BIT-EXACT vs the oracle — no psum ever touches an activation."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.parallel.mesh import tp_param_pspec
        from paddle_tpu.parallel.ulysses import (heads_to_seq,
                                                 seq_to_heads)
        L = self._dims()[0]
        Bl = tokens.shape[1] // tp.size
        scale = self._attention_scale
        at = tp.index() * Bl
        positions = (at + jnp.arange(Bl))[None]              # [1, Bl]
        x = self._embed(
            state, jax.lax.dynamic_slice(tokens, (0, at), (1, Bl)),
            positions, tp)
        ks, vs = [], []

        def whole(*prefixes):
            out = {}
            for n in state:
                if n.startswith(prefixes):
                    axes = [a for a, ax in enumerate(
                        tp_param_pspec(n, state[n].shape)) if ax]
                    out[n] = tp.all_gather(state[n], axis=axes[0]) \
                        if axes else state[n]
            return out

        def attend(q, k, v):
            # seq->heads: full sequence, resident head block (exact)
            q, k, v = (seq_to_heads(t, tp.axis) for t in (q, k, v))
            ks.append(k)
            vs.append(v)
            return heads_to_seq(_causal_attention(q, k, v, scale),
                                tp.axis)

        for i in range(L):
            x, _ = self._block(whole("l%d_" % i), i, x, positions, attend,
                               positions[0] < true_len)
        xg = tp.all_gather(x, axis=1)            # [1, B, D] whole
        first = self._first_token(whole("lnf_", "lm_head"), xg, true_len,
                                  _OFF_MESH)
        return (first,) + _zero_pad_positions(ks, vs, true_len)

    def _write(self, kc, vc, i, where, k_new, v_new, tp):
        """(kc', vc'): the new rows of the K/V tables' layer i (an
        attention layer's `_table_layer`) `_land`ed at `where` in the
        carried tables.  Under int8 they quantize in-graph first
        (every phase through here, so a row is the same byte whichever
        phase wrote it) and the attention dequantizes in-register —
        float KV rows never reach the cache arrays."""
        if self._kv_quant:
            sc = tp.head_scales(self._kv_scales[:, i], k_new.shape[-2])
            k_new = self._quantize_kv(k_new, sc[0])
            v_new = self._quantize_kv(v_new, sc[1])
        # [.., Hkv, Dh] -> the table's flat row
        return tuple(_land(t, i, where, r.reshape(r.shape[:-2] + (-1,)))
                     for t, r in ((kc, k_new), (vc, v_new)))

    def _attend_table(self, q, kc, vc, lengths, ahead, i, tp, window=0,
                      sinks=None):
        """The decode kernel over layer i of the carried K/V tables: q
        [N, Hl, Dh], slot n under its first `lengths[n] + ahead`
        positions -> [N, Hl, Dv] (the V table's rows may hold heads of
        another size than the K table's; `sinks` [Hl] f32: a window
        layer's learned logit a head in the softmax's denominator, the
        kernel's).  With `window` W the tables are a window
        layer's RINGS [window layers, N, W, Hc * Dh] and slot n attends
        under min(lengths[n] + ahead, W) rows: the kernel as it is, for
        rows carry their own rotation and a softmax does not care in which
        order its keys lie; a ring that has not wrapped holds its rows
        from 0 on, one that has is all live, and a row of the slot's last
        owner is never under the clamp.  The kernel reads the layer of the
        stacked table through its block index maps
        (`decode_attention(..., layer=i)`): no layer is sliced out.
        Where the table holds fewer heads than q has (grouped-query),
        the kernel streams each K/V row once for its group of query
        heads."""
        from paddle_tpu.ops.pallas_kernels import (
            decode_attention, decode_attention_head_slice)
        Hl = q.shape[1]
        scale = self._attention_scale
        scales = self._kv_scales[:, i] if self._kv_quant else None
        seen = lengths + ahead
        if window:
            import jax.numpy as jnp
            seen = jnp.minimum(seen, window)
        more = {} if sinks is None else {"sinks": sinks}
        if tp.size > 1:
            # each member slices its heads' scales out of the baked full
            # table
            return decode_attention_head_slice(
                q, kc, vc, seen, tp.index() * Hl, Hl,
                scale=scale, kv_scales=scales, layer=i, **more)
        return decode_attention(q, kc, vc, seen, scale=scale,
                                kv_scales=scales, layer=i, **more)

    def _step_logits(self, state, *args, tp=_OFF_MESH):
        """`_step_core` without the routing facts, on the flat arguments
        of `_table_specs` (the slot state's `_n_tables` leaves first):
        (logits [N, vocab] f32, *slot state').  A stack in which a
        recurrent layer follows a routed FFN (`_step_picks`) hands out
        the routed layers' chosen experts [routed layers, N, k] i32
        second: there a router's near-tie moves the positions AFTER it
        too, and only the picks tell that from a fault."""
        import jax.numpy as jnp
        n = self._n_tables
        picks = [] if self._step_picks else None
        logits, tables, _ = self._step_core(state, args[:n], *args[n:],
                                            tp=tp, picks=picks)
        return (logits,) + ((jnp.stack(picks),) if picks else ()) + tables

    def _step_tokens(self, state, kc, vc, lengths, last_tokens, active,
                     tp):
        """One greedy step without the routing facts, for the fused
        speculative round's draft: a routed FFN runs, what it touched is
        dropped."""
        import jax.numpy as jnp
        logits, (kc, vc), _ = self._step_core(
            state, (kc, vc), lengths, last_tokens, active, tp=tp)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), kc, vc

    def _step_core(self, state, tables, lengths, last_tokens, active,
                   tp=_OFF_MESH, picks=None):
        """One fixed-shape decode step over the slots' whole state.
        `tables` = the leaves of `_table_names`, the stack's kinds of
        slot state in the record's order (`slot_state.KINDS`); lengths
        [N] i32 (live cached positions), last_tokens [N] i32, active [N]
        bool -> (logits [N, vocab] f32, tables', per-layer routing
        facts).  Each layer is `_block` at position `lengths` (a slot's
        own), its callbacks each on the layer's OWN kind of state: an
        attention layer's `attend` the write of the new row and the
        decode kernel over the slot table (a window layer's over its
        ring, an MLA layer's `latent` over its one table of latent rows),
        a conv layer's `convolve` the taps over the slot's conv state and
        the new input, which then roll into the state, a state-space
        mixer's (or a linear_attention layer's) `scan` one step of the
        recurrence on every live slot's whole scanned state, a
        sparse_attention layer's `attend` the write of the new row, of the
        compressed key it completes, the selection and the sparse kernel
        over the selected blocks.

        The tables are CARRIED through the layers and updated IN PLACE:
        attention layer i scatters its N new rows to (i, n, lengths[n])
        of the stacked table (`_land`) and the kernel reads layer i of
        that same table (`_attend_table`).  No layer is selected, sliced
        out or stacked back, so with the tables donated (every phase
        that returns them donates them: `_phase_jit`) the step's input
        and output are ONE buffer and what it writes is N rows a layer.
        Nobody else may hold them: `DecodeSession` replaces its own by
        each call's results.

        Writes are gated by `active`: an inactive slot's K/V row goes to
        position S (a ring's to W), out of range, and is DROPPED, as is
        the row of a slot already at `lengths == S`, and its state of a
        fixed size keeps what it held; so a freed (zeroed) slot stays
        zero and per-slot independence is exact.

        Under TP (inside shard_map) kc/vc are this member's resident
        HEAD shard and weights are local column/row shards — params and
        KV never materialize unsharded, per-step HBM traffic per member
        ~1/mesh_size."""
        import contextlib
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas_kernels import (
            sparse_decode_attention, ssm_update)
        L = self._dims()[0]
        # each layer's callback below reads and replaces its OWN leaves
        held = dict(zip(self._table_names, tables))
        N, S = tables[0].shape[1:3]
        x = self._embed(state, last_tokens, lengths, tp)        # [N, D]
        # where a slot's new row lands; S (past the end) = nowhere
        where = (jnp.arange(N),
                 jnp.where(active, lengths, S).astype(jnp.int32))
        W = self._block_meta["sliding_window"]
        if W:
            # ... and in a ring: at lengths % W, W (past its end) = nowhere
            where_ring = (where[0], jnp.where(
                active & (lengths < S), lengths % W, W).astype(jnp.int32))
        facts = []
        for i in range(L):
            def attend(q, k_new, v_new, at=self._table_layer(i)):
                held["kc"], held["vc"] = self._write(
                    held["kc"], held["vc"], at, where, k_new, v_new, tp)
                with (jax.named_scope("full_attention") if W
                      else contextlib.nullcontext()):
                    return self._attend_table(q, held["kc"], held["vc"],
                                              lengths, 1, at, tp)

            def attend_window(q, k_new, v_new,
                              at=self._table_layer(i, "ring"), sink=None):
                held["kw"], held["vw"] = self._write(
                    held["kw"], held["vw"], at, where_ring, k_new, v_new,
                    tp)
                with jax.named_scope("window_attention"):
                    return self._attend_table(
                        q, held["kw"], held["vw"], lengths, 1, at, tp,
                        window=W,
                        **({} if sink is None else {"sinks": sink}))

            def attend_sparse(q, k_new, v_new, at=self._table_layer(i),
                              ai=self._table_layer(i, "index")):
                # the new row lands as an attention layer's; stage 1 picks
                # the slot's blocks, stage 2 stages those alone
                held["kc"], held["vc"] = self._write(
                    held["kc"], held["vc"], at, where, k_new, v_new, tp)
                with jax.named_scope("sparse_select"):
                    held["ki"] = self._land_compressed(
                        held["ki"], held["kc"], ai, at, lengths, active)
                    ids, counts = self._step_select(q, held["ki"], ai,
                                                    lengths)
                if picks is not None:
                    # the blocks chosen, -1 past their count
                    picks.append(jnp.where(
                        jnp.arange(ids.shape[-1]) < counts[..., None], ids,
                        -1))
                with jax.named_scope("sparse_attention"):
                    return sparse_decode_attention(
                        q, held["kc"], held["vc"], ids,
                        jnp.where(active[:, None], counts, 0), lengths + 1,
                        at, self._block_meta["sparse_block"],
                        scale=1.0 / np.sqrt(q.shape[-1]))

            def convolve(z, taps, at=self._table_layer(i, "conv")):
                # z [N, C]: the slot's K-1 kept inputs, then this one
                cs = held["cs"]
                seen = jnp.concatenate([cs[at], z[:, None]], axis=1)
                held["cs"] = cs.at[at].set(jnp.where(
                    active[:, None, None], seen[:, 1:], cs[at]))
                return sum(taps[:, j] * seen[:, j]
                           for j in range(taps.shape[1]))

            def scan(xs, Bm, Cm, dt, A, at=self._table_layer(i, "ssm")):
                # one step of the recurrence on every RUNNING slot's state
                # [Hs, P, Ns], in one pass over it (`pk.ssm_update`: read
                # once, read out, written back in place in the carried
                # table); a slot that does not run keeps what it held and
                # is not visited
                y, held["ss"] = ssm_update(
                    held["ss"], jnp.exp(dt * A), dt[:, :, None] * xs, Bm,
                    Cm, active, at)
                return y

            def latent(q_nope, q_rope, row, wkv_b,
                       at=self._table_layer(i, "latent")):
                held["kc"] = _land(held["kc"], at, where, row)
                return self._mla_absorbed(q_nope, q_rope, held["kc"],
                                          lengths + 1, at, wkv_b)

            x, f = self._block(
                state, i, x, lengths,
                {"window_attention": attend_window,
                 "sparse_attention": attend_sparse}.get(
                     self.layer_kinds[i][0], attend),
                active, tp=tp, convolve=convolve, picks=picks, latent=latent,
                ssm=("ssm_update", scan))
            facts.append(f)
        return self._head(state, x, tp), tuple(held.values()), facts

    def _land_compressed(self, ki, kc, ai, at, lengths, active):
        """The indexer's cache `ki` [sparse layers, N, J, W] with the
        compressed key that COMPLETES at this step landed in layer `ai`:
        slot n's new row is position lengths[n]; where that is the last
        position of compressed key j (stride * j + size - 1), the key is
        the mean of rows stride * j .. of layer `at` of the K table (the
        new row among them, already written) and lands at row j; for any
        other slot, and one that does not run, nowhere (`_land`)."""
        import jax.numpy as jnp
        blk = self._block_meta
        size, stride = blk["sparse_kernel_size"], blk["sparse_kernel_stride"]
        N, J = ki.shape[1:3]
        first = lengths - (size - 1)
        due = active & (first >= 0) & (first % stride == 0)
        rows = kc[at, jnp.arange(N)[:, None],
                  jnp.maximum(first, 0)[:, None] + jnp.arange(size)[None]]
        return _land(ki, ai, (jnp.arange(N), jnp.where(
            due, first // stride, J).astype(jnp.int32)),
            _compressed_keys(rows, blk)[:, 0])

    def _step_select(self, q, ki, ai, lengths):
        """Stage 1 for the step's one query a slot: q [N, H, Dh] at
        position lengths[n] against layer `ai` of the indexer's cache ->
        (block ids [N, Hc, k], counts [N, Hc]) (`_sparse_select`)."""
        import jax.numpy as jnp
        N, H, Dh = q.shape
        Hc = self._kv_heads()
        s1 = jnp.einsum(
            "nhgd,njhd->nhgj", q.reshape(N, Hc, H // Hc, Dh),
            ki[ai].reshape(N, -1, Hc, Dh),
            precision="highest") * np.float32(1.0 / np.sqrt(Dh))
        ids, counts, _, _ = _sparse_select(
            s1, lengths[:, None], self._block_meta,
            self.max_seq_len // self._block_meta["sparse_block"])
        return ids, counts

    def _verify_math(self, state, kc, vc, lengths, tokens, active,
                     tp=_OFF_MESH):
        """One speculative VERIFY step over the whole slot table:
        tokens [N, C] = [pending last token, draft d1..dk] (C = k+1),
        -> (g [N, C] target greedy tokens per position, m [N] accepted
        draft counts 0..k, kc', vc').

        The step's phase over C positions a slot: x is [N, C, D] at
        positions `lengths + j`, so the chunk's projections and FFN run
        ONCE (weights stream once for all C positions — the bandwidth
        win); a layer's `attend` lands all C rows a slot in the carried
        table with the step's write, an inactive slot's past the end,
        and position j of every slot then attends through the step's
        own kernel call under `lengths + j + 1` — C calls of the plain
        step's shape, so verify logits round like C sequential steps',
        which is what makes greedy acceptance exact against the
        fp32-only stream.  A routed FFN runs; its routing facts are
        dropped.

        Acceptance and rollback are in-graph: m = longest prefix with
        d_i == g_{i-1}; the rows past length+m (the rejected suffix)
        are cleared by one scatter of zeros over all layers before the
        tables return, so stale draft K/V never survives into the
        committed cache."""
        import jax.numpy as jnp
        L = self._dims()[0]
        N, C = tokens.shape
        S = kc.shape[2]
        ahead = jnp.arange(C)[None]
        positions = lengths[:, None] + ahead                    # [N, C]
        x = self._embed(state, tokens, positions, tp)           # [N,C,D]
        where = (jnp.arange(N)[:, None],
                 jnp.where(active[:, None], positions, S + ahead))
        live = jnp.repeat(active, C)
        for i in range(L):
            def attend(q, k_new, v_new, i=i):
                nonlocal kc, vc
                kc, vc = self._write(kc, vc, i, where, k_new, v_new, tp)
                return jnp.stack(
                    [self._attend_table(q[:, j], kc, vc, lengths, j + 1,
                                        i, tp) for j in range(C)], axis=1)

            x, _ = self._block(state, i, x, positions, attend, live,
                               tp=tp)
        g = jnp.argmax(self._head(state, x, tp),
                       axis=-1).astype(jnp.int32)               # [N, C]
        match = (tokens[:, 1:] == g[:, :C - 1]).astype(jnp.int32)
        m = jnp.sum(jnp.cumprod(match, axis=1), axis=1).astype(jnp.int32)
        # the committed cache keeps rows for the pending token + the m
        # accepted drafts (length + m + 1 rows); the rest of the chunk
        # goes
        lo, hi = lengths + m + 1, jnp.where(active, lengths + C, 0)
        return (g, m, _clear_rows(kc, lo, hi, C - 1),
                _clear_rows(vc, lo, hi, C - 1))

    def _step_math(self, tp=_OFF_MESH):
        """Build the decode STEP phase (SERVING.md "Fused multi-step
        decode"): up to `STEP_WINDOW` greedy decode steps as ONE
        executable, a `lax.while_loop` carrying {the slots' state, last
        tokens, the token block} through `_step_core` + argmax per
        trip.  Per-slot math is independent and every trip is the same
        `_step_core`, so a window's stream is that of one-trip
        dispatches token for token.

        Runtime arguments beside the step's own (the executable stays
        one fingerprint per slot count):
          * `budget` [N] i32: tokens each slot may still emit (its
            max_new / cache-room headroom);
          * `max_trips` [] i32: the dispatch's trip count (at most the
            window).  The serving lane sets it per dispatch: the
            largest live budget, less under the deadline governor.

        The slots that RUN are those active with budget and cache room
        left when the window begins.  The carry holds who is still
        `alive` and what each slot has `emitted`: a slot leaves `alive`
        with the trip in which it stops, whatever stops it (EOS, its
        budget met, its cache full), and from then on it is to
        `_step_core` a slot that does not run, so it writes no K/V or
        latent row, rolls no conv window and updates no scanned state:
        its state after the window is its state at its own stop.  (It
        also attends at length 0, so the decode kernel stages one block
        for it a trip and not its rows.)  The window ends at `max_trips`
        or with the trip in which the LAST running slot stops.  What a
        window that runs past its first ender costs is dead slot-trips,
        W - j for a slot that stops at trip j of W (`slots_busy_share`
        in the benchmark); what it saves is a dispatch of host work for
        every ender.  Returns (out, *slot state'); `out` is ONE int32
        vector, so a dispatch costs one fetch
        (`DecodeSession.decode_fused` splits it): the [N, STEP_WINDOW]
        token block (`emitted[s]` of row s valid, in stream order), `emitted`
        [N] (a slot's OWN count: the trips it ran, at most the window's),
        the trips run, and for a routed-expert artifact each layer's
        (experts touched SUMMED over the trips, most tokens on one
        expert, the LARGEST over the trips, the pairs that stayed on this
        member SUMMED over the trips; of a member that holds some of the
        experts also the trips that ran the layer's grouped matmuls full
        size), which is what
        `_pack_routing` carries for a prefill.  Its arguments are those
        of `_step_specs`, flat."""
        import jax
        import jax.numpy as jnp
        W = int(STEP_WINDOW)
        eos = self.eos_id
        routed = self.routed_layers

        def window(state, tables, lengths, last_tokens, active, budget,
                   max_trips):
            N, S = tables[0].shape[1:3]
            alive = active & (budget > 0) & (lengths < jnp.int32(S))
            trips = jnp.minimum(max_trips, jnp.int32(W))

            def cond(carry):
                return (carry[0] < trips) & jnp.any(carry[-2])

            def body(carry):
                i, tables, last, toks, facts, alive, emitted = carry
                # a slot that has stopped is one that does not run: it
                # writes nothing, and attends over no row of its own
                logits, tables, f = self._step_core(
                    state, tables, jnp.where(alive, lengths + emitted, 0),
                    last, alive, tp=tp)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # land this trip's tokens at column i, which for a slot
                # still alive is its own count (one-hot select: a slot
                # that does not run keeps its zeros)
                col = (jnp.arange(W)[None, :] == i) & alive[:, None]
                toks = jnp.where(col, tok[:, None], toks)
                if routed:
                    f = jnp.stack([r for r in f if r is not None])
                    facts = jnp.stack(
                        [facts[:, 0] + f[:, 0],
                         jnp.maximum(facts[:, 1], f[:, 1])]
                        + [facts[:, c] + f[:, c]
                           for c in range(2, facts.shape[1])], axis=1)
                last = jnp.where(alive, tok, last)
                emitted = emitted + alive.astype(jnp.int32)
                alive = (alive & (tok != jnp.int32(eos))
                         & (emitted < budget)
                         & (lengths + emitted < jnp.int32(S)))
                return (i + 1, tables, last, toks, facts, alive, emitted)

            carry = (jnp.int32(0), tables, last_tokens,
                     jnp.zeros((N, W), jnp.int32),
                     jnp.zeros((routed, self._routing_facts), jnp.int32),
                     alive, jnp.zeros((N,), jnp.int32))
            i, tables, _last, toks, facts, _, emitted = jax.lax.while_loop(
                cond, body, carry)
            out = jnp.concatenate([toks.reshape(-1), emitted, i[None],
                                   facts.reshape(-1)])
            return (out,) + tables

        # the executable's arguments, flat (`_step_specs`): the slot
        # state's leaves first
        n_tables = self._n_tables

        def step(state, *args):
            return window(state, args[:n_tables], *args[n_tables:])

        return step

    def _fused_spec_math(self, draft, spec_k, tp=_OFF_MESH):
        """Build the FUSED speculative round: k draft decode steps +
        the batched k+1-position verify + in-graph accept / draft-
        rollback / draft-catch-up bookkeeping, all ONE executable (one
        dispatch instead of k draft dispatches + one verify).  The
        draft's state dict rides as a traced ARGUMENT (its weights are
        not baked), and the phase key carries the draft's model
        fingerprint + cache dtype so two different drafts never collide
        on one executable.

        Every sub-phase is the same traced math the host-driven round
        runs (`draft._step_tokens` per draft trip, `self._verify_math`
        for scoring, `_clear_rows` for the draft's rollback as in
        `DecodeSession.rollback`), so committed streams stay
        bit-identical to the fp32-only plain stream and twin-draft
        acceptance stays exactly 1.0."""
        import jax.numpy as jnp
        k = int(spec_k)

        def fused(state, dstate, t_kc, t_vc, t_len, t_last,
                  d_kc, d_vc, d_len, d_last, active):
            N = t_kc.shape[1]
            adv = active.astype(jnp.int32)
            rows = jnp.arange(N)
            # 1. DRAFT: k steps on the draft table (unrolled — k is a
            # geometry constant of this executable)
            drafts = []
            for _ in range(k):
                dtok, d_kc, d_vc = draft._step_tokens(
                    dstate, d_kc, d_vc, d_len, d_last, active, tp)
                d_len = d_len + adv
                d_last = jnp.where(active, dtok, d_last)
                drafts.append(dtok)
            # 2. VERIFY: score [pending, d1..dk] in one batched step
            chunk = jnp.stack([t_last] + drafts, axis=1)      # [N, C]
            g, m, t_kc, t_vc = self._verify_math(
                state, t_kc, t_vc, t_len, chunk, active, tp=tp)
            m = jnp.where(active, m, 0)
            # 3. COMMIT: target bookkeeping (mirrors the host round)
            counts = jnp.where(active, m + 1, 0).astype(jnp.int32)
            t_len = t_len + counts
            t_last = jnp.where(active, g[rows, jnp.minimum(m, k)],
                               t_last)
            # draft sync, in-graph: partially-accepted slots roll the
            # rejected rows back (cleared, length pointer retreats,
            # pending token re-pins to the target's correction)...
            part = active & (m < k)
            newlen = d_len - jnp.where(part, k - 1 - m, 0)
            d_kc = _clear_rows(d_kc, newlen, d_len, k - 1)
            d_vc = _clear_rows(d_vc, newlen, d_len, k - 1)
            d_len = newlen
            d_last = jnp.where(part, g[rows, jnp.minimum(m, k)], d_last)
            # ...and fully-accepted slots owe the draft one catch-up
            # step (it emitted d_k without ever consuming it), pending
            # token re-pinned to the target's bonus token
            full = active & (m == k)
            _cu, d_kc, d_vc = draft._step_tokens(
                dstate, d_kc, d_vc, d_len, d_last, full, tp)
            d_len = d_len + full.astype(jnp.int32)
            d_last = jnp.where(full, g[:, k], d_last)
            return (g, m, t_kc, t_vc, t_len, t_last,
                    d_kc, d_vc, d_len, d_last)

        return fused

    # -- compiled-phase resolution (the PR 6 compile-cache ride) --------

    @staticmethod
    def _argsig(spec):
        """Fingerprint encoding of one arg spec: a plain ShapeDtype
        leaf, or a dict of them (the fused-speculative phase passes the
        DRAFT predictor's state dict as a traced argument)."""
        if isinstance(spec, dict):
            return {k: [list(v.shape), str(v.dtype)]
                    for k, v in sorted(spec.items())}
        return [list(spec.shape), str(spec.dtype)]

    def _fingerprint(self, phase_key, arg_specs, extra=None):
        from paddle_tpu import compile_cache as cc
        fp = {
            "kind": "decode_phase",
            "model": self._model_fp,
            "phase": list(phase_key),
            # the cache dtype changes the traced math (quantize-on-
            # write epilogues, baked dequant scales) without changing
            # the prefill arg specs — fingerprinting it keeps fp32 and
            # int8 executables from ever colliding (COMPILE_CACHE.md);
            # rev bumps when the phase math itself changes shape (14: a
            # member that holds some of the experts runs its grouped
            # matmuls over `held_cap` rows and hands out a fourth fact;
            # 13: a
            # routed layer's phases hand out a third fact, the pairs that
            # stayed here, and a prefill of routed FFNs behind state-space
            # layers its picks; 12: the
            # sparse decode kernel stages T selected tiles a grid step,
            # `pallas_kernels.sparse_tiles_per_step`; 11: a
            # prefill in chunks hands out the blocks its last position
            # selected behind its token; 10:
            # the state-space step's recurrence is one Mosaic call,
            # `pallas_kernels.ssm_update`; 9: a
            # window runs past its first ender, a slot that stops sits
            # the rest out; 8: a
            # prefill returns its K and V as the tables hold a position,
            # one flat row; 7:
            # the decode kernel's stream stops at a slot's length, the
            # same results from a fraction of the bytes; 6: the step is
            # a window of runtime trips; 5: verify and the
            # fused rounds scatter their rows into the carried table as
            # the step does since 4; a stored phase of older math must
            # miss)
            "kv_dtype": self._kv_dtype,
            # so do the block's keys (norm, position, qk-norm, FFN kind
            # and routing): equal weight shapes, another function
            # (a key younger than rev 12 is named only where the meta
            # moves it: an artifact written before it keeps its
            # fingerprint, and its stored executables; `v_head_dim`, which
            # attention layers read since, is older than rev 12 and always
            # named here: a stack without mla that set it, when it was
            # ignored, no longer matches its weights' shapes and does not
            # open, so no stored executable answers for another function)
            "block": [[k, self._block_meta[k]]
                      for k in sorted(self._block_meta)
                      if k not in _LATER_KEYS
                      or self._block_meta[k] != _LATER_KEYS[k]],
            "rev": 14,
            "state": cc._spec_sig(self._state_host),
            "args": [self._argsig(s) for s in arg_specs],
            "env": cc.environment_fingerprint(self._device),
        }
        if extra:
            # tensor-parallel phases fold the mesh shape in: the
            # partitioned module's collectives are specialized to the
            # axis size, so a (2,) and a (4,) executable must never
            # resolve each other's blobs
            fp.update(extra)
        return fp

    def _device_kind(self):
        import jax
        d = self._device
        if d is None:
            devs = jax.devices()
            d = devs[0] if devs else None
        return "%s/%s" % (getattr(d, "platform", "cpu"),
                          getattr(d, "device_kind", ""))

    def _resolve(self, phase_key, math_fn, arg_specs, tp_math=None,
                 draft=None, tables=()):
        """Persistent-cache-first compile of one phase (same order as
        Predictor._get_aot_fn: in-process shared map -> store hit ->
        fresh export+commit; direct compilation only with the store
        switched off or for a gather-mode mesh lane).  `tp_math` is the
        per-member tensor-parallel body (math_fn with a bound
        _TPContext); when set and the predictor rides a mesh, the phase
        compiles as ONE shard_map'd partitioned program instead of the
        replicate-compute gather wrap.  `draft` (fused-spec only) tells
        the spec builder how the draft's dict-shaped state is actually
        placed.  `tables`: where the slot state sits among the
        arguments (`_phase_jit` donates it)."""
        import time as _time
        import jax
        fn = self._fns.get(phase_key)
        if fn is not None:
            return fn
        with self._lock:
            fn = self._fns.get(phase_key)
            if fn is not None:
                return fn
            fn = self._resolve_locked(phase_key, math_fn, arg_specs,
                                      _time, jax, tp_math=tp_math,
                                      draft=draft, tables=tables)
            self._fns[phase_key] = fn
            return fn

    def _mesh_group(self):
        from paddle_tpu.parallel.mesh import as_mesh_group
        return as_mesh_group(self._device)

    def _tp_ctx(self):
        from paddle_tpu.parallel.mesh import MODEL_AXIS
        return _TPContext(self._tp_size, MODEL_AXIS)

    def _tp_math(self, math_fn):
        """The per-member tensor-parallel body for a phase math fn, or
        None when this predictor isn't TP-active (single device, gather
        fallback, or a model the TP grammar can't split)."""
        if not self._tp_size:
            return None
        tp = self._tp_ctx()

        def fn(state, *args):
            return math_fn(state, *args, tp=tp)
        return fn

    def _mesh_specs(self, group, state_spec, arg_specs, jax,
                    draft=None):
        """Attach the at-rest shardings to the phase's arg specs so the
        compiled executable matches what the session actually passes:
        params sharded per `param_sharding` (or `tp_param_sharding`
        when this predictor runs tensor-parallel — AOT executables are
        strict about input placement), the K/V slot tables (a mesh's
        only 4-D arguments: it holds K/V stacks alone) per
        `kv_sharding`, everything else replicated.  Dict-shaped args
        (the fused-speculative phase's DRAFT state) shard per the
        DRAFT's own placement — it rides the same mesh group as its
        target lane but may be TP-placed or gather-placed
        independently."""
        def attach(s, sh):
            return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

        def params(spec, tp):
            if tp:
                return {k: attach(v, group.tp_param_sharding(k, v.shape))
                        for k, v in spec.items()}
            return {k: attach(v, group.param_sharding(v.shape))
                    for k, v in spec.items()}

        def one(spec):
            if isinstance(spec, dict):
                return params(spec,
                              draft is not None
                              and getattr(draft, "_tp_size", 0))
            if len(spec.shape) == 4:
                return attach(spec, group.kv_sharding(spec.shape))
            return attach(spec, group.replicated())

        state_spec = params(state_spec, self._tp_size)
        return state_spec, tuple(one(s) for s in arg_specs)

    def _tp_shard_map(self, tp_math, plain_math, state_spec, arg_specs,
                      group, jax):
        """Build the partitioned program: ONE shard_map over the
        group's 1-D "model" axis running the per-member body.  Params
        enter under the TP grammar (`tp_param_pspec`), the K/V slot
        tables [L, N, S, H * Dh] (a mesh's only 4-D arguments) sharded
        by heads: the row's axis, a member's H / m heads its contiguous
        lanes (`tp_supported` guarantees heads divide, so this
        coincides with the at-rest `kv_sharding`), scalars/token tables
        replicated.  Output specs come from eval_shape of the plain
        (off-mesh) math — the TP body returns the same tree, with the
        tables and a prefill's K/V rows staying head-sharded and
        everything else fully reduced (psum/all_gather) hence
        replicated."""
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.parallel.mesh import (
            MODEL_AXIS, shard_map_no_rep_check, tp_param_pspec)

        kv_spec = P(None, None, None, MODEL_AXIS)

        def pspec_of(spec):
            if isinstance(spec, dict):
                return {k: tp_param_pspec(k, v.shape)
                        for k, v in spec.items()}
            if len(spec.shape) == 4:
                return kv_spec
            return P()

        in_specs = ({n: tp_param_pspec(n, s.shape)
                     for n, s in state_spec.items()},)
        in_specs += tuple(pspec_of(s) for s in arg_specs)
        out_shape = jax.eval_shape(plain_math, state_spec, *arg_specs)
        out_specs = jax.tree_util.tree_map(
            lambda s: kv_spec if len(s.shape) == 4 else P(), out_shape)
        return shard_map_no_rep_check(tp_math, group.mesh(),
                                      in_specs=in_specs,
                                      out_specs=out_specs)

    def _phase_jit(self, call, tables):
        """`jax.jit(call)` for a phase `call(state, *args)`, with the
        slot state among `args` (`tables`: the positions in `args` of
        its leaves) DONATED: a phase that
        takes a slot's state returns it, and the session replaces its
        own by the result (`DecodeSession._call`), so nothing reads the
        table a call was given and the call may update it in place.  It
        stays a jitted callable: `fn.lower(state, *specs).compile()` is
        the module the lane runs (the benchmark reads instruction names
        from it)."""
        import jax
        donate = tuple(1 + j for j in tables)
        if donate and self._device_kind().startswith("tpu/"):
            return jax.jit(call, donate_argnums=donate,
                           compiler_options=_TPU_PHASE_OPTIONS)
        return jax.jit(call, donate_argnums=donate)

    def _resolve_locked(self, phase_key, math_fn, arg_specs, _time, jax,
                        tp_math=None, draft=None, tables=()):
        from paddle_tpu import compile_cache as cc
        state_spec = {n: jax.ShapeDtypeStruct(np.shape(v),
                                              np.asarray(v).dtype)
                      for n, v in self._state_host.items()}
        fp_extra = None
        group = self._mesh_group()
        if group is not None:
            state_spec, arg_specs = self._mesh_specs(
                group, state_spec, arg_specs, jax, draft=draft)
            if tp_math is None:
                # gather-mode meshed phases compile directly against
                # the sharded state (no export: the replicate-compute
                # wrap is a sharding annotation, not program structure).
                # predictor._mesh_wrap keeps streams bit-exact vs a
                # single-device replica; KV outputs re-shard at rest.
                # NOT donated: the wrap gathers the table to replicated
                # and shards the result again, so no output is the
                # buffer an input was.
                from paddle_tpu.inference.predictor import _mesh_wrap
                return jax.jit(_mesh_wrap(
                    math_fn, group, kv_outputs=True)).lower(
                        state_spec, *arg_specs).compile()
            # tensor-parallel: the shard_map'd partitioned program IS
            # part of the traced module and sharded ShapeDtypeStructs
            # round-trip through jax.export — so TP phases ride the
            # persistent cache like single-device ones, with the mesh
            # shape folded into the fingerprint (warm boots of a TP
            # server deserialize the partitioned executable).  Its
            # tables stay head-sharded in and out, as at rest: donated.
            math_fn = self._tp_shard_map(tp_math, math_fn, state_spec,
                                         arg_specs, group, jax)
            fp_extra = {"mesh": list(group.shape), "tp": True}
        if cc.cache_enabled() and not (
                self._device is not None
                and self._device.platform != jax.default_backend()):
            # the EXPORT is shared by reference across clone_to replicas
            # of one device kind; the jitted call around it (donation,
            # the placement's compiler options) is each predictor's own
            skey = (self._device_kind(), phase_key)
            with self._shared_lock:
                exp = self._shared_exports.get(skey)
            if exp is None:
                from jax import export as jax_export
                cache = cc.default_cache()
                fp = self._fingerprint(phase_key, arg_specs,
                                       extra=fp_extra)
                blob = cache.get(fp) if cache is not None else None
                if blob is not None:
                    try:
                        t0 = _time.monotonic()
                        exp = jax_export.deserialize(blob)
                        cc.note_deserialize_ms(
                            (_time.monotonic() - t0) * 1000.0)
                    except Exception:
                        # a stored blob this jax cannot read back is a
                        # miss (the store's contract: corruption costs a
                        # recompile); the fresh export below still raises
                        exp = None
                if exp is None:
                    # an export failure RAISES: a phase that cannot be
                    # traced and lowered is broken, not uncacheable, and
                    # no second way of compiling it may hide that
                    t0 = _time.monotonic()
                    exp = jax_export.export(jax.jit(math_fn))(
                        state_spec, *arg_specs)
                    cc.note_compile_ms(
                        (_time.monotonic() - t0) * 1000.0)
                    if cache is not None:
                        cache.put(fp, exp.serialize())
                with self._shared_lock:
                    self._shared_exports[skey] = exp
            return self._phase_jit(exp.call, tables)
        # compile NOW (not on first call) so warm() covers the stall
        return self._phase_jit(math_fn, tables).lower(
            state_spec, *arg_specs).compile()

    def prefill_fn(self, bucket, prompts=1):
        """The prefill executable of a bucket: of one prompt (tokens [1,
        B], its length a scalar), or with `prompts` P > 1 of a group
        (tokens [P, B], lengths [P]: `_prefill_group_math`; off a mesh
        only, `prefill_width`)."""
        import jax
        bucket, P = int(bucket), int(prompts)
        i32 = np.dtype(np.int32)
        if P > 1:
            return self._resolve(
                ("prefill", bucket, P), self._prefill_group_math,
                (jax.ShapeDtypeStruct((P, bucket), i32),
                 jax.ShapeDtypeStruct((P,), i32)))
        specs = (jax.ShapeDtypeStruct((1, bucket), i32),
                 jax.ShapeDtypeStruct((), i32))
        return self._resolve(("prefill", bucket), self._prefill_math,
                             specs,
                             tp_math=self._tp_math(self._prefill_math))

    @property
    def _n_tables(self):
        """Leaves of a session's slot state (`_table_names`).  They lead
        the arguments of every phase over the slots (`_table_specs`)."""
        return len(self._table_names)

    @functools.cached_property
    def _table_names(self):
        """What the leaves of a session's slot state are, in the order
        every phase takes and returns them: those of the kinds the stack
        holds (`slot_state.KINDS`)."""
        return tuple(leaf for kind, _ in self._kinds
                     for leaf in kind.leaves)

    def _table_specs(self, n_slots):
        """(the slot state's leaves (`_table_names`), lengths [N] i32,
        last tokens [N] i32, active [N] bool): what every phase over the
        slots takes, their state first."""
        import jax
        n = int(n_slots)
        i32 = np.dtype(np.int32)
        return tuple(
            jax.ShapeDtypeStruct(shape, dtype)
            for shape, dtype in self._slot_state(n)[0].values()) + (
            jax.ShapeDtypeStruct((n,), i32),
            jax.ShapeDtypeStruct((n,), i32),
            jax.ShapeDtypeStruct((n,), np.dtype(bool)))

    def _step_specs(self, n_slots):
        """The step executable's arguments: the table's, then `budget`
        [N] i32 and `max_trips` [] i32 (`_step_math`)."""
        import jax
        i32 = np.dtype(np.int32)
        return self._table_specs(n_slots) + (
            jax.ShapeDtypeStruct((int(n_slots),), i32),
            jax.ShapeDtypeStruct((), i32))

    def step_fn(self, n_slots):
        """The decode step executable of a slot table: up to
        `STEP_WINDOW` steps a dispatch, how many being a runtime
        argument (`_step_math`).  ONE executable per slot count serves
        every round of a lane, a one-trip round and a full window
        alike.  (The window is in the phase's key, so a process that
        rebinds `STEP_WINDOW` for a sweep meets no stored phase of
        another window.)"""
        n = int(n_slots)
        tp_math = (self._step_math(tp=self._tp_ctx())
                   if self._tp_size else None)
        return self._resolve(("step", n, int(STEP_WINDOW)),
                             self._step_math(), self._step_specs(n),
                             tp_math=tp_math,
                             tables=range(self._n_tables))

    def step_logits_fn(self, n_slots):
        """The decode step with its logits left un-argmaxed (same math,
        one more compile-cache fingerprint per n_slots) — what a
        logit-level comparison against a reference reads
        (`DecodeSession.decode_logits`)."""
        # (the picks are in the phase's key: a stored executable of the
        # same artifact without them has another signature)
        return self._resolve(("step_logits", int(n_slots))
                             + (("picks",) if self._step_picks else ()),
                             self._step_logits,
                             self._table_specs(n_slots),
                             tp_math=self._tp_math(self._step_logits),
                             tables=range(self._n_tables))

    def verify_fn(self, n_slots, spec_k):
        """The speculative-verify executable for a (slot table,
        draft depth) pair: scores k+1 positions per slot in one launch.
        One new compile-cache fingerprint per (n_slots, k) — a warm
        boot of a spec-configured server deserializes it like every
        other phase (COMPILE_CACHE.md)."""
        import jax
        self._require("the speculative verify", "speculative")
        n, C = int(n_slots), int(spec_k) + 1
        cache, _, lengths, _, active = self._table_specs(n)
        specs = (cache, cache, lengths,
                 jax.ShapeDtypeStruct((n, C), np.dtype(np.int32)), active)
        return self._resolve(("verify", n, C), self._verify_math, specs,
                             tp_math=self._tp_math(self._verify_math),
                             tables=(0, 1))

    def fused_spec_fn(self, draft, n_slots, spec_k):
        """The fused speculative-round executable: k draft steps +
        batched verify + in-graph accept/rollback/catch-up as ONE
        dispatch (`_fused_spec_math`).  Keyed per (n_slots, k, draft
        identity) — the draft's model fingerprint and cache dtype ride
        the phase key, so swapping drafts can never resolve a stale
        executable."""
        import jax
        for side in (self, draft):
            side._require("the fused speculative round", "speculative")
        n, C = int(n_slots), int(spec_k) + 1
        cache, _, i32n, _, active = self._table_specs(n)
        dcache = draft._table_specs(n)[0]
        dstate = {name: jax.ShapeDtypeStruct(np.shape(v),
                                             np.asarray(v).dtype)
                  for name, v in draft._state_host.items()}
        specs = (dstate, cache, cache, i32n, i32n,
                 dcache, dcache, i32n, i32n, active)
        key = ("fused_spec", n, C, draft._model_fp[:16],
               draft._kv_dtype)
        # the fused round partitions only when BOTH sides split under
        # the TP grammar — a gather-placed draft beside a TP target
        # falls back to the replicate-compute wrap (whose specs still
        # reflect each side's actual placement via _mesh_specs)
        tp_math = (self._fused_spec_math(draft, int(spec_k),
                                         tp=self._tp_ctx())
                   if self._tp_size and getattr(draft, "_tp_size", 0)
                   else None)
        return self._resolve(key,
                             self._fused_spec_math(draft, int(spec_k)),
                             specs, tp_math=tp_math, draft=draft,
                             tables=(1, 2, 5, 6))

    def new_session(self, n_slots):
        return DecodeSession(self, n_slots)


class DecodeSession:
    """One lane's slots: their state + occupancy bookkeeping.  A slot's
    state is the leaves of the kinds its stack holds (`slot_state.KINDS`),
    each an attribute `_<leaf>` (`_kc` .. `_vw`: `slot_state.LEAVES`;
    a stack of latent attention holds ONE table of latent rows, `_kc`),
    None where the stack has none.
    Every phase that advances the slots is given all of it donated and
    the session keeps the results (`_tables`, `_keep`).
    NOT thread-safe — a serving lane owns its session exclusively (the
    decode loop is single-threaded per replica by design: the step
    function is one executable over the whole table)."""

    def __init__(self, predictor, n_slots):
        import jax
        import jax.numpy as jnp
        self.predictor = predictor
        self.n_slots = int(n_slots)
        # on one device every write to the table lands IN PLACE, the
        # table donated: a step's rows (`_phase_jit`), a slot's
        # admission, release and rollback (`_slot_writers`).  On a mesh
        # the table shards AT REST (the row's axis, by heads, first:
        # per-device resident KV ~ 1/mesh_size, which is what makes decode
        # slots scale with mesh HBM) and its sharding is the eager
        # write's to keep.
        from paddle_tpu.parallel.mesh import as_mesh_group
        group = as_mesh_group(predictor.device) \
            if predictor.device is not None else None
        self._inplace = group is None

        def table(shape, dtype):
            z = jnp.zeros(shape, dtype)
            if group is not None:
                return jax.device_put(z, group.kv_sharding(shape))
            # COMMITTED to its device from the start, under the default
            # placement too (to the device jax chose).  A phase's K/V
            # come back committed, so a fresh table would be committed
            # by its first admission, and a jitted write is lowered
            # anew when a table's commitment differs from what it has
            # seen: the lane's first prefill of every bucket but the one
            # `ModelEntry.warm` happened to run first, and the second of
            # that one, compiled under traffic
            return jax.device_put(
                z, predictor.device or next(iter(z.devices())))

        # a buffer a leaf: a donated K table must not take V's with it.
        # The cache's leaves allocate at the predictor's kv_cache_dtype
        # width: int8 slot tables hold exact int8 zeros when free
        # (QUANTIZE.md "Quantized KV cache" — the zero-slot contract is
        # dtype-blind)
        leaves = predictor._slot_state(self.n_slots)[0]
        for leaf in slot_state.LEAVES:
            setattr(self, "_" + leaf,
                    table(*leaves[leaf]) if leaf in leaves else None)
        # what a stack's fetch spans say of it
        self._stack_attrs = slot_state.stack_attrs(
            predictor._kinds, lambda kind: self._kind_bytes(name=kind),
            self._kind_lanes)
        if predictor._block_meta["experts_held"]:
            self._stack_attrs["moe_experts_held"] = \
                predictor._block_meta["experts_held"][1]
        if "linear_attention" in predictor._block_meta["layer_types"]:
            self._stack_attrs["linear_layers"] = predictor._block_meta[
                "layer_types"].count("linear_attention")
        if self._ss is not None:
            # what a STEP's fetch span says of its recurrence: the one-pass
            # Mosaic call, or XLA's form where the TPU's tiles do not hold
            # a head's state whole
            from paddle_tpu.ops.pallas_kernels import ssm_update_block_heads
            self._ssm_update = "pallas" if ssm_update_block_heads(
                *self._ss.shape[2:]) else "xla"
        # the decode kernel's block edge over the table of rows a position,
        # as the step's trace resolves it (None: no edge divides S, the
        # step attends through the plain-XLA reference, which reads whole
        # rows)
        from paddle_tpu.ops import attention_tuning
        shape = self._kc.shape
        self._kv_block = attention_tuning.get_decode_config(
            shape[2], shape[-1] if predictor.latent
            else predictor._dims()[2], self._kc.dtype.name)
        # ... and over a ring, which is that many rows long
        self._ring_block = None if self._kw is None \
            else attention_tuning.get_decode_config(
                self._kw.shape[2], predictor._dims()[2], "float32")
        # what a block of each kind of table weighs in `_kv_stream`'s count:
        # its K and V rows' lanes over the stack's common measure of them
        lanes = {kind.name: sum(self._kind_lanes(kind))
                 for kind, _ in predictor._kinds if kind.live}
        unit = int(np.gcd.reduce(list(lanes.values()) or [1]))
        self._block_weight = {name: n // unit for name, n in lanes.items()}
        # set when a call failed after its table was donated to it
        # (`_mark_dead`): (phase, error); every later use raises
        self._dead = None
        # each slot's index as a device scalar: a write's `slot` is then
        # no upload (a jitted call handed a numpy value waits for its
        # copy to land: PERF.md, PR 24)
        self._slot_ids = [
            jnp.asarray(np.int32(i)) if predictor.device is None
            else jax.device_put(np.int32(i), predictor.device)
            for i in range(self.n_slots)] if self._inplace else None
        self.lengths = np.zeros(self.n_slots, np.int32)
        self.last_tokens = np.zeros(self.n_slots, np.int32)
        self.active = np.zeros(self.n_slots, bool)
        self.steps = 0
        # a routed-expert artifact: the newest step's or prefill's
        # (experts touched, most tokens on one expert) of each routed
        # layer [routed layers, 2]
        self.last_routing = None
        self.last_pairs_held = None
        # a hybrid routed stack's newest `decode_logits`: the experts
        # each routed layer chose [routed layers, n_slots, k]
        self.last_picks = None
        self.last_prefill_picks = None
        self._n_routed = predictor.routed_layers
        self._n_facts = predictor._routing_facts
        # the `decode/put` / `decode/launch` spans of a call whose
        # results are not fetched yet (`_call`, `_fetch`)
        self._launched = ()
        # the step dispatch `launch_fused` left for `fetch_fused`: (its
        # result vector, still on the device; the steps it was asked)
        self._in_flight = None
        # the prefills `launch_prefill` left for `fetch_prefill`, oldest
        # first: (slot, or a group's slots as a tuple; first-token vector
        # still on the device; the prompts' lengths; the span facts
        # `scanned`; the call's `_launched`)
        self._prefills = collections.deque()

    # -- occupancy ------------------------------------------------------

    def free_slots(self):
        """The slots no stream holds and no unfetched prefill is landing
        in (`launch_prefill` reserves its slot)."""
        landing = {s for p in self._prefills for s in np.atleast_1d(p[0])}
        return [i for i in range(self.n_slots)
                if not self.active[i] and i not in landing]

    def occupancy(self):
        return int(self.active.sum())

    def _kind_bytes(self, **where):
        """MEASURED bytes (the device arrays' nbytes: rows as the table
        holds them, their padding to the tile included) of the kinds whose
        record says `where` (one field of `slot_state.Kind`: name="ring",
        total="kv_cache_bytes"); 0 where the stack holds none."""
        (field, value), = where.items()
        return sum(int(getattr(self, "_" + leaf).nbytes)
                   for kind, _ in self.predictor._kinds
                   if getattr(kind, field) == value for leaf in kind.leaves)

    def _kind_lanes(self, kind):
        """(lanes of a row of the kind's first leaf, of its last): a K
        row's and a V row's of a kind that holds both."""
        return tuple(int(getattr(self, "_" + leaf).shape[-1])
                     for leaf in (kind.leaves[0], kind.leaves[-1]))

    def cache_bytes(self):
        """MEASURED footprint of the slot state that bounds the slots:
        every kind counted in `kv_cache_bytes` plus the int8 cache's fp32
        scale table — what bench_serving's --kv_dtype A/B reports against
        the closed-form `GenerativePredictor.kv_cache_bytes`.  The conv
        layers' state is `conv_state_bytes`, apart."""
        n = self._kind_bytes(total="kv_cache_bytes")
        if self.predictor._kv_quant:
            n += int(np.asarray(self.predictor._kv_scales).nbytes)
        return n

    def conv_state_bytes(self):
        """MEASURED footprint of the conv layers' slot state (0 for a
        stack with none)."""
        return self._kind_bytes(total="conv_state_bytes")

    def ssm_state_bytes(self):
        """MEASURED footprint of the scanned state (0 for a stack with no
        attention+ssm layer)."""
        return self._kind_bytes(name="ssm")

    def window_kv_bytes(self):
        """MEASURED footprint of the window_attention layers' K and V
        rings (0 for a stack with none): what they RESERVE, which is what
        they hold once a slot is `sliding_window` positions long."""
        return self._kind_bytes(name="ring")

    def kv_live_bytes(self):
        """{"full": bytes, "window": bytes}: the K and V rows the active
        slots hold NOW, by kind of table (`slot_state.KINDS`' `live`): a
        full layer's table `lengths` rows a slot, a window layer's ring
        min(lengths, sliding_window); beside what the tables reserve
        (`cache_bytes`, `window_kv_bytes`)."""
        held = np.where(self.active, self.lengths, 0).astype(np.int64)
        out = {"full": 0, "window": 0}
        for kind, _ in self.predictor._kinds:
            for t in (getattr(self, "_" + leaf) for leaf in kind.leaves
                      if kind.live):
                rows = held if kind.by_length \
                    else np.minimum(held, t.shape[2])
                out[kind.live] += int(rows.sum() * t.shape[0] * t.shape[3]
                                      * t.dtype.itemsize)
        return out

    def _tables(self):
        """The slots' state as the phases take it, the leaves of
        `GenerativePredictor._table_names`."""
        return tuple(getattr(self, "_" + leaf)
                     for leaf in self.predictor._table_names)

    def _keep(self, tables):
        """Replace the slots' state by a phase's results."""
        for leaf, t in zip(self.predictor._table_names, tables):
            setattr(self, "_" + leaf, t)

    # -- phases ---------------------------------------------------------

    def _put(self, arr):
        if self.predictor.device is not None:
            from paddle_tpu.inference.predictor import _put_feed
            return _put_feed(arr, self.predictor.device)
        return arr

    def _alive(self):
        if self._dead is not None:
            raise DecodeSessionDead(
                "this decode session lost its slot table: its %s call "
                "failed after the table had been donated to it (%s); "
                "every stream in it is gone, open a new session"
                % self._dead)

    def _call(self, phase, fn, cache, small):
        """`fn(state, *cache, *small)`, with its `decode/put` and
        `decode/launch` spans when tracing is on (stamped once the
        call's results are fetched: `_fetch`): `_put` of the small
        per-call arguments, then the executable call until it returns
        (synchronous for whatever host argument it has to upload;
        `h2d_bytes` counts those: the weights and the cache are
        device-resident under every placement, so under the default one
        it is the small arguments `_put` leaves as numpy).  `cache` is
        DONATED to the call wherever the placement allows
        (`_phase_jit`; not on a gather-mode mesh): the caller replaces
        `_kc`/`_vc` by the results.  A call that raises after consuming
        them leaves this session dead (`_mark_dead`).  A call that takes
        the tables (a step of any form) is refused while a prefill is
        unfetched: its slot is reserved, not active yet."""
        self._alive()
        if cache and self._prefills:
            raise RuntimeError("a prefill is not fetched yet: fetch_prefill "
                               "comes before a step")
        state = self.predictor._state
        try:
            if not obs_tracing.enabled():
                return fn(state, *cache,
                          *[self._put(a) for a in small])
            t0 = time.monotonic()
            args = [self._put(a) for a in small]
            t1 = time.monotonic()
            out = fn(state, *cache, *args)
            t2 = time.monotonic()
        except BaseException as e:
            _mark_dead(phase, e, self)
            raise
        # stamped by the `_fetch` that ends this call, which knows how
        # many trips a step's dispatch ran
        self._launched = (
            ("decode/put", t0, t1, {}),
            ("decode/launch", t1, t2,
             {"h2d_bytes": self.predictor.state_host_bytes()
              + _host_nbytes(cache) + _host_nbytes(args)}))
        return out

    def _write_slot(self, slot, rows=None, start=0, n=None):
        """THE eager write of a slot's state, every leaf the session
        holds: a prefill's `rows` ([L, 1, ..] a leaf) from position 0;
        zeros over the slot's whole state (its release); or zeros over
        `n` positions from `start` (a rollback).  On one device through
        the donated writers, in place (`_slot_writers`); on a mesh
        undonated, the tables keeping their sharding."""
        tables = self._tables()
        if self._inplace:
            write_rows, zero_slot, clear_rows = _slot_writers()
            if isinstance(slot, tuple):
                # a group's prefill: its members' rows, a slot each, the
                # dead rows behind them dropped
                at = np.zeros(rows[0].shape[0], np.int32)
                at[:len(slot)] = slot
                return self._keep(write_rows(tables, tuple(rows), at,
                                             np.int32(len(slot))))
            at = self._slot_ids[slot]
            if rows is not None:
                return self._keep(write_rows(tables, tuple(rows), at))
            if n is None:
                return self._keep(zero_slot(tables, at))
            mine = np.arange(self.n_slots) == slot
            lo = np.where(mine, start, 0).astype(np.int32)
            hi = np.where(mine, start + n, 0).astype(np.int32)
            return self._keep([clear_rows(t, lo, hi, n) for t in tables])
        import jax.lax
        import jax.numpy as jnp
        if rows is None:
            rows = [self._put(jnp.zeros(
                (t.shape[0], 1, n or t.shape[2]) + t.shape[3:], t.dtype))
                for t in tables]
        self._keep([jax.lax.dynamic_update_slice(
            t, r, (0, int(slot), start) + (0,) * (t.ndim - 3))
            for t, r in zip(tables, rows)])

    def prefill(self, slot, tokens):
        """Run the prompt through the bucketed prefill, land its K/V
        (and the conv layers' state at the prompt's end) in `slot`, and
        return the first generated token (greedy).  The slot must be
        free (and therefore zeroed).  It is `launch_prefill` followed at
        once by `fetch_prefill`: a caller with another prompt to queue
        while the device runs this one calls the two apart."""
        self.launch_prefill(slot, tokens)
        return self.fetch_prefill()

    def launch_prefill(self, slot, tokens):
        """The first half of `prefill`: the executable call and the
        landing of its rows in `slot`, both queued on the device when
        this returns (`_call`, `_write_slot`).  The slot is RESERVED
        (`free_slots` leaves it out) and becomes active with
        `fetch_prefill`, which takes the launched prefills oldest first.
        A prefill reads no table and its landing chains on the tables by
        donation, in program order, so another may be launched behind
        one that is not fetched yet; a step may not (`_call`).  Returns
        whether it was: True where an earlier prefill was still
        unfetched when this one was queued.

        A GROUP: `slot` a list of free slots and `tokens` as many prompts
        of ONE bucket, 2 to `prefill_width(bucket)` of them.  They run as
        one call of the bucket's group executable (`prefill_fn(bucket,
        width)`: the weights and the head read once for all of them), a
        short group padded with dead rows of one pad token whose results
        are dropped, and land in one write; `fetch_prefill` then brings the
        group's first tokens in one copy."""
        from paddle_tpu.parallel.mesh import check_member_poison
        check_member_poison(self.predictor.device)
        group = isinstance(slot, (list, tuple))
        slots = tuple(int(i) for i in slot) if group else (slot,)
        free = self.free_slots()
        for i in slots:
            if i not in free:
                raise ValueError("slot %d is occupied" % i)
        if self._in_flight is not None:
            raise RuntimeError("a step dispatch is in flight: fetch_fused "
                               "comes before a prefill")
        prompts = [np.asarray(t, np.int32).reshape(-1)
                   for t in (tokens if group else [tokens])]
        lens = [t.size for t in prompts]
        if min(lens) < 1:
            raise ValueError("empty prompt")
        bucket = self.predictor.prompt_bucket(lens[0])
        width = self.predictor.prefill_width(bucket) if group else 1
        if group and not (2 <= len(slots) == len(set(slots)) == len(prompts)
                          <= width and all(self.predictor.prompt_bucket(n)
                                           == bucket for n in lens)):
            raise ValueError(
                "a group is 2 to %d prompts of one bucket, a free slot "
                "each: got %d prompts of %s tokens for slots %s"
                % (width, len(prompts), lens, list(slots)))
        padded = np.zeros((width, bucket), np.int32)
        for row, t in zip(padded, prompts):
            row[:t.size] = t
        fn = self.predictor.prefill_fn(bucket, width)
        first, *new = self._call("prefill", fn, (), (
            padded, np.int32(lens + [1] * (width - len(lens))) if group
            else np.int32(lens[0])))
        # this call's `decode/put` / `decode/launch` stamps wait for its
        # own fetch: the next launch must not take their place
        launched, self._launched = self._launched, ()
        # land the bucket-length rows (and the state of a fixed size) at
        # the slot; positions past the bucket are already zero (the slot
        # was zeroed on free)
        self._write_slot(slots if group else slot, new)
        # a scanning stack's prefill spans say what was scanned
        scanned = {}
        if self._ss is not None:
            chunk = min(self.predictor._block_meta["ssm_chunk"], int(bucket))
            scanned = {"bucket": int(bucket),
                       "ssm_chunks": -(-int(bucket) // chunk)}
        chunks = self.predictor.prefill_chunks(lens[0])
        if chunks:
            scanned["chunks"] = chunks
        if group:
            scanned["prompts"] = len(slots)
        behind = bool(self._prefills)
        self._prefills.append((slots if group else slot, first, lens,
                               scanned, launched))
        return behind

    def fetch_prefill(self):
        """The second half of `prefill`, for the OLDEST prefill
        `launch_prefill` left unfetched: the wait for the device and the
        copy of its first token (`_fetch`), then the slot's length, last
        token and occupancy.  Returns the token; of a group the list of
        its first tokens, a prompt each (`last_routing` is then [prompts,
        routed layers, 2], a prompt's own facts a row).  A stack with
        routed FFNs behind state-space layers keeps the experts each
        routed layer chose at every position of the bucket as
        `last_prefill_picks`."""
        if not self._prefills:
            raise RuntimeError("no prefill in flight: launch_prefill "
                               "comes first")
        slot, first, lens, scanned, self._launched = self._prefills.popleft()
        group = isinstance(slot, tuple)
        first = self._fetch("prefill", first, routed=True, more=scanned)[0]
        # a row a prompt (a group's dead rows behind them): its token first
        first = first.reshape(first.shape[0] if group else 1, -1)
        if self._ki is not None:
            # [sparse layers, K/V heads, k]: `_prefill_core`
            self.last_prefill_picks = first[0, 1:].reshape(
                self._ki.shape[0], self.predictor._kv_heads(), -1)
        slots = slot if group else (slot,)
        if self.predictor._prefill_picks:
            # [routed layers, bucket, k], a prompt's own ([prompts, ..] of
            # a group): `GenerativePredictor._prefill_picks`
            picks = first[:len(slots), 1:].reshape(
                len(slots), self._n_routed, -1,
                self.predictor._block_meta["experts_per_token"])
            self.last_prefill_picks = picks if group else picks[0]
        if group and self.last_routing is not None:
            self.last_routing = self.last_routing[:len(slots)]
            self.last_pairs_held = self.last_pairs_held[:len(slots)]
        toks = [int(t) for t in first[:len(slots), 0]]
        for i, n, tok in zip(slots, lens, toks):
            self.lengths[i] = n
            self.last_tokens[i] = tok
            self.active[i] = True
        return toks if group else toks[0]

    def decode(self):
        """ONE fixed-shape step over the whole slot table: a one-trip
        dispatch of the step executable.  Returns the np.int32
        [n_slots] token vector (only entries of slots active at call
        time are meaningful).  Bumps each active slot's length and last
        token."""
        return self.decode_fused(1)[0][:, 0]

    def decode_logits(self):
        """One step that hands back its logits: returns (tokens
        [n_slots] i32, logits [n_slots, vocab] f32), through an
        executable of its own (`step_logits_fn`: the step's `_step_core`,
        no window around it).  For a stack with conv layers and routed
        FFNs the step's chosen experts [routed layers, n_slots, k] are
        kept as `last_picks`.  A caller comparing two predictors on one
        stream overwrites `last_tokens` afterwards (teacher forcing), as
        the speculative session does for its draft."""
        from paddle_tpu.parallel.mesh import check_member_poison
        check_member_poison(self.predictor.device)
        logits, *tables = self._call(
            "step", self.predictor.step_logits_fn(self.n_slots),
            self._tables(), (self.lengths, self.last_tokens, self.active))
        if self.predictor._step_picks:
            picks, *tables = tables
            self.last_picks = np.asarray(picks)
        self._keep(tables)
        logits, = self._fetch("step", logits)
        toks = logits.argmax(axis=-1).astype(np.int32)
        act = self.active
        self.lengths = self.lengths + act.astype(np.int32)
        self.last_tokens = np.where(act, toks, self.last_tokens).astype(
            np.int32)
        self.steps += 1
        return toks, logits

    def _kv_stream(self, counts, trips):
        """What the decode kernel staged in a step dispatch of `trips`
        trips of which slot n ran its own first `counts[n]`, by the
        kernel's own rule (`pallas_kernels.kv_last_block`): trip t
        attends slot n under `lengths[n] + t + 1` positions while it
        runs and under one (a block, all masked) once it has stopped,
        as a slot that never ran does (`_step_math`), in every attention
        layer.  `kv_blocks_live` are the K/V blocks staged,
        `kv_blocks_total` what whole rows would be (trips x slots x
        layers x S / block); a window layer's call counts among both, a
        ring being its whole row.  A block counts its K and its V tile AT
        THEIR OWN WIDTHS: a kind's block weighs its two rows' lanes over
        the stack's common measure of those sums (`_block_weight`), which
        is 1 where every table's rows are one width and makes the share
        one of bytes where a ring's rows are not a full table's."""
        trip = np.arange(trips)[:, None]
        if self._ki is not None:
            return self._sparse_stream(
                np.where(trip < counts[None], self.lengths[None] + trip + 1,
                         0), trips)
        if not self._kv_block:
            return {}
        from paddle_tpu.ops.pallas_kernels import kv_last_block
        seen = np.where(trip < counts[None], self.lengths[None] + trip, 0) + 1
        out = {"kv_blocks_live": 0, "kv_blocks_total": 0}
        edges = {"full": self._kv_block, "window": self._ring_block}
        for kind, layers in self.predictor._kinds:
            block = kind.live and edges[kind.live]
            if not block:
                continue
            layers = layers * self._block_weight[kind.name]
            # a table addressed by the slot's length under the positions
            # themselves, a ring under min(positions, its rows)
            rows = getattr(self, "_" + kind.leaves[0]).shape[2]
            n_blocks = rows // block
            live = kv_last_block(
                seen if kind.by_length else np.minimum(seen, rows), block,
                n_blocks) + 1
            out["kv_blocks_live"] += int(live.sum()) * layers
            out["kv_blocks_total"] += trips * self.n_slots * layers * n_blocks
        return out

    def _sparse_stream(self, seen, trips):
        """`_kv_stream` of a stack with sparse_attention layers, whose
        kernel stages the SELECTED blocks: `seen` [trips, N] the positions
        a slot attends under in a trip (0: it does not run, and is not
        visited).  A running slot's K/V head stages min(sparse_topk, the
        blocks in sight) tiles of `sparse_block` rows in each sparse layer
        (`kv_blocks_live`, of `kv_blocks_total` = every block of every
        slot's row); `selected_blocks` is that count a trip,
        `selected_rows` / `rows_in_sight` the positions attended over those
        a dense layer would read (the selection always holds the slot's own
        last block, the only partial one).  `kv_grid_steps` are the grid
        steps the kernel staged them in, T tiles a step
        (`pallas_kernels.sparse_tiles_per_step`): ceil(chosen / T) a
        running slot's K/V head a sparse layer a trip."""
        from paddle_tpu.ops.pallas_kernels import sparse_tiles_per_step
        blk = self.predictor._block_meta
        block, topk = blk["sparse_block"], blk["sparse_topk"]
        heads = self.predictor._kv_heads() * self._ki.shape[0]
        in_sight = -(-seen // block)
        chosen = np.minimum(in_sight, topk)
        rows = np.where(in_sight <= topk, seen,
                        (topk - 1) * block + seen - (in_sight - 1) * block)
        per_step = sparse_tiles_per_step(
            min(topk, self._kc.shape[2] // block), block,
            self._kc.shape[3] // self.predictor._kv_heads(),
            self._kc.dtype.itemsize)
        return {"kv_blocks_live": int(chosen.sum()) * heads,
                "kv_grid_steps": int((-(-chosen // per_step)).sum()) * heads,
                "kv_blocks_total": trips * self.n_slots * heads
                * (self._kc.shape[2] // block),
                "selected_blocks": int(chosen.sum()) * heads // max(trips, 1),
                "selected_rows": int(rows.sum()),
                "rows_in_sight": int(seen.sum())}

    def _fetch(self, phase, *outs, routed=False, trips_at=None, more=None):
        """`np.asarray` of each result: the wait for the device and the
        copy to the host, under one `decode/fetch` span; the call's
        `decode/put` and `decode/launch` spans (`_call`) are stamped
        here too.  `routed` marks a step's or a prefill's result: for a
        routed-expert artifact the call's routing facts sit behind its
        tokens in that one vector (`_pack_routing`, `_step_math`) and
        are split off here, kept as `last_routing` (and `last_pairs_held`)
        and given to the fetch span as `moe_experts_touched` and
        `moe_pairs_held` (the live pairs that stayed on this member; both
        summed over the layers and a step's trips),
        `moe_tokens_per_expert_max` and, of a member that holds some of
        the experts, `moe_cap_overflows` (the (layer, trip) calls whose
        routing kept more pairs here than `held_cap` rows and ran the
        grouped matmuls full size).  `trips_at`
        is where a step's vector holds the trips it ran, behind each
        slot's emitted count: all three spans carry them as `trips`,
        and the fetch span what the decode kernel streamed in them
        (`_kv_stream`: `kv_blocks_live`, `kv_blocks_total`).  The fetch
        span of a step or a prefill says what kinds of slot state the
        stack holds, their layers and the bytes they RESERVE
        (`_stack_attrs`: each kind's `attrs` in `slot_state.KINDS`); one
        with attention+ssm layers a step's `ssm_update` too ("pallas":
        the recurrence is the one-pass kernel `pallas_kernels.ssm_update`;
        "xla": its reference), and `more` (its prefill's `bucket` and
        `ssm_chunks`, the chunks scanned) goes on the call's launch and
        fetch spans; one with window_attention layers what the active
        slots HOLD of the two kinds of K/V table as the call begins
        (`full_kv_live_bytes`, `window_kv_live_bytes`: `kv_live_bytes`).
        Any other artifact takes the path it always took."""
        width = self._n_facts
        n_routed = width * self._n_routed if routed else 0
        if not (n_routed or obs_tracing.enabled()):
            return [np.asarray(o) for o in outs]
        t0 = time.monotonic()
        got = [np.asarray(o) for o in outs]
        d2h, attrs = _nbytes(got), {}
        if n_routed:
            # (a group's prefill: a row of them a prompt, [P, ..])
            facts = got[0][..., -n_routed:].reshape(
                got[0].shape[:-1] + (-1, width))
            got[0] = got[0][..., :-n_routed]
            self.last_routing = facts[..., :2]
            self.last_pairs_held = facts[..., 2]
            attrs = {"moe_experts_touched": int(facts[..., 0].sum()),
                     "moe_tokens_per_expert_max": int(facts[..., 1].max()),
                     "moe_pairs_held": int(facts[..., 2].sum())}
            if width == 4:
                # (a group's prefill: the call's own, on every prompt's row)
                attrs["moe_cap_overflows"] = int(
                    facts[..., 3].reshape(-1, self._n_routed).max(0).sum())
        if routed:
            attrs.update(self._stack_attrs)
            if phase == "step" and self._ss is not None:
                attrs["ssm_update"] = self._ssm_update
        if obs_tracing.enabled():
            t1 = time.monotonic()
            if routed and self._kw is not None:
                held = self.kv_live_bytes()
                attrs.update(full_kv_live_bytes=held["full"],
                             window_kv_live_bytes=held["window"])
            trips = {}
            if trips_at is not None:
                trips = {"trips": int(got[0][trips_at])}
                attrs.update(self._kv_stream(
                    got[0][trips_at - self.n_slots:trips_at],
                    trips["trips"]))
            launched, self._launched = self._launched, ()
            more = more or {}
            for name, a, b, own in launched:
                obs_tracing.stamp(name, a, b, kind="serving", phase=phase,
                                  **own, **trips,
                                  **(more if name == "decode/launch" else {}))
            obs_tracing.stamp("decode/fetch", t0, t1, kind="serving",
                              phase=phase, d2h_bytes=d2h, **attrs, **trips,
                              **more)
        return got

    def decode_fused(self, n_steps, budget=None, max_trips=None):
        """Up to `n_steps` decode steps (at most `STEP_WINDOW`) in ONE
        dispatch of the step executable (SERVING.md "Fused multi-step
        decode").  Returns (tokens [n_slots, n_steps] int32, counts
        [n_slots] int32, trips int): slot s emitted `counts[s]` tokens
        this dispatch, `tokens[s, :counts[s]]` in stream order; `trips`
        is how many loop iterations ran.  A slot stops with the trip in
        which it emits EOS, meets its budget or fills its cache and
        sits out the trips left; the window ends with the trip in which
        the last running slot stops (`_step_math`), so `counts[s]` is
        the slot's own count, at most `trips`, and a caller that wants
        more calls again.  `budget` [n_slots] caps each slot's
        emissions (max_new / cache-room headroom; clipped to [0,
        n_steps], zero for inactive slots); `max_trips` clamps the
        whole dispatch.  Both are runtime arguments of the slot table's
        one executable.  Token for token the stream of one-trip
        dispatches: per-slot math is independent and every trip is the
        same `_step_core`.  It is `launch_fused` followed at once by
        `fetch_fused`: a caller with host work to do while the device
        runs the window calls the two apart."""
        self.launch_fused(n_steps, budget=budget, max_trips=max_trips)
        return self.fetch_fused()

    def launch_fused(self, n_steps, budget=None, max_trips=None):
        """The first half of `decode_fused`: the executable call, which
        returns once the dispatch is in the device's queue (`_call`).
        The slot tables are the call's results from here on; `lengths`
        and `last_tokens` stay as they were until `fetch_fused`, which
        must come before any other use of the session."""
        N, W, act = self.n_slots, int(STEP_WINDOW), self.active
        T = min(int(n_steps), W)
        if T < 1:
            raise ValueError("n_steps must be >= 1, got %d" % T)
        if self._in_flight is not None:
            raise RuntimeError("a step dispatch is in flight: fetch_fused "
                               "comes before the next launch_fused")
        self._alive()
        from paddle_tpu.parallel.mesh import check_member_poison
        check_member_poison(self.predictor.device)
        if budget is None:
            b = np.where(act, T, 0).astype(np.int32)
        else:
            b = np.asarray(budget, np.int32).reshape(N)
            b = np.clip(np.where(act, b, 0), 0, T).astype(np.int32)
        mt = T if max_trips is None else max(1, min(int(max_trips), T))
        out, *tables = self._call(
            "step", self.predictor.step_fn(N), self._tables(),
            (self.lengths, self.last_tokens, act, b, np.int32(mt)))
        self._keep(tables)
        self._in_flight = (out, T)

    def fetch_fused(self):
        """The second half of `decode_fused`: the wait for the dispatch
        `launch_fused` left in flight and the copy of its one small
        vector (`_fetch`), then the slots' lengths and last tokens from
        what it returned.  Returns what `decode_fused` returns."""
        if self._in_flight is None:
            raise RuntimeError("no step dispatch in flight: launch_fused "
                               "comes first")
        (out, T), self._in_flight = self._in_flight, None
        N, W = self.n_slots, int(STEP_WINDOW)
        out, = self._fetch("step", out, routed=True, trips_at=N * W + N)
        toks = out[:N * W].reshape(N, W)[:, :T]
        counts, trips = out[N * W:N * W + N], int(out[N * W + N])
        # what the loop carried, from what it returned: a slot advanced
        # by the tokens it emitted and holds the last of them
        self.lengths = (self.lengths + counts).astype(np.int32)
        self.last_tokens = np.where(
            counts > 0, toks[np.arange(N), np.maximum(counts - 1, 0)],
            self.last_tokens).astype(np.int32)
        self.steps += trips
        return toks, counts, trips

    def room(self, slot):
        """Generated tokens this slot can still hold (cache positions
        left)."""
        return int(self.predictor.max_seq_len - self.lengths[slot])

    def free(self, slot):
        """Release a slot: its state of EVERY kind is ZEROED before it
        can be reused — a later occupant starts from exact zeros, never
        from a previous request's keys or inputs (the no-leakage contract
        the chaos decode-disconnect scenario pins)."""
        self._alive()
        self._write_slot(slot)
        self.lengths[slot] = 0
        self.last_tokens[slot] = 0
        self.active[slot] = False

    def rollback(self, slot, n, last_token=None):
        """Roll `slot` back by `n` cached positions: the length pointer
        retreats and the rolled-back KV rows are ZEROED, so the slot is
        bit-identical to one that never advanced past the restored
        length (pinned by tests/test_spec_decode.py).  `last_token`,
        when given, restores the slot's pending token alongside — a
        full rewind needs both, since the pending token is the one
        committed token whose K/V is not in the cache yet.

        The speculative decoder's draft-side sync is built on this: a
        partially-accepted round rolls the draft's rejected rows back
        and re-pins its pending token to the target's correction."""
        self.predictor._require("a rollback", "rollback")
        slot, n = int(slot), int(n)
        if n < 0:
            raise ValueError("rollback of %d positions" % n)
        length = int(self.lengths[slot])
        if n > length:
            raise ValueError(
                "rollback of %d positions on slot %d with only %d "
                "cached" % (n, slot, length))
        self._alive()
        if n > 0:
            # (rows of positions all: any other kind is refused above)
            self._write_slot(slot, start=length - n, n=n)
            self.lengths[slot] = length - n
        if last_token is not None:
            self.last_tokens[slot] = np.int32(last_token)

    def slot_is_zero(self, slot):
        """True when the slot's state of every kind is exact zeros — the
        test hook for the zero-before-reuse contract."""
        self._alive()
        return not any(np.asarray(t[:, slot]).any()
                       for t in self._tables())


class SpeculativeDecodeSession:
    """Draft-and-verify generation over one slot table (SERVING.md
    "Speculative decoding"): pairs the fp32 *target* predictor with a
    cheap *draft* predictor (the int8 twin of the same artifact, or any
    decode artifact sharing its vocab/eos) and advances every occupied
    slot 1..k+1 committed tokens per round:

      1. DRAFT: k batched draft decode steps propose d1..dk per slot
         (the draft keeps its own KV slot table, mirroring the
         committed stream);
      2. VERIFY: the target scores all k+1 positions in ONE fixed-shape
         batched step (`GenerativePredictor.verify_fn`) — acceptance
         and stale-row zeroing happen in-graph;
      3. COMMIT: the longest greedily-agreeing prefix (plus the
         target's correction/bonus token) commits to the target cache;
         the draft rolls its rejected rows back (`DecodeSession.
         rollback`) — or runs one catch-up step after a fully-accepted
         round — so both tables mirror the committed stream again.

    Every committed token is a TARGET argmax, so the stream is
    bit-identical to target-only plain decode; the draft only ever
    changes how many steps that stream costs.  Any draft failure
    (`set_draft_poison`, a dead predictor, an incompatible state)
    degrades the session to target-only plain rounds within the same
    step — `degraded` latches, the stream never stalls or corrupts.

    Duck-types the DecodeSession surface the DecodeBatcher drives
    (prefill/free/room/free_slots/occupancy/decode), plus `step()` —
    the variable-accept round returning (tokens [N, k+1], counts [N]).
    NOT thread-safe, same single-owner contract as DecodeSession."""

    def __init__(self, target, draft, n_slots, spec_k):
        if int(spec_k) < 1:
            raise ValueError("spec_k must be >= 1, got %r" % (spec_k,))
        for side in (target, draft):
            side._require("speculative decoding", "speculative")
        if draft.vocab_size != target.vocab_size:
            raise ValueError(
                "draft vocab %d != target vocab %d — not a compatible "
                "draft artifact" % (draft.vocab_size, target.vocab_size))
        if draft.eos_id != target.eos_id:
            raise ValueError(
                "draft eos_id %d != target eos_id %d"
                % (draft.eos_id, target.eos_id))
        if draft.max_seq_len < target.max_seq_len:
            raise ValueError(
                "draft max_seq_len %d < target max_seq_len %d — the "
                "draft cache cannot mirror the committed stream"
                % (draft.max_seq_len, target.max_seq_len))
        self.predictor = target
        self.draft_predictor = draft
        self.spec_k = int(spec_k)
        self.n_slots = int(n_slots)
        self.session = target.new_session(n_slots)
        self.draft_session = draft.new_session(n_slots)
        self._degraded = False
        self.degrade_error = None
        # accept telemetry the serving layer rolls up per round
        self.rounds = 0          # verify launches
        self.plain_steps = 0     # fallback/degraded plain rounds
        self.proposed = 0        # draft tokens offered to verify
        self.accepted = 0        # draft tokens accepted
        self.last_spec = False   # did the latest round verify?
        self.last_draft_end = None   # monotonic draft->verify boundary
        # first tokens `launch_prefill` keeps for `fetch_prefill`
        self._firsts = collections.deque()

    # -- DecodeSession surface (the batcher's contract) -----------------

    @property
    def steps(self):
        return self.session.steps

    @property
    def degraded(self):
        return self._degraded

    def free_slots(self):
        return self.session.free_slots()

    def occupancy(self):
        return self.session.occupancy()

    def room(self, slot):
        return self.session.room(slot)

    def slot_is_zero(self, slot):
        return self.session.slot_is_zero(slot)

    def _degrade(self, exc):
        self._degraded = True
        if self.degrade_error is None:
            self.degrade_error = "%s: %s" % (type(exc).__name__, exc)

    def prefill(self, slot, tokens):
        """Prefill BOTH tables; the draft's own first-token prediction
        is discarded — its pending token is re-pinned to the target's
        (the committed stream is always the target's)."""
        self.launch_prefill(slot, tokens)
        return self.fetch_prefill()

    def launch_prefill(self, slot, tokens):
        """`DecodeSession.launch_prefill`'s name for the lane's one
        admission path: the whole of `prefill` (the draft's pending
        token is the target's first, which must be on the host), the
        token kept for `fetch_prefill`.  The lane launches prompt i+1
        before it takes prompt i's token, so two may be kept.  Returns
        False: nothing is left on the device, so nothing was queued
        behind anything."""
        first = self.session.prefill(slot, tokens)
        if not self._degraded:
            try:
                _check_draft_poison()
                self.draft_session.prefill(slot, tokens)
                self.draft_session.last_tokens[slot] = np.int32(first)
            except BaseException as e:
                self._degrade(e)
        self._firsts.append(first)
        return False

    def fetch_prefill(self):
        """The first token of the oldest `launch_prefill` not taken yet."""
        if not self._firsts:
            raise RuntimeError("no prefill in flight: launch_prefill "
                               "comes first")
        return self._firsts.popleft()

    def free(self, slot):
        self.session.free(slot)
        # a draft that died with its table (the session is degraded to
        # target-only rounds by then) has nothing left to release
        if self.draft_session.active[slot] \
                and self.draft_session._dead is None:
            self.draft_session.free(slot)

    def decode(self):
        """Plain target-only step (the greedy_decode/static-baseline
        surface); keeps the draft synced so a later spec round starts
        from a mirrored table."""
        toks, _ = self.step(force_plain=True)
        return toks[:, 0]

    # -- the speculative round ------------------------------------------

    def _draft_catchup(self, mask, pins, draft_delay=0.0):
        """Advance the draft one step for `mask` slots (consuming their
        pending token, landing its KV row) and re-pin their pending
        tokens to the committed stream's (`pins` [N])."""
        ds = self.draft_session
        saved = ds.active
        try:
            _check_draft_poison()
            if draft_delay:
                time.sleep(draft_delay)
            ds.active = mask
            ds.decode()
        except BaseException as e:
            self._degrade(e)
            return
        finally:
            ds.active = saved
        for s in np.nonzero(mask)[0]:
            ds.last_tokens[s] = np.int32(pins[s])

    def step(self, step_delay=0.0, draft_delay=0.0, force_plain=False,
             fused=False):
        """One round over the slot table.  Returns (tokens [N, k+1]
        int32, counts [N] int32): slot s committed `counts[s]` tokens
        this round, `tokens[s, :counts[s]]` in stream order (counts is
        0 for inactive slots, 1 for plain rounds, 1..k+1 for spec
        rounds).  `step_delay`/`draft_delay` are the bench/chaos
        per-launch device-cost stand-ins (GIL-released sleeps before
        the verify/plain step and before each draft step).

        A round runs speculatively unless the session is degraded,
        `force_plain` is set, or some occupied slot lacks the k+1 cache
        rows a verify writes — those rounds fall back to ONE plain
        target step for every slot (progress is never blocked by a
        nearly-full slot), with a draft catch-up step keeping the
        tables mirrored.

        `fused=True` runs the whole round as ONE dispatch (SERVING.md
        "Fused multi-step decode"): k draft steps + verify + accept /
        rollback / catch-up ride `GenerativePredictor.fused_spec_fn`
        instead of k+1 host-driven launches.  Committed streams are
        bit-identical either way — the fused program is the same
        traced math; only the dispatch count changes.  Draft-poison
        chaos still fires per logical draft step (checked host-side
        before the dispatch), degrading to the same plain round."""
        from paddle_tpu.parallel.mesh import check_member_poison
        # a lost mesh member kills the TARGET lane whole (typed, never
        # wedged) — unlike a draft death, which only degrades the round
        check_member_poison(self.predictor.device)
        ts = self.session
        k = self.spec_k
        C = k + 1
        N = self.n_slots
        active = ts.active.copy()
        occupied = np.nonzero(active)[0]
        spec_ok = (not force_plain and not self._degraded
                   and occupied.size > 0
                   and all(ts.room(int(s)) >= C for s in occupied))
        self.last_spec = False
        drafts = []
        if spec_ok and fused:
            # host-side chaos parity: the poison counter advances once
            # per LOGICAL draft step (and the draft-cost stand-in
            # sleeps k times), exactly like the host-driven round — a
            # poisoned draft degrades this round to plain before the
            # fused dispatch ever launches
            try:
                for _ in range(k):
                    _check_draft_poison()
                    if draft_delay:
                        time.sleep(draft_delay)
            except BaseException as e:
                self._degrade(e)
                spec_ok = False
        if spec_ok and fused:
            ds = self.draft_session
            self.last_draft_end = time.monotonic()
            if step_delay:
                time.sleep(step_delay)
            fn = self.predictor.fused_spec_fn(self.draft_predictor,
                                              N, k)
            ts._alive()
            ds._alive()
            try:
                # both sessions' tables are donated to the round
                (g, m, ts._kc, ts._vc, t_len, t_last,
                 ds._kc, ds._vc, d_len, d_last) = fn(
                    self.predictor._state, self.draft_predictor._state,
                    ts._kc, ts._vc, ts._put(ts.lengths),
                    ts._put(ts.last_tokens), ds._kc, ds._vc,
                    ds._put(ds.lengths), ds._put(ds.last_tokens),
                    ts._put(active))
            except BaseException as e:
                _mark_dead("fused_spec", e, ts, ds)
                raise
            g = np.asarray(g)
            m = np.where(active, np.asarray(m), 0).astype(np.int32)
            counts = np.where(active, m + 1, 0).astype(np.int32)
            # integer bookkeeping round-trips the device exactly
            ts.lengths = np.asarray(t_len).astype(np.int32)
            ts.last_tokens = np.asarray(t_last).astype(np.int32)
            ds.lengths = np.asarray(d_len).astype(np.int32)
            ds.last_tokens = np.asarray(d_last).astype(np.int32)
            ts.steps += 1
            # the draft table advanced k steps (+1 catch-up when any
            # slot fully accepted), same as the host-driven round
            ds.steps += k + (1 if bool((m[occupied] == k).any()) else 0)
            self.rounds += 1
            self.proposed += k * occupied.size
            self.accepted += int(m[occupied].sum())
            self.last_spec = True
            return g, counts
        if spec_ok:
            ds = self.draft_session
            try:
                for _ in range(k):
                    _check_draft_poison()
                    if draft_delay:
                        time.sleep(draft_delay)
                    drafts.append(np.asarray(ds.decode()))
            except BaseException as e:
                # draft died mid-round: discard its proposals and keep
                # the stream moving with a plain target step THIS round
                self._degrade(e)
                spec_ok = False
        if spec_ok:
            self.last_draft_end = time.monotonic()
            if step_delay:
                time.sleep(step_delay)
            chunk = np.zeros((N, C), np.int32)
            chunk[:, 0] = ts.last_tokens
            for j in range(k):
                chunk[:, j + 1] = drafts[j]
            fn = self.predictor.verify_fn(N, k)
            g, m, ts._kc, ts._vc = ts._call(
                "verify", fn, (ts._kc, ts._vc),
                (ts.lengths, chunk, active))
            g, m = ts._fetch("verify", g, m)
            m = np.where(active, m, 0).astype(np.int32)
            counts = np.where(active, m + 1, 0).astype(np.int32)
            ts.lengths = (ts.lengths + counts).astype(np.int32)
            ts.last_tokens = np.where(
                active, g[np.arange(N), np.minimum(m, k)],
                ts.last_tokens).astype(np.int32)
            ts.steps += 1
            # draft sync: rejected rows roll back; fully-accepted slots
            # owe the draft one catch-up row (it emitted d_k without
            # ever consuming it)
            if not self._degraded:
                for s in occupied:
                    s = int(s)
                    if m[s] < k:
                        self.draft_session.rollback(
                            s, k - 1 - int(m[s]),
                            last_token=int(g[s, m[s]]))
                full = active & (m == k)
                if full.any():
                    self._draft_catchup(full, g[:, k],
                                        draft_delay=draft_delay)
            self.rounds += 1
            self.proposed += k * occupied.size
            self.accepted += int(m[occupied].sum())
            self.last_spec = True
            return g, counts
        # plain fallback round: one target step, every occupied slot
        # advances exactly one token (degraded mode lives here)
        if step_delay:
            time.sleep(step_delay)
        toks1 = ts.decode()
        self.plain_steps += 1
        if not self._degraded and active.any():
            self._draft_catchup(active, toks1, draft_delay=draft_delay)
        out = np.zeros((N, C), np.int32)
        out[:, 0] = toks1
        return out, active.astype(np.int32)


def load_decode_predictor(dirname, kv_cache_dtype=None):
    """Open a `save_decode_model` artifact (fresh-process serving);
    `kv_cache_dtype` overrides the artifact's cache-numerics pin."""
    return GenerativePredictor(dirname, kv_cache_dtype=kv_cache_dtype)


def greedy_decode(predictor, tokens, max_new_tokens, n_slots=1,
                  slot=0, session=None):
    """Single-request reference decode: prefill + step loop on a
    dedicated session — the unbatched oracle the continuous-batching
    parity tests (and bench_serving's bit_exact replay) compare
    against.  Returns (generated_tokens, finish_reason)."""
    sess = session if session is not None \
        else predictor.new_session(n_slots)
    out = []
    reason = "length"
    tok = sess.prefill(slot, tokens)
    out.append(tok)
    eos = predictor.eos_id
    try:
        while len(out) < max_new_tokens and out[-1] != eos:
            if sess.room(slot) <= 0:
                break
            toks = sess.decode()
            out.append(int(toks[slot]))
    finally:
        sess.free(slot)
    if out[-1] == eos:
        reason = "eos"
    return out, reason
