"""Typed runtime flag registry with environment ingestion.

Reference analogue: the gflags config surface — 87 ``DEFINE_*`` across
fluid (e.g. ``fraction_of_gpu_memory_to_use`` platform/gpu_info.cc:22,
``use_mkldnn`` framework/executor.cc:28, allocator strategy
allocation/allocator_strategy.h:21) re-exported to Python through a curated
env-flag allowlist at import (python/paddle/fluid/__init__.py:114-134
``read_env_flags`` -> ``core.init_gflags``).

TPU redesign: one typed registry. A flag is declared with DEFINE_*; at
import, ``PADDLE_TPU_FLAGS_<name>`` (or reference-style ``FLAGS_<name>``)
environment variables override defaults; at runtime ``set_flags`` /
``get_flags`` mirror the modern fluid API. Flags may register an on-change
callback for live wiring (e.g. AMP). Flags whose reference meaning is owned
by XLA on TPU (allocator sizing, per-op GC) are kept as documented
advisory knobs so reference configs keep loading.
"""

import os

__all__ = ["DEFINE_bool", "DEFINE_int", "DEFINE_float", "DEFINE_string",
           "FLAGS", "set_flags", "get_flags", "flag_info"]

_TRUE = frozenset(["1", "true", "yes", "on"])
_FALSE = frozenset(["0", "false", "no", "off", ""])


class _FlagDef:
    __slots__ = ("name", "type", "default", "help", "on_change", "value")

    def __init__(self, name, type_, default, help_, on_change=None):
        self.name = name
        self.type = type_
        self.default = default
        self.help = help_
        self.on_change = on_change
        self.value = default


_DEFS = {}


class _Flags:
    """Attribute access mirror of the registry: ``FLAGS.check_nan_inf``."""

    def __getattr__(self, name):
        d = _DEFS.get(name)
        if d is None:
            raise AttributeError("unknown flag %r" % name)
        return d.value

    def __setattr__(self, name, value):
        set_flags({name: value})


FLAGS = _Flags()


def _coerce(d, value):
    if d.type is bool:
        if isinstance(value, str):
            lv = value.strip().lower()
            if lv in _TRUE:
                return True
            if lv in _FALSE:
                return False
            raise ValueError("flag %s: cannot parse %r as bool"
                             % (d.name, value))
        return bool(value)
    return d.type(value)


def _env_override(d):
    for key in ("PADDLE_TPU_FLAGS_" + d.name, "FLAGS_" + d.name):
        if key in os.environ:
            return os.environ[key]
    return None


def _define(name, type_, default, help_, on_change=None):
    d = _FlagDef(name, type_, default, help_, on_change)
    _DEFS[name] = d
    raw = _env_override(d)
    if raw is not None:
        set_flags({name: raw})
    return d


def DEFINE_bool(name, default, help_="", on_change=None):
    return _define(name, bool, default, help_, on_change)


def DEFINE_int(name, default, help_="", on_change=None):
    return _define(name, int, default, help_, on_change)


def DEFINE_float(name, default, help_="", on_change=None):
    return _define(name, float, default, help_, on_change)


def DEFINE_string(name, default, help_="", on_change=None):
    return _define(name, str, default, help_, on_change)


def set_flags(flags_dict):
    """Set one or more flags (modern fluid API: fluid.set_flags)."""
    for name, value in flags_dict.items():
        d = _DEFS.get(name)
        if d is None:
            raise KeyError(
                "unknown flag %r; known flags: %s"
                % (name, ", ".join(sorted(_DEFS))))
        new = _coerce(d, value)
        old, d.value = d.value, new
        if d.on_change is not None and new != old:
            d.on_change(new)


def get_flags(names):
    """Read flags by name (str or list of str) -> dict."""
    if isinstance(names, str):
        names = [names]
    return {n: _DEFS[n].value for n in names}


def flag_info():
    """name -> (type, default, current, help) for documentation/tests."""
    return {n: (d.type.__name__, d.default, d.value, d.help)
            for n, d in sorted(_DEFS.items())}


# ---------------------------------------------------------------------------
# built-in flag definitions (the curated allowlist)
# ---------------------------------------------------------------------------

def _amp_changed(v):
    from .ops import registry
    registry.set_amp(v)


DEFINE_bool(
    "check_nan_inf", False,
    "Re-check every op output for NaN/Inf and NAME the first offending op "
    "(reference FLAGS_check_nan_inf, framework/operator.cc:29). Forces "
    "eager per-op execution — a debugging mode with per-op dispatch cost, "
    "exactly like the reference's per-op re-check + sync.")
DEFINE_bool(
    "benchmark", False,
    "Synchronize after every executor step and make timing honest "
    "(reference FLAGS_benchmark forced per-op device sync, scope.cc:25).")
DEFINE_bool(
    "use_bf16_amp", False,
    "bf16 automatic mixed precision: MXU-native bf16 matmuls/convs with "
    "fp32 master weights (the TPU analogue of the reference's fp16 "
    "data-transform story).", on_change=_amp_changed)
DEFINE_bool(
    "whole_graph_ad", False,
    "Serve a program's backward section with ONE jax.vjp over the whole "
    "forward region instead of per-op stashed vjps, when the program shape "
    "allows it (straight-line forward, generic grads only). Enables real "
    "jax.checkpoint rematerialization via FLAGS.remat_policy.")
DEFINE_string(
    "remat_policy", "",
    "Rematerialization policy for whole_graph_ad: '' (save everything), "
    "'conv_out' (keep conv outputs, recompute BN/activation tails — "
    "ROOFLINE.md's remat lever), 'dots', or 'nothing'.")
DEFINE_int(
    "fuse_bottleneck_max_width", 0,
    "FuseBottleneckPass fuses only bottlenecks whose width F (the 3x3 "
    "conv's channel count) is <= this; 0 (default) disables the pass. "
    "The r05 chip measurements set this default: standalone, the Pallas "
    "kernel beats XLA at F=64 (+12%) and F=128 (tune_bottleneck stages, "
    "ROOFLINE.md round 5), but IN-GRAPH the custom-call boundary "
    "around each fused block costs more than the kernel saves — "
    "end-to-end ResNet-50 serving measured slower at every gate "
    "(F<=128, 7 blocks: 1354 vs 1599 img/s; F<=64, 3 blocks: 1526 vs "
    "1584; fuse-all was worst). Set a width to opt in for experiments.")
DEFINE_int(
    "flash_block_q", 0,
    "Flash-attention forward q-block edge; 0 (default) resolves per shape "
    "via the tune cache (FLAGS.attention_tune_cache) then the MXU-aligned "
    "heuristic (ops/attention_tuning.py). Nonzero overrides both — the "
    "process-wide expert knob; per-call block args override even this.")
DEFINE_int(
    "flash_block_kv", 0,
    "Flash-attention forward k/v-block edge; 0 = auto (see flash_block_q).")
DEFINE_int(
    "flash_block_q_bwd", 0,
    "Flash-attention backward (dq/dkv kernels) q-block edge; 0 = auto.")
DEFINE_int(
    "flash_block_kv_bwd", 0,
    "Flash-attention backward (dq/dkv kernels) k/v-block edge; 0 = auto.")
DEFINE_string(
    "attention_tune_cache", "",
    "Path of the flash-attention shape->block-config tune cache written "
    "by `tools/bench_attention.py --tune` and consulted at trace time; "
    "empty means <repo>/tools/attention_tune_cache.json.")
DEFINE_bool(
    "ring_use_flash", True,
    "Ring attention (parallel/ring_attention.py) computes each hop's "
    "block with the tuned Pallas flash kernel and merges hops by "
    "logsumexp, instead of the plain-XLA online-softmax update. The "
    "kernel path never materializes the [S_loc, S_loc] score tile; "
    "disable to A/B against the composition the r5 numbers were "
    "recorded on.")
DEFINE_int(
    "roi_align_adaptive_cap", 8,
    "roi_align adaptive-grid cap (sampling_ratio <= 0): the reference's "
    "per-roi ceil(roi_h/ph) x ceil(roi_w/pw) sample grid is emulated "
    "under static shapes by evaluating a [cap, cap] grid and masking; a "
    "roi needing more samples per bin degrades to a cap x cap uniform "
    "subsample (a one-time warning fires when eager inputs actually "
    "clip). Raise for detection heads pooling very large rois; cost is "
    "quadratic in the cap.")
DEFINE_bool(
    "cpu_deterministic", False,
    "Prefer deterministic reduction order (reference FLAGS_cpu_deterministic, "
    "python/paddle/fluid/__init__.py:123). Advisory on TPU: XLA reductions "
    "are deterministic for a fixed compilation.")
DEFINE_string(
    "profiler_path", "/tmp/paddle_tpu_profile",
    "Default trace output directory for fluid.profiler "
    "(reference profiler proto path).")
DEFINE_float(
    "eager_delete_tensor_gb", -1.0,
    "Reference GC threshold (executor.cc eager deletion). Advisory: XLA "
    "owns device memory; buffer lifetime ends with the computation.")
DEFINE_float(
    "fraction_of_gpu_memory_to_use", 0.92,
    "Reference gpu_info.cc:22. Advisory on TPU (XLA preallocates HBM); "
    "honored for CPU client via XLA_PYTHON_CLIENT_MEM_FRACTION when set "
    "before first device use.")
DEFINE_int(
    "paddle_num_threads", 1,
    "Reference inter-op CPU threads. Advisory: XLA owns scheduling.")
DEFINE_float(
    "rpc_deadline", 180.0,
    "Parameter-server RPC timeout in seconds (reference FLAGS_rpc_deadline).")
DEFINE_int(
    "rpc_retry_times", 5,
    "Attempts for the jittered-backoff retry wrappers on the distributed "
    "control plane (MasterClient._call re-dials, wait_server_ready polls, "
    "RPCClient idempotent-command reconnects). 1 disables retries.")
DEFINE_float(
    "rpc_retry_backoff", 0.05,
    "Base delay (seconds) of the retry wrappers' exponential backoff; "
    "each attempt doubles it up to 2s with +/-50% jitter so restarting "
    "peers are not stampeded (utils/retry.py RetryPolicy).")
DEFINE_bool(
    "sentinel_nan_check", False,
    "Anomaly sentinel: screen each Trainer step's fetched losses (and "
    "params with sentinel_check_params) for NaN/Inf at the step boundary "
    "— cheap, jit-preserving, unlike check_nan_inf's eager per-op mode. "
    "A bad step is reverted (immutable-array snapshot restore) and, "
    "after sentinel_max_bad_steps consecutive bad steps, the policy "
    "decides: raise, or roll back to the last-good checkpoint.")
DEFINE_string(
    "sentinel_policy", "skip",
    "What the sentinel does after sentinel_max_bad_steps consecutive "
    "non-finite steps: 'skip' raises SentinelError; 'rollback' reloads "
    "the last-good checkpoint from the Trainer's checkpoint dir and "
    "keeps training (raising only if training re-diverges right after).")
DEFINE_int(
    "sentinel_max_bad_steps", 3,
    "Consecutive non-finite steps the sentinel absorbs by skipping "
    "before escalating to its policy (K in the rollback design).")
DEFINE_bool(
    "sentinel_check_params", False,
    "Sentinel also screens every persistable (params + optimizer "
    "accumulators) each step, not just the fetched losses. Catches "
    "corruption the loss hasn't seen yet; costs a host transfer of the "
    "full state per step.")
DEFINE_float(
    "step_watchdog_secs", 0.0,
    "Wall-clock watchdog on each Executor.run/run_loop dispatch: the "
    "device computation runs on a worker thread and a step exceeding "
    "this many seconds raises StepWatchdogTimeout instead of blocking "
    "forever (a hung XLA dispatch sits inside C, unkillable from "
    "Python). "
    "0 disables; enabling forces a block_until_ready per step, so this "
    "is a hang-detection mode, not a fast path.")
DEFINE_int(
    "async_dispatch_depth", 0,
    "Asynchronous step dispatch: the Trainer (and the bench harnesses) "
    "keep up to this many steps' fetches in flight as live device "
    "arrays (Executor.run(as_future=True) -> FetchFuture) and resolve "
    "them at the pipeline tail with one batched jax.device_get each — "
    "loss bookkeeping, sentinel NaN/Inf screening and event callbacks "
    "lag dispatch by <= depth steps (PIPELINE.md). 0 (default) keeps "
    "the fully synchronous per-step behavior. The async trajectory is "
    "bit-exact vs sync on finite runs (same RNG step folds, same "
    "donation discipline); after a non-finite step the sentinel's "
    "recovery re-dispatches the in-flight batches from the reverted "
    "state, so post-anomaly trajectories legitimately differ.")
DEFINE_int(
    "reader_prefetch_depth", 0,
    "Device prefetch queue depth for the Trainer's reader path "
    "(reader.prefetch_to_device): a bounded background thread runs "
    "prepare_feeds + the device_put for the NEXT batch while the "
    "current step computes — the double_buffer/py_reader infeed "
    "overlap (operators/reader/buffered_reader.cc). 0 (default) feeds "
    "on the main thread each step.")
DEFINE_float(
    "serving_batch_deadline_ms", 5.0,
    "Serving micro-batcher coalescing window: after the first request of "
    "a dispatch group arrives, wait at most this many milliseconds for "
    "more compatible requests before dispatching (paddle_tpu/serving/"
    "batcher.py). 0 dispatches immediately — no cross-request batching "
    "beyond what is already queued.")
DEFINE_int(
    "serving_max_queue", 256,
    "Serving admission control: maximum requests waiting in a model's "
    "batcher queue. A submit beyond this depth is shed with an explicit "
    "ServerOverloaded instead of growing an unbounded backlog "
    "(shed-not-hang; see SERVING.md overload semantics).")
DEFINE_int(
    "serving_workers", 1,
    "Dispatch worker threads per replica execution lane: each worker "
    "takes one coalesced micro-batch group off its lane and runs it on "
    "that lane's replica; >1 allows overlapping micro-batches of the "
    "same replica (useful when the runner releases the GIL during XLA "
    "execution).")
DEFINE_string(
    "serving_replicas", "1",
    "Default replica placement spec for served models (SERVING.md "
    "multi-chip serving): an integer N places N device-resident replicas "
    "round-robin over the local devices (1 keeps the single default-"
    "device replica — the pre-multichip behavior); 'auto' places one "
    "replica per local device; an explicit comma list names devices "
    "('0,2' = local device indices, 'cpu:0,tpu:3' = platform:index). "
    "Mesh replicas (SERVING.md 'Mesh replicas'): 'mesh:2' or 'mesh:2x2' "
    "packs the whole host into device meshes of that size, one replica "
    "per mesh (params + KV cache sharded across the members, replies "
    "bit-exact vs a single-device replica); '+' inside a comma list "
    "builds one mesh replica from named members ('tpu:0+tpu:1,"
    "tpu:2+tpu:3'); a member may not repeat across replicas. "
    "Each replica's params live on its device (or mesh) and its batch "
    "buckets compile and warm there; a router assigns each coalesced "
    "micro-batch group to the least-loaded replica.")
DEFINE_int(
    "serving_lane_depth", 1,
    "Per-replica dispatch lane bound: at most this many coalesced "
    "groups wait behind each replica's in-flight dispatches. When every "
    "lane is full the router holds the next group (sticky back-"
    "pressure), the admission queue fills, and submits shed with "
    "ServerOverloaded — overload still sheds at the front instead of "
    "queueing unboundedly behind slow replicas.")
DEFINE_int(
    "serving_device_mem_mb", 0,
    "Per-replica device memory budget (MiB) for the serving admission "
    "fit check (ANALYSIS.md resource analysis): load_model statically "
    "estimates each replica's peak HBM (params + activation peak + "
    "decode KV slot table) and rejects an un-fittable placement with a "
    "ResourceFitError BEFORE any build/warm work — naming the "
    "estimated and available bytes. 0 (default) resolves the budget "
    "from the device itself (memory_stats bytes_limit, else the known "
    "TPU HBM capacity table); on CPU with no configured budget the "
    "check passes trivially.")
DEFINE_int(
    "serving_decode_slots", 8,
    "Slot-table size of each replica's decode lane (SERVING.md "
    "continuous batching): the fixed-shape decode step XLA compiles "
    "once runs over this many KV-cache slots per lane, so it is also "
    "the per-replica cap on concurrently generating requests. A new "
    "request joins the RUNNING decode batch the step after any slot "
    "frees (EOS / max-tokens / deadline / disconnect) — no coalesce "
    "window. Larger tables raise aggregate tokens/sec under load at "
    "the cost of KV-cache HBM (slots x max_seq_len x layers).")
DEFINE_int(
    "serving_max_new_tokens", 128,
    "Default generation budget per streaming request: a decode slot is "
    "reclaimed after this many generated tokens when the request does "
    "not set its own max_new_tokens (which is still clamped to this "
    "server-side ceiling — one runaway prompt must not pin a slot "
    "forever).")
DEFINE_int(
    "serving_stream_chunk_tokens", 1,
    "Streaming reply granularity: the server flushes a token-delta "
    "frame to the client every this many generated tokens (and always "
    "at end of stream). 1 streams every token as it decodes; larger "
    "values trade time-to-token for fewer wire frames.")
DEFINE_string(
    "serving_kv_cache_dtype", "",
    "Default KV-cache numerics for decode artifacts that do not pin "
    "one in decode_meta (QUANTIZE.md \"Quantized KV cache\"): '' or "
    "'fp32'/'float32' keeps the fp32 slot table; 'int8' stores K/V "
    "slots as int8 with per-(layer,head) fp32 scales — ~0.25x cache "
    "bytes per slot, greedy streams bit-stable against themselves. "
    "Per-load override: load_model(kv_cache_dtype=...).")
DEFINE_bool(
    "mesh_tp", False,
    "Tensor-parallel mesh compute (SERVING.md \"Tensor-parallel "
    "compute\"): a mesh replica's decode program lowers as ONE "
    "shard_map'd executable over the replica's MeshGroup — fc/mul "
    "weights in Megatron column->row pairs with one psum per pair, "
    "attention head-parallel with the decode kernel running per member "
    "on its resident KV shard (int8 scales slice along heads too), "
    "embedding row-sharded over vocab — so params and KV never "
    "materialize unsharded and per-step HBM traffic per member drops "
    "~1/mesh_size (the decode-roofline win, ROOFLINE.md). Streams stay "
    "top-1 identical to a single-device replica; activations carry "
    "psum-reduction-order noise at float tolerance where a matmul is "
    "row-split (documented contract, tests/test_mesh_tp.py). False "
    "(default) keeps PR 18's shard-at-rest gather path — bit-exact by "
    "construction. Read at predictor build time: registry fault-in / "
    "hot-swap rebuilds pick up a flip.")
DEFINE_int(
    "mesh_tp_prefill_seq", 128,
    "Minimum prompt bucket for sequence-parallel TP prefill: at or "
    "above this bucket (and when the bucket divides the mesh), prefill "
    "shards the SEQUENCE axis across members ulysses-style (all_to_all "
    "into head-parallel attention, parallel/ulysses.py) with per-layer "
    "weight all_gathers amortized over the long prompt — bit-exact vs "
    "the single-device oracle because every position's math runs with "
    "full weights. Below it, prefill runs head/column-parallel like "
    "decode (top-1 contract). Only read when FLAGS.mesh_tp is on.")
DEFINE_int(
    "serving_spec_k", 4,
    "Speculative-decoding draft depth (SERVING.md): when a decode "
    "model is loaded WITH a draft artifact (load_model(draft=...) or "
    "FLAGS.serving_spec_draft), each round the draft proposes this "
    "many tokens and the fp32 target verifies all k+1 positions in one "
    "fixed-shape batched step; the longest greedily-agreeing prefix "
    "commits, so slots advance 1..k+1 tokens per target step while the "
    "stream stays bit-identical to target-only decode. Only meaningful "
    "with a draft configured; < 1 disables speculation outright.")
DEFINE_string(
    "serving_spec_draft", "",
    "Default draft artifact directory for speculative decoding: a "
    "decode artifact sharing the target's vocab/eos (canonically the "
    "int8 twin of the same model — QUANTIZE.md, the int8 lane's second "
    "job). Every decode load_model without an explicit draft= uses it; "
    "empty (default) serves decode models without speculation. The "
    "draft is fit-checked by the ANALYSIS.md admission gate alongside "
    "the target (both KV slot tables count).")
DEFINE_bool(
    "compile_cache", True,
    "Persistent compile/artifact cache (COMPILE_CACHE.md): Predictor "
    "AOT bucket compiles are keyed by a content fingerprint (program "
    "hash, feed/state shapes+dtypes, device kind, jax+lib versions) and "
    "their serialized jax.export executables committed to the on-disk "
    "store with the checkpoint vault's write-temp->fsync->rename "
    "discipline, so a later server boot or hot-swap flip of the same "
    "(model, bucket, device-kind) deserializes instead of re-tracing "
    "and re-compiling. jax's own persistent XLA-executable cache is "
    "pointed at <store>/xla so the XLA compile is a disk hit too. "
    "Corrupt/truncated entries are silently recompiled; disable to "
    "force fresh compilation everywhere.")
DEFINE_string(
    "compile_cache_dir", "",
    "Root directory of the persistent AOT compile cache + kernel-tuning "
    "registry; empty means <checkout>/.cache/paddle_tpu (git-ignored, "
    "inside the tree). The store is cross-process shared: every "
    "commit is atomic and readers verify CRC32s, so concurrent servers "
    "and a killed writer cannot poison each other. jax's own persistent "
    "cache is NOT placed by this flag: it lives where "
    "JAX_COMPILATION_CACHE_DIR says, else at <checkout>/.cache/jax.")
DEFINE_int(
    "compile_cache_max_mb", 1024,
    "Size cap (MiB) of the compile cache store; a put past the cap "
    "evicts least-recently-used AOT entries (manifest mtime, touched "
    "on every hit). The entry just written is never the victim.")
DEFINE_int(
    "quantize_min_weight_elems", 1024,
    "PTQ size floor (inference/quantize.py): a weight with fewer "
    "elements than this stays fp32 — biases, norm scales and small "
    "embeddings are not worth the dequant plumbing (their bytes are "
    "noise on the HBM roofline) and are the numerically riskiest to "
    "quantize. Applies to mul/conv filters and embedding tables alike.")
DEFINE_int(
    "quantize_calib_batches", 4,
    "How many user-supplied calibration batches the PTQ pass consumes "
    "(inference/quantize.py): per-channel int8 scales start at absmax "
    "and a small clip-ratio search refines them against the calibration "
    "activations (fc layers) or the weight-quantization MSE (conv); "
    "extra batches beyond this are ignored so a big feed list cannot "
    "turn quantization into a training run.")
DEFINE_bool(
    "verify_program", False,
    "Pre-run program verification (ANALYSIS.md): before an Executor / "
    "ParallelExecutor compiles a program (or a Predictor loads one), run "
    "the static analysis passes — use-before-def, shape/dtype "
    "propagation, dead-op and fetch-reachability, AOT-exportability — "
    "and raise ProgramVerificationError on error findings instead of "
    "letting the bug surface as a runtime backend trace N steps in. "
    "Memoized per (program version, feeds, fetches): the check runs at "
    "build/load, never per step, so the hot path cost is one dict hit. "
    "The save_inference_model / load_inference_model artifact "
    "boundaries verify unconditionally — this flag adds the in-process "
    "executor surfaces.")
DEFINE_bool(
    "executor_compile_cache", False,
    "Opt-in: Executor.run also consults the persistent compile cache "
    "for INFERENCE-SHAPED programs (single block, no *_grad ops, no "
    "optimizer ops, no host ops) whose fingerprint is derivable from "
    "the Program serialization. Off by default: training steps donate "
    "buffers and change shape rarely, so the win is serving-side; "
    "enable for executor-driven batch inference over a fixed program.")
def _trace_changed(v):
    from .obs import tracing
    tracing.configure(enabled=v)


def _trace_buffer_changed(v):
    from .obs import tracing
    tracing.configure(capacity=v)


def _event_log_changed(v):
    from .obs import events
    events.configure(path=v)


def _event_log_max_changed(v):
    from .obs import events
    events.configure(max_kb=v)


def _flight_changed(v):
    from .obs import flightrec
    flightrec.configure()


# NOTE: companion flags (buffer size / rotation cap) are defined BEFORE
# the flags whose on_change hooks read them, so an env override firing
# mid-import finds them registered.
DEFINE_int(
    "trace_buffer_events", 65536,
    "Capacity of the obs span ring buffer (paddle_tpu/obs/tracing.py): "
    "completed spans land in a fixed-size ring; the oldest fall off "
    "silently under load (the drop count rides the metrics surface). "
    "Sized so the slowest recent requests/steps tools/trace_top.py "
    "prints are always resolvable, and so a 45 s benchmark window of a "
    "decode lane (6 spans a round, 8 a request) fits whole with room to "
    "spare: 19 300 spans at a 13 ms round (PR 27; the benchmark calls a "
    "run with a dropped span not correct); memory cost is ~200 "
    "bytes/span.",
    on_change=_trace_buffer_changed)
DEFINE_float(
    "trace_slow_ms", 0.0,
    "Slow-request/step log gate: a serving request (root span) or train "
    "step whose duration exceeds this many milliseconds is also emitted "
    "as a 'slow' structured event (event log), carrying its trace_id / "
    "step id so the outlier is findable after the span ring wrapped. "
    "0 disables the slow log.")
DEFINE_bool(
    "trace", True,
    "End-to-end span tracing (OBSERVABILITY.md): serving requests get "
    "per-stage spans (admission, queue wait, coalesce, lane routing, "
    "device compute, reply scatter) under a reply-visible trace_id; "
    "training steps get prefetch_wait/dispatch/drain/ckpt spans. "
    "Overhead is pinned <3% on the bench smoke lanes (BENCH_r09.json); "
    "disable to make the tracer a no-op (spans, not metrics — counters "
    "keep working).", on_change=_trace_changed)
DEFINE_int(
    "event_log_max_kb", 1024,
    "Rotation threshold (KiB) of the structured event log file: past "
    "this size the file is fsynced and atomically renamed to <path>.1 "
    "(vault commit discipline — tools/chaos.py --scenario "
    "trace-overflow kills a writer mid-rotation to prove the old log "
    "survives intact).", on_change=_event_log_max_changed)
DEFINE_string(
    "event_log", "",
    "Path of the append-only JSONL structured event log "
    "(paddle_tpu/obs/events.py): discrete lifecycle events — hot-swap "
    "flips, compile-cache deltas, sentinel skips/rollbacks, sheds with "
    "priority, watchdog fires, checkpoint commits — each stamped with "
    "trace/step ids so logs, metrics and traces cross-reference. "
    "Empty (default) keeps events in the bounded in-memory ring only.",
    on_change=_event_log_changed)
DEFINE_bool(
    "slo_monitor", True,
    "Run the SLO monitor thread on every InferenceServer "
    "(paddle_tpu/obs/slo.py): samples the serving counters every "
    "slo_eval_interval_ms into a bounded time-series ring and "
    "evaluates declared SLOs (serving_slo) with Google-SRE-style "
    "multi-window burn rates into the ok/degraded/breach state "
    "machine the `health` RPC verb renders. Overhead is a counter "
    "read per model per interval (<3% pinned, BENCH_r13.json); "
    "disable only to rule the monitor out while debugging.")
DEFINE_float(
    "slo_eval_interval_ms", 1000.0,
    "SLO monitor sampling/evaluation interval in milliseconds. Each "
    "tick appends one sample per served model lane to the timeline "
    "ring (also the flight-recorder bundle's metrics timeline) and "
    "re-evaluates the burn-rate windows; detection latency for a "
    "hard breach is ~2 fast-window ticks.")
DEFINE_string(
    "serving_slo", "",
    "Declared SLOs (OBSERVABILITY.md \"SLOs & burn rates\"): "
    "semicolon-separated '[model:]key=val,key=val' declarations; no "
    "model prefix (or '*') sets the default for every model. Keys: "
    "p95_ms, ttft_p95_ms, error_rate, shed_rate, spec_accept "
    "(objectives) plus budget, fast_window, slow_window, fast_burn, "
    "slow_burn, breach_evals, recover_evals (tuning). Example: "
    "'p95_ms=250,error_rate=0.01;llm:ttft_p95_ms=400'. Empty = "
    "sample-only (timeline for the flight recorder, no evaluation).")
DEFINE_string(
    "flight_dir", "",
    "Flight-recorder bundle root (paddle_tpu/obs/flightrec.py): on "
    "trigger (watchdog_fire, sentinel giveup/rollback, slo_breach, "
    "serving thread death, manual `flight` RPC) a post-mortem bundle "
    "— spans, events, metrics, SLO timeline, all-thread stacks, "
    "resolved flags, server snapshots — is committed atomically "
    "(write-temp -> fsync -> rename, vault discipline) under this "
    "directory. Empty (default) disables the recorder.",
    on_change=_flight_changed)
DEFINE_int(
    "flight_keep", 8,
    "Keep-N rotation for flight-recorder bundles: after each commit "
    "the oldest bundles beyond this count are deleted.",
    on_change=_flight_changed)
DEFINE_float(
    "flight_cooldown_s", 30.0,
    "Per-trigger-reason cooldown (seconds) on the flight recorder: a "
    "breach storm writes ONE bundle per reason per window, not "
    "hundreds. The manual `flight` RPC bypasses it (force).",
    on_change=_flight_changed)
DEFINE_bool(
    "fleet_controller", False,
    "Run the fleet controller on every InferenceServer "
    "(paddle_tpu/serving/fleet.py, SERVING.md \"Fleet controller\"): a "
    "background loop that closes the loop from the SLO burn/queue/"
    "occupancy/shed sensors to the registry's actuators — scaling a "
    "model's replica set within its declared [min,max] policy (every "
    "resize rides the build-warm-flip hot swap, so scaling is zero-"
    "drop by construction, and the resource fit check gates every "
    "grow), paging idle-past-TTL models out to their artifact paths "
    "(they fault back in on the next request — a reload, not a "
    "recompile, under the warm compile cache), and degrading under "
    "sustained burn by shifting ab_weight toward the int8 lane BEFORE "
    "admission sheds. Off (default) keeps replica counts, residency "
    "and lane weights fully operator-driven.")
DEFINE_float(
    "fleet_eval_interval_ms", 1000.0,
    "Fleet-controller evaluation interval in milliseconds: each tick "
    "reads the per-model sensors (SLO state/burn, queue depth, slot "
    "occupancy, shed/request deltas, idle age) and decides at most a "
    "few cooldown-bounded actions. Detection-to-actuation latency for "
    "a hard breach is roughly one SLO fast window plus one tick.")
DEFINE_string(
    "fleet_policy", "",
    "Declared fleet policies (SERVING.md \"Fleet controller\"): "
    "semicolon-separated '[model:]key=val,key=val' declarations; no "
    "model prefix (or '*') sets the default for every model. Keys: "
    "min_replicas, max_replicas (the scale range; max_replicas=1 "
    "disables scaling), page_ttl_s (idle seconds before a model pages "
    "out to its artifact path; 0 never pages), scale_up_queue (queued "
    "requests per live replica that trigger a grow), "
    "scale_down_idle_s, degrade_weight (the int8 lane's ab share "
    "under sustained burn), restore_evals (clean ticks before the "
    "weight restores — hysteresis), scale_cooldown_s, page_cooldown_s, "
    "degrade_cooldown_s. Example: 'max_replicas=4;llm:page_ttl_s=600,"
    "scale_up_queue=8'. Empty = observe-only (no policy, no actions).")
DEFINE_bool(
    "fleet_dry_run", False,
    "Fleet-controller dry-run: every tick still senses and decides, "
    "and every decision is logged as a fleet_decision event with its "
    "triggering signal, but NO action touches the registry — replica "
    "counts, residency and ab weights stay untouched. The rehearsal "
    "mode for a new policy spec against live traffic.")
DEFINE_string(
    "federation_frontend", "",
    "Federation frontend endpoint HOST:PORT (SERVING.md \"Federated "
    "serving\"): when set, every InferenceServer registers a "
    "membership lease with that front-door router at start, "
    "heartbeats its resident-model/queue payload, and deregisters on "
    "shutdown — the server becomes a BACKEND the frontend places "
    "traffic onto. Empty (default) keeps the server standalone. An "
    "InferenceServer(federation=...) argument overrides per server.")
DEFINE_float(
    "federation_ttl_s", 3.0,
    "Membership lease TTL in seconds (paddle_tpu/federation/"
    "membership.py): a backend whose heartbeat goes missing this long "
    "expires from the placement set and a backend_lost event fires. "
    "The frontend re-places subsequent traffic within one TTL of a "
    "backend death — this is the detection bound the chaos "
    "backend-kill scenario pins. Must exceed federation_heartbeat_ms "
    "with slack (3x is a sane floor: one lost beat must not flap the "
    "lease).")
DEFINE_float(
    "federation_heartbeat_ms", 1000.0,
    "Backend heartbeat interval toward the federation frontend in "
    "milliseconds. Each beat renews the lease and refreshes the "
    "serving payload the frontend places by (resident models with "
    "est_peak_mb, paged set, queue depth, accepting flag), so "
    "placement staleness is bounded by one beat.")
DEFINE_float(
    "federation_capacity_mb", 0.0,
    "Device-memory capacity this backend advertises on its lease in "
    "MB — the denominator of the global controller's placement-by-"
    "capacity signal (free = capacity - sum of resident est_peak_mb). "
    "0 (default) means unknown: the backend still serves, but "
    "capacity-aware placement treats it as last resort. An "
    "InferenceServer(capacity_mb=...) argument overrides per server.")
DEFINE_bool(
    "global_fleet", False,
    "Run the fleet-of-fleets controller on the federation frontend "
    "(paddle_tpu/federation/global_fleet.py): per-model GLOBAL "
    "replica budgets within declared [min,max] policies, placed "
    "across backends by the free-capacity signal (lease capacity_mb "
    "minus resident est_peak_mb); cold models page out cluster-wide "
    "past their idle TTL and fault back in wherever capacity lives, "
    "via the persisted lane specs the frontend records from "
    "load_model passthrough. Per-backend fleet controllers delegate "
    "their scale/page decisions to this tier while a federation link "
    "is up (degrade-before-shed stays local). Off (default) keeps "
    "cross-host placement operator-driven.")
DEFINE_string(
    "global_fleet_policy", "",
    "Global fleet policies, same grammar as fleet_policy "
    "('[model:]key=val,...;...', '*' or no prefix = default) but with "
    "min_replicas/max_replicas read as CLUSTER-WIDE totals across "
    "backends. Example: 'llm:min_replicas=2,max_replicas=8,"
    "page_ttl_s=600,scale_up_queue=8'. Empty = observe-only.")
DEFINE_float(
    "global_fleet_eval_interval_ms", 1000.0,
    "Global fleet-of-fleets evaluation interval in milliseconds: "
    "each tick senses the whole membership table (heartbeat-fed, no "
    "RPC fan-out) and decides at most a few cooldown-bounded "
    "cross-host actions.")
DEFINE_int(
    "dist_threadpool_size", 0,
    "Reference distributed thread pool size. Advisory.")
DEFINE_bool(
    "enable_rpc_profiler", False,
    "Record every parameter-server RPC as a profiler event "
    "(reference profiler.cc:33 FLAGS_enable_rpc_profiler).")
DEFINE_int(
    "while_grad_max_iters", 256,
    "Trip-count bucket for differentiating an UNBOUNDED While loop "
    "in-graph: the jit-native while gradient records per-iteration "
    "carries into a static buffer of this size. A loop still running at "
    "the cap poisons its float carries with NaN (loud failure, never a "
    "silently-truncated forward). Raise it for longer data-dependent "
    "loops; memory cost is cap x carry size.")
DEFINE_bool(
    "dynamic_while_host_grad", False,
    "Differentiate unbounded While loops via the host-path replay op "
    "(while_grad_dynamic) instead of the jit-native recorded gradient. "
    "The replay supports truly unbounded trip counts but forces the "
    "whole program onto the segmented eager path (reference "
    "while_op.cc:119 semantics).")
