"""Inference predictor — the serving layer.

Reference analogue: paddle/fluid/inference/api/ — `PaddlePredictor` /
`CreatePaddlePredictor` (paddle_api.h:134,:204), `NativePaddlePredictor`
(api_impl.cc:95 creates an Executor over the loaded program; Run at :135),
and `AnalysisPredictor` (analysis_predictor.cc) which runs the analysis pass
pipeline + TensorRT subgraph slicing before the same run loop.

TPU redesign: XLA *is* the analysis layer. NativeConfig -> load + jit the
pruned inference program; AnalysisConfig additionally runs the
InferenceTranspiler rewrites (BN fold, dropout removal — the ir/ fusion
passes whose effect XLA cannot replicate because they rewrite *weights*)
then AOT-compiles with jax.jit(...).lower(...).compile(), the TensorRT
engine analogue. Batch-size bucketing bounds recompiles the way TRT
profiles bounded engine shapes.
"""

import threading
import warnings

import numpy as np

__all__ = ["NativeConfig", "AnalysisConfig", "PaddleTensor", "Predictor",
           "create_paddle_predictor", "AotPredictor",
           "load_aot_predictor"]


# sentinel in the shared export map: this program cannot ride the
# export/serialize path (host callbacks, exotic lowering) — every
# replica falls back to direct compilation without retrying the export
_UNEXPORTABLE = object()

# mesh placements an AotPredictor has already warned about degrading
# (once per mesh label per process, not once per replica build)
_AOT_MESH_WARNED = set()


def _aot_degrade_mesh(device):
    """Serialized AOT exports carry a single-device calling convention —
    they cannot run sharded.  A mesh placement degrades LOUDLY (warn
    once per mesh) to the group's primary member so the artifact still
    serves; use Predictor/GenerativePredictor artifacts for real mesh
    replicas (SERVING.md "Mesh replicas")."""
    group = _mesh_of(device)
    if group is None:
        return device
    lbl = group.label()
    if lbl not in _AOT_MESH_WARNED:
        _AOT_MESH_WARNED.add(lbl)
        warnings.warn(
            "AOT artifacts cannot shard across a mesh — replica "
            "placement %s degrades to its primary member %s (serialized "
            "exports have a single-device calling convention; serve a "
            "Program or decode artifact to use the mesh)"
            % (lbl, _device_label(group.primary)),
            RuntimeWarning, stacklevel=3)
    return group.primary


def _amp_enabled():
    from paddle_tpu.ops.registry import amp_enabled
    return bool(amp_enabled())


def _var_is_batch_major(gb, name):
    """True when the program var's recorded shape leads with -1 — the
    marker save_aot already persists for AOT artifacts; the live
    Predictor reads the same ground truth instead of guessing from
    runtime shapes."""
    v = gb._find_var_recursive(name)
    return bool(v is not None and v.shape is not None
                and len(v.shape) >= 1 and int(v.shape[0]) == -1)


class PaddleTensor:
    """Loose analogue of paddle_api.h PaddleTensor (name + data)."""

    def __init__(self, data, name=None, lod=None):
        self.data = np.asarray(data)
        self.name = name
        self.lod = lod or []

    @property
    def shape(self):
        return self.data.shape


class NativeConfig:
    """reference paddle_api.h NativeConfig."""

    def __init__(self, model_dir=None, prog_file=None, param_file=None,
                 use_gpu=False, device=0):
        self.model_dir = model_dir
        self.prog_file = prog_file
        self.param_file = param_file
        self.use_gpu = use_gpu  # accepted for parity; backend is jax's
        self.device = device


class AnalysisConfig(NativeConfig):
    """reference analysis_predictor: adds graph rewrites + AOT compile."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.ir_optim = True
        self.aot_compile = True
        self.batch_size_buckets = (1, 2, 4, 8, 16, 32, 64, 128)


def _device_label(device):
    """Stable wire-encodable device id ('cpu:0', 'tpu:3' — or the
    '+'-joined member list 'tpu:0+tpu:1' for a mesh group) for metrics
    and the per-replica stats the serving layer surfaces; 'default' when
    the predictor floats on jax's default device.  Mesh labels parse
    back through `model_registry.resolve_placement`, which is what lets
    a persisted lane spec replay a mesh placement verbatim."""
    if device is None:
        return "default"
    group = _mesh_of(device)
    if group is not None:
        return group.label()
    return "%s:%d" % (getattr(device, "platform", "dev"),
                      getattr(device, "id", 0))


def _mesh_of(device):
    """The device as a MeshGroup, or None for a plain device."""
    from paddle_tpu.parallel.mesh import as_mesh_group
    return as_mesh_group(device)


def _put_state(state, device):
    """Place a param dict on its placement, once: plain device ->
    device_put (committed); None -> jax's default device, UNCOMMITTED
    (the "floating" default replica of `resolve_placement(1)`: resident,
    but pinned nowhere); mesh group -> every param SHARDED AT REST over
    the mesh (`MeshGroup.param_sharding` — per-device resident bytes ~
    1/mesh_size, the whole point of a mesh replica)."""
    import jax
    group = _mesh_of(device)
    if group is not None:
        return {n: jax.device_put(np.asarray(v),
                                  group.param_sharding(np.shape(v)))
                for n, v in state.items()}
    return {n: jax.device_put(np.asarray(v), device)
            for n, v in state.items()}


def _put_state_tp(state, group):
    """Tensor-parallel at-rest placement (SERVING.md "Tensor-parallel
    compute"): every NAMED decode parameter lands on the mesh axis its
    role in the partitioned program dictates (`MeshGroup.
    tp_param_sharding` — column weights split output columns, row
    weights split input rows, the embedding splits vocab rows) instead
    of `param_sharding`'s any-divisible-axis scan.  Resident bytes stay
    ~1/mesh_size like shard-at-rest; the difference is the compute
    consumes these shards IN PLACE — no gather per dispatch."""
    import jax
    return {n: jax.device_put(np.asarray(v),
                              group.tp_param_sharding(n, np.shape(v)))
            for n, v in state.items()}


def _put_feed(arr, device):
    """Commit one feed/arg to its placement (replicated on every mesh
    member — feeds are small; the sharded thing is the resident
    state)."""
    import jax
    group = _mesh_of(device)
    if group is not None:
        return jax.device_put(arr, group.replicated())
    return jax.device_put(arr, device)


def _mesh_wrap(math_fn, group, kv_outputs=False):
    """The mesh-replica compute contract (SERVING.md "Mesh replicas"):
    gather every operand back to REPLICATED before any math runs, so the
    traced computation is identical on every member and no float
    reduction ever reorders across devices — a mesh replica's output is
    bit-exact vs a single-device replica by construction (the
    weight-update-sharding blueprint: HBM shards, math does not).

    `kv_outputs=True` re-shards 4-D outputs (the decode KV slot tables
    [L, N, S, H * Dh], and a prefill's K/V rows) back to their at-rest
    `kv_sharding` before returning, so the
    session-resident cache stays ~1/mesh_size per device between
    dispatches; everything else returns replicated."""
    import jax

    def _rep(x):
        return jax.lax.with_sharding_constraint(x, group.replicated())

    def _out(x):
        if kv_outputs and getattr(x, "ndim", 0) == 4:
            return jax.lax.with_sharding_constraint(
                x, group.kv_sharding(x.shape))
        return _rep(x)

    def wrapped(state, *args):
        state = jax.tree_util.tree_map(_rep, state)
        args = jax.tree_util.tree_map(_rep, args)
        return jax.tree_util.tree_map(_out, math_fn(state, *args))

    return wrapped


def _mesh_wrap_tp(math_fn, group):
    """Partitioned-compute contract for PROGRAM predictors under
    `FLAGS.mesh_tp` (SERVING.md "Tensor-parallel compute"): instead of
    gathering operands to replicated, PIN the resident at-rest
    shardings on the state and let XLA's SPMD partitioner run the math
    over the shards — a contraction against a sharded weight computes
    on local columns/rows with the partitioner inserting the reduce,
    so weights never materialize unsharded and per-dispatch HBM
    traffic per member drops ~1/mesh_size.  Feeds and outputs stay
    replicated (the serving wire is host-side either way).  Outputs
    agree with a single-device replica at float tolerance, not
    bit-exactly (partitioned reductions reorder), which is exactly why
    the flag gates it; the decode path (inference/decode.py) carries
    the explicit shard_map'd program and the top-1 pins."""
    import jax

    def _rep(x):
        return jax.lax.with_sharding_constraint(x, group.replicated())

    def wrapped(state, *args):
        state = {n: jax.lax.with_sharding_constraint(
            x, group.param_sharding(np.shape(x)))
            for n, x in state.items()}
        args = jax.tree_util.tree_map(_rep, args)
        return jax.tree_util.tree_map(_rep, math_fn(state, *args))

    return wrapped


class Predictor:
    """`device`: optional jax.Device this predictor is pinned to — its
    params are `jax.device_put` there, feeds are committed there per
    run, and every bucket executable AOT-compiles for it.  The serving
    registry places one replica Predictor per device this way (SERVING.md
    multi-chip serving); None keeps jax's default-device behavior."""

    def __init__(self, config, device=None):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import functionalizer

        self._config = config
        self._scope = fluid.Scope()
        self._exe = fluid.Executor(
            fluid.TPUPlace(config.device) if _tpu_available()
            else fluid.CPUPlace())
        with fluid.scope_guard(self._scope):
            program, feed_names, fetch_vars = fluid.load_inference_model(
                config.model_dir, self._exe,
                model_filename=config.prog_file,
                params_filename=config.param_file)
            if isinstance(config, AnalysisConfig) and config.ir_optim:
                fluid.InferenceTranspiler().transpile(program,
                                                      scope=self._scope)
        from paddle_tpu.flags import FLAGS
        if FLAGS.verify_program:
            # load_inference_model already verified the artifact; this
            # re-checks AFTER the transpiler rewrites (BN fold, fusion)
            # — a buggy rewrite is exactly what the shape pass catches
            from paddle_tpu.analysis import check_program
            check_program(program, feeds=feed_names,
                          fetches=[v.name for v in fetch_vars],
                          what="predictor program (post-transpile)")
        self._program = program
        # the numerics lane this artifact serves (QUANTIZE.md): 'int8'
        # when the PTQ pass rewrote its contractions to dequant_* ops,
        # else 'fp32'.  Read from the program (not the dir) so clones
        # and registry replicas agree by construction.
        self._precision = "int8" if any(
            op.type.startswith("dequant_")
            for op in program.global_block().ops) else "fp32"
        self._feed_names = list(feed_names)
        self._fetch_names = [v.name for v in fetch_vars]
        self._fetch_vars = fetch_vars
        self._state_names = tuple(
            functionalizer.persistable_names(program))
        self._state = {n: self._scope.get(n) for n in self._state_names
                       if self._scope.get(n) is not None}
        self._device = device
        if device is not None:
            self._state = _put_state(self._state, device)
        self._compiled = {}  # feed shape signature -> compiled fn
        # serializes compile-and-cache and the overflow warn-once set:
        # concurrent dispatch lanes must neither double-compile one
        # bucket signature nor double-warn one overflow size
        self._lock = threading.Lock()
        # (device_kind, sig) -> jitted exported call, SHARED BY REFERENCE
        # across clone()/clone_to() replicas: N replicas of the same
        # device kind deserialize/export one executable, not N
        # (COMPILE_CACHE.md). _UNEXPORTABLE marks programs the export
        # path cannot serve (fall back to lower().compile() once, not
        # once per replica).
        self._shared_exports = {}
        self._shared_lock = threading.Lock()
        self._program_fp = None  # lazy sha256 of the transpiled program
        # batch-major markers from the program vars (-1 leading dim),
        # the same ground truth save_aot records in aot_meta.bin: only
        # these feeds get bucket-padded and only these fetches un-padded
        gb = program.global_block()
        self._batched_feed = {n: _var_is_batch_major(gb, n)
                              for n in self._feed_names}
        self._fetch_batched = [_var_is_batch_major(gb, n)
                               for n in self._fetch_names]
        self._overflow_warned = set()

    # ------------------------------------------------------------------
    def _device_kind(self):
        """Executable-compatibility label of this replica's target: two
        replicas with the same kind can share one AOT executable."""
        import jax
        d = self._device
        if d is None:
            devs = jax.devices()
            d = devs[0] if devs else None
        return "%s/%s" % (getattr(d, "platform", "cpu"),
                          getattr(d, "device_kind", ""))

    def _build_fwd(self, feed_names):
        from paddle_tpu.fluid import functionalizer
        step_fn = functionalizer.build_step_fn(
            self._program, tuple(feed_names),
            tuple(self._fetch_names), ())

        def fwd(state, feed_dict):
            fetches, _ = step_fn(state, feed_dict, np.uint32(0))
            return fetches

        group = _mesh_of(self._device)
        if group is not None:
            from paddle_tpu.flags import FLAGS
            if FLAGS.mesh_tp:
                return _mesh_wrap_tp(fwd, group)
            return _mesh_wrap(fwd, group)
        return fwd

    def _aot_fingerprint(self, feeds):
        from paddle_tpu import compile_cache as cc
        if self._program_fp is None:
            self._program_fp = cc.program_fingerprint(self._program)
        return {
            "kind": "predictor_aot",
            "program": self._program_fp,
            "feeds": cc._spec_sig(feeds),
            "fetches": list(self._fetch_names),
            "state": cc._spec_sig(self._state),
            "amp": _amp_enabled(),
            # the numerics lane is an explicit fingerprint field: an
            # int8 and an fp32 build of the same model must NEVER share
            # an executable, whatever else collides (COMPILE_CACHE.md)
            "precision": self._precision,
            "env": cc.environment_fingerprint(self._device),
        }

    def _get_aot_fn(self, sig, feeds):
        """Cached-executable resolution for the AnalysisConfig AOT path
        (called under self._lock).  Order: in-process shared map (one
        deserialize per device kind across all replica clones) -> the
        persistent store (hit: deserialize, no trace/lower) -> fresh
        export (miss: trace+lower once, serialize, commit).  Any failure
        returns None and the caller falls back to the legacy
        lower().compile() — the cache can only ever cost a recompile."""
        import time as _time
        import jax
        from paddle_tpu import compile_cache as cc
        if not cc.cache_enabled():
            return None
        if _mesh_of(self._device) is not None:
            # meshed replicas compile directly (lower().compile() against
            # the sharded state): a serialized export has no sharding in
            # its calling convention, so a cached single-device blob
            # would silently gather the whole model onto one member.
            # _device_kind carries a '/meshN' suffix, so nothing meshed
            # ever namespace-collides with a single-device executable.
            return None
        if self._device is not None and \
                self._device.platform != jax.default_backend():
            # cross-platform pinning (e.g. a cpu replica on a tpu host):
            # trace-time kernel dispatch follows the default backend, so
            # an export here could embed the wrong lowering — keep the
            # legacy per-device compile for this exotic case
            return None
        skey = (self._device_kind(), sig)
        with self._shared_lock:
            ent = self._shared_exports.get(skey)
        if ent is _UNEXPORTABLE:
            return None
        if ent is not None:
            return ent
        from jax import export as jax_export
        cache = cc.default_cache()
        fn = None
        try:
            fp = self._aot_fingerprint(feeds)
            blob = cache.get(fp) if cache is not None else None
            if blob is not None:
                try:
                    t0 = _time.monotonic()
                    exp = jax_export.deserialize(blob)
                    fn = jax.jit(exp.call)
                    cc.note_deserialize_ms(
                        (_time.monotonic() - t0) * 1000.0)
                except Exception:
                    blob = None  # truncated/alien entry: recompile
            if fn is None:
                t0 = _time.monotonic()
                fwd = self._build_fwd(sorted(feeds))
                state_spec = {
                    n: jax.ShapeDtypeStruct(np.shape(v), v.dtype)
                    for n, v in self._state.items()}
                feeds_spec = {
                    n: jax.ShapeDtypeStruct(np.shape(v), v.dtype)
                    for n, v in feeds.items()}
                exp = jax_export.export(jax.jit(fwd))(state_spec,
                                                      feeds_spec)
                cc.note_compile_ms((_time.monotonic() - t0) * 1000.0)
                if cache is not None:
                    cache.put(fp, exp.serialize())
                fn = jax.jit(exp.call)
        except Exception as e:
            with self._shared_lock:
                already = self._shared_exports.get(skey)
                self._shared_exports[skey] = _UNEXPORTABLE
            if already is not _UNEXPORTABLE:
                warnings.warn(
                    "compile cache disabled for this program (export "
                    "failed: %s: %s) — falling back to direct "
                    "compilation" % (type(e).__name__, e),
                    RuntimeWarning, stacklevel=3)
            return None
        with self._shared_lock:
            self._shared_exports[skey] = fn
        return fn

    def _get_compiled(self, feeds):
        import jax
        sig = tuple((n, feeds[n].shape, str(feeds[n].dtype))
                    for n in sorted(feeds))
        fn = self._compiled.get(sig)
        if fn is not None:
            return fn
        with self._lock:
            # re-check under the lock: another dispatch lane may have
            # compiled this signature while we waited — without the
            # recheck both lanes would pay the compile and the loser's
            # executable would be silently thrown away
            fn = self._compiled.get(sig)
            if fn is not None:
                return fn
            aot = isinstance(self._config, AnalysisConfig) and \
                self._config.aot_compile
            jitted = self._get_aot_fn(sig, feeds) if aot else None
            if jitted is None:
                jitted = jax.jit(self._build_fwd(sorted(feeds)))
                if aot:
                    # AOT: lower+compile now so first Run has no compile
                    # stall (the TRT build-engine-at-init analogue); with
                    # `self._state` committed to this replica's device,
                    # the executable compiles for that device
                    jitted = jitted.lower(self._state, feeds).compile()
            self._compiled[sig] = jitted
            return jitted

    def _bucket_cap(self, b):
        """Smallest configured batch bucket >= b, or None when bucketing
        is off (NativeConfig) or `b` overflows every bucket.  The
        overflow fall-through compiles a one-off computation per exact
        size — fine for a notebook, a recompile storm in serving — so it
        warns ONCE per overflow size, naming it."""
        if not isinstance(self._config, AnalysisConfig):
            return None
        buckets = self._config.batch_size_buckets
        for cap in buckets:
            if b <= cap:
                return cap
        if b not in self._overflow_warned:
            with self._lock:
                # re-check under the lock: concurrent dispatch lanes
                # racing the same overflow size must produce exactly one
                # warning, not one per lane
                if b in self._overflow_warned:
                    return None
                self._overflow_warned.add(b)
            warnings.warn(
                "batch %d exceeds every configured bucket %s on replica "
                "device [%s] — falling through to an unbucketed per-size "
                "compile; raise batch_size_buckets (or split the "
                "request) to avoid a recompile per distinct oversize "
                "batch in serving"
                % (b, tuple(buckets), _device_label(self._device)),
                RuntimeWarning, stacklevel=3)
        return None

    def _is_batched_feed(self, name):
        cached = self._batched_feed.get(name)
        if cached is None:
            cached = self._batched_feed[name] = _var_is_batch_major(
                self._program.global_block(), name)
        return cached

    def run(self, inputs):
        """inputs: dict name->array, list of PaddleTensor, or list of arrays
        (positional, matching the saved feed order). Returns list of numpy
        arrays in fetch order."""
        import jax.numpy as jnp
        from paddle_tpu.parallel.mesh import check_member_poison
        # a mesh replica dies whole: a lost member fails the dispatch
        # typed (MeshMemberLost) so the serving lane can mark itself
        # dead instead of wedging (chaos mesh-member-loss)
        check_member_poison(self._device)
        if isinstance(inputs, dict):
            named = {k: np.asarray(v) for k, v in inputs.items()}
        else:
            named = {}
            for i, t in enumerate(inputs):
                if isinstance(t, PaddleTensor):
                    named[t.name or self._feed_names[i]] = t.data
                else:
                    named[self._feed_names[i]] = np.asarray(t)

        # the batch is read from (and padding applied to) BATCH-MAJOR
        # feeds only — a fixed-shape side feed goes through untouched,
        # the same contract AotPredictor.run already enforces
        real_batch = next(
            (arr.shape[0] for name, arr in named.items()
             if arr.ndim >= 1 and self._is_batched_feed(name)), None)
        cap = self._bucket_cap(real_batch) if real_batch is not None \
            else None
        feeds = {}
        gb = self._program.global_block()
        for name, arr in named.items():
            v = gb._find_var_recursive(name)
            if v is not None and v.dtype is not None:
                want = v.np_dtype
                if arr.dtype != want:
                    arr = arr.astype(want)
            if cap is not None and cap > real_batch and \
                    self._is_batched_feed(name):
                pad = np.zeros((cap - real_batch,) + arr.shape[1:],
                               arr.dtype)
                arr = np.concatenate([arr, pad], axis=0)
            if self._device is not None:
                # commit the feed to this replica's device (replicated
                # across a mesh group) so the computation runs there,
                # not on jax's default device
                feeds[name] = _put_feed(arr, self._device)
            else:
                feeds[name] = jnp.asarray(arr)

        fn = self._get_compiled(feeds)
        fetches = fn(self._state, feeds)
        out = []
        for i, f in enumerate(fetches):
            a = np.asarray(f)
            # un-pad only batch-major fetches (program-var -1 leading
            # dim), never a global output whose leading dim happens to
            # equal the padded bucket
            batched = (i < len(self._fetch_batched)
                       and self._fetch_batched[i])
            if cap is not None and cap > real_batch and batched and \
                    a.ndim >= 1 and a.shape[0] == cap:
                a = a[:real_batch]
            out.append(a)
        return out

    # C++-API-shaped alias
    Run = run

    def clone(self):
        """reference PaddlePredictor::Clone — share weights, new exec state."""
        p = object.__new__(Predictor)
        p._config = self._config
        p._scope = self._scope
        p._exe = self._exe
        p._program = self._program
        p._precision = self._precision
        p._feed_names = list(self._feed_names)
        p._fetch_names = list(self._fetch_names)
        p._fetch_vars = self._fetch_vars
        p._state_names = self._state_names
        p._state = self._state
        p._device = self._device
        p._compiled = {}
        p._lock = threading.Lock()
        # shared BY REFERENCE: replicas of the same device kind reuse
        # one exported executable instead of re-tracing per clone
        p._shared_exports = self._shared_exports
        p._shared_lock = self._shared_lock
        p._program_fp = self._program_fp
        p._batched_feed = dict(self._batched_feed)
        p._fetch_batched = list(self._fetch_batched)
        p._overflow_warned = set()
        return p

    def clone_to(self, device):
        """Replica placement: a clone whose param copy lives on `device`
        and whose bucket executables compile for it.  The Program parse
        + InferenceTranspiler work is shared (done once at load); only
        the device commit and the per-device compile cache are new —
        this is how the serving registry builds N device-resident
        replicas from one artifact load."""
        p = self.clone()
        p._device = device
        if device is not None:
            p._state = _put_state(self._state, device)
        return p

    @property
    def device(self):
        """The jax.Device this predictor is pinned to, or None."""
        return self._device

    @property
    def precision(self):
        """The numerics lane this predictor serves: 'fp32' or 'int8'
        (the serving registry's precision axis, QUANTIZE.md)."""
        return self._precision

    def resource_report(self, batch=None):
        """Static ResourceReport of the program THIS predictor actually
        serves — post-transpile, so BN folds / fusions / the PTQ
        dequant rewrite are priced as they will run (sharper than
        analysis.analyze_artifact, which reads the artifact as saved).
        `batch` defaults to the largest configured bucket."""
        from paddle_tpu.analysis import analyze_program
        if batch is None:
            buckets = self.batch_buckets()
            batch = buckets[-1] if buckets else 1
        return analyze_program(self._program, feeds=self._feed_names,
                               fetches=self._fetch_names, batch=batch,
                               device=self._device,
                               what="predictor(%s)"
                                    % (self._config.model_dir,))

    # ------------------------------------------------------------------
    # serving introspection (paddle_tpu/serving): the batcher needs the
    # same three facts from a live Predictor and an AotPredictor — batch
    # buckets, feed specs, batch-major markers — in one shape.
    # ------------------------------------------------------------------

    def batch_buckets(self):
        """Sorted batch-size buckets this predictor pads requests into;
        () when bucketing is off (NativeConfig)."""
        if isinstance(self._config, AnalysisConfig):
            return tuple(sorted(self._config.batch_size_buckets))
        return ()

    def feed_specs(self):
        """name -> (shape list with -1 dynamic dims, dtype str)."""
        gb = self._program.global_block()
        out = {}
        for name in self._feed_names:
            v = gb._find_var_recursive(name)
            out[name] = ([int(d) for d in v.shape],
                         str(np.dtype(v.np_dtype)))
        return out

    def batched_feed_names(self):
        return frozenset(n for n in self._feed_names
                         if self._is_batched_feed(n))

    def fetch_batched_flags(self):
        return list(self._fetch_batched)


    # ------------------------------------------------------------------
    # AOT export (VERDICT r3 #8 — native-callable inference).
    #
    # Decision note: the reference exposes a C++ `PaddlePredictor`
    # (paddle_api.h:134) because its runtime IS C++. Here the compiled
    # artifact is an XLA executable; a C ABI would have to embed either a
    # Python interpreter or the PJRT C API + StableHLO deserializer —
    # disproportionate plumbing that re-wraps what jax.export already
    # standardizes. So the native-serving contract is: `save_aot` writes
    # the serialized StableHLO modules (jax.export, versioned+stable) +
    # weights + metadata in the no-pickle wire format; `load_aot_predictor`
    # in a FRESH process deserializes and serves with NO Program rebuild
    # and NO jax trace (XLA compiles the stored module directly). Any
    # PJRT-capable host — including a C++ one via the PJRT C API — can
    # consume the same artifact.
    # ------------------------------------------------------------------

    def save_aot(self, dirname, batch_sizes=(1,), platforms=None):
        """Export the inference computation for the given batch sizes so
        a new process can serve without rebuilding or retracing.

        `platforms` selects the artifact's target(s): ("tpu",) CROSS-
        COMPILES from a CPU build host with the real Mosaic kernels
        embedded; ("cpu", "tpu") embeds both lowerings in one artifact
        but only for Pallas-free programs (jax lowers every
        platform_dependent branch on every platform when the platform
        index is dynamic, and Pallas has no non-interpret CPU
        lowering). Default: the current platform only."""
        import os
        import jax
        import jax.numpy as jnp
        from jax import export as jax_export
        from paddle_tpu.fluid import functionalizer
        from paddle_tpu.native import wire

        os.makedirs(dirname, exist_ok=True)
        if isinstance(platforms, str):
            # list("tpu") would become ['t','p','u'] and fail far away
            platforms = (platforms,)
        gb = self._program.global_block()
        feed_specs = {}
        for name in self._feed_names:
            v = gb._find_var_recursive(name)
            shape = [int(d) for d in v.shape]
            feed_specs[name] = (shape, str(np.dtype(v.np_dtype)))

        step_fn = functionalizer.build_step_fn(
            self._program, tuple(sorted(self._feed_names)),
            tuple(self._fetch_names), ())

        def fwd(state, feed_dict):
            fetches, _ = step_fn(state, feed_dict, np.uint32(0))
            return fetches

        state_spec = {n: jax.ShapeDtypeStruct(np.shape(v),
                                              np.asarray(v).dtype)
                      for n, v in self._state.items()}
        for name, (shape, dt) in feed_specs.items():
            if any(d == -1 for d in shape[1:]):
                # same guard as train_export.save_aot_trainer: a
                # non-leading dynamic dim silently frozen to the batch
                # size would produce an artifact that rejects every
                # differently-shaped request at serve time
                raise ValueError(
                    "feed %r has non-batch dynamic dims %s — AOT export "
                    "needs static non-batch shapes" % (name, shape))
        exports = {}
        for bs in batch_sizes:
            feeds_spec = {}
            for name, (shape, dt) in feed_specs.items():
                s = [bs if d == -1 else d for d in shape]
                feeds_spec[name] = jax.ShapeDtypeStruct(
                    tuple(s), np.dtype(dt))
            from paddle_tpu.ops.pallas_kernels import mosaic_lowering
            # a pure-TPU target embeds the real Mosaic kernels even from
            # a CPU build host; any cpu target keeps interpret emulation
            with mosaic_lowering(bool(platforms)
                                 and "tpu" in platforms
                                 and "cpu" not in platforms):
                exp = jax_export.export(
                    jax.jit(fwd),
                    platforms=list(platforms) if platforms else None)(
                    state_spec, feeds_spec)
            fname = "aot_b%d.bin" % bs
            with open(os.path.join(dirname, fname), "wb") as f:
                f.write(exp.serialize())
            exports[str(bs)] = fname

        with open(os.path.join(dirname, "aot_state.bin"), "wb") as f:
            f.write(wire.encode({n: np.asarray(v)
                                 for n, v in self._state.items()}))
        # which fetches are batch-major (program var has a -1 leading
        # dim): only those get un-padded at serve time — a global output
        # whose leading dim merely EQUALS the padded bucket must come
        # back whole
        fetch_batched = []
        for name in self._fetch_names:
            v = gb._find_var_recursive(name)
            fetch_batched.append(
                bool(v is not None and v.shape is not None
                     and len(v.shape) >= 1 and int(v.shape[0]) == -1))
        meta = {
            "feed_names": list(self._feed_names),
            "fetch_names": list(self._fetch_names),
            "feed_specs": {n: {"shape": list(s), "dtype": d}
                           for n, (s, d) in feed_specs.items()},
            "fetch_batched": fetch_batched,
            "exports": exports,
            "platform": jax.default_backend(),
        }
        with open(os.path.join(dirname, "aot_meta.bin"), "wb") as f:
            f.write(wire.encode(meta))
        return dirname


class AotPredictor:
    """Serve a `save_aot` artifact: no Program, no trace — the stored
    StableHLO modules are deserialized and compiled directly by XLA.

    `device`: optional jax.Device to pin this instance to (state +
    per-run feeds committed there) — the replica-per-device serving
    placement; `clone_to` shares the deserialized modules across
    replicas so only the first replica pays the artifact read."""

    def __init__(self, dirname, device=None):
        import os
        from jax import export as jax_export
        from paddle_tpu.native import wire
        from paddle_tpu import compile_cache as cc

        # the artifact IS a pre-serialized AOT cache; with jax's
        # persistent cache placed, even the first .call per bucket can
        # skip the XLA compile on a warm boot (counted as
        # artifact_loads, not hits — the hit/miss ratio stays about
        # the fingerprint store)
        cc.ensure_jax_cache()

        with open(os.path.join(dirname, "aot_meta.bin"), "rb") as f:
            meta = wire.decode(f.read())
        with open(os.path.join(dirname, "aot_state.bin"), "rb") as f:
            self._state = wire.decode(f.read())
        self._feed_names = list(meta["feed_names"])
        self._fetch_names = list(meta["fetch_names"])
        self._feed_specs = meta["feed_specs"]
        self._fetch_batched = meta.get("fetch_batched")
        self._fns = {}
        for bs, fname in sorted(meta["exports"].items(),
                                key=lambda kv: int(kv[0])):
            with open(os.path.join(dirname, fname), "rb") as f:
                self._fns[int(bs)] = jax_export.deserialize(
                    f.read()).call
        cc.note_artifact_load(len(self._fns))
        device = _aot_degrade_mesh(device)
        self._device = device
        if device is not None:
            import jax
            self._state = {n: jax.device_put(np.asarray(v), device)
                           for n, v in self._state.items()}

    def run(self, inputs):
        import jax.numpy as jnp
        if isinstance(inputs, dict):
            named = {k: np.asarray(v) for k, v in inputs.items()}
        else:
            named = {}
            for i, t in enumerate(inputs):
                if isinstance(t, PaddleTensor):
                    named[t.name or self._feed_names[i]] = t.data
                else:
                    named[self._feed_names[i]] = np.asarray(t)
        # the batch is read from (and padding applied to) BATCH-MAJOR
        # feeds only — those whose recorded var shape leads with -1; a
        # fixed-shape side feed must go through untouched
        batched_feed = {n: bool(spec["shape"]
                                and int(spec["shape"][0]) == -1)
                        for n, spec in self._feed_specs.items()}
        b = next((arr.shape[0] for name, arr in named.items()
                  if batched_feed.get(name)), None)
        if b is None:
            b = next(iter(named.values())).shape[0]
        cap = next((c for c in self._fns if c >= b), None)
        if cap is None:
            raise ValueError(
                "batch %d exceeds every exported batch size %s"
                % (b, sorted(self._fns)))
        feeds = {}
        for name, arr in named.items():
            want = np.dtype(self._feed_specs[name]["dtype"])
            if arr.dtype != want:
                arr = arr.astype(want)
            if cap > b and batched_feed.get(name):
                arr = np.concatenate(
                    [arr, np.zeros((cap - b,) + arr.shape[1:],
                                   arr.dtype)], axis=0)
            if self._device is not None:
                import jax
                feeds[name] = jax.device_put(arr, self._device)
            else:
                feeds[name] = jnp.asarray(arr)
        fetches = self._run_export(cap, feeds)
        out = []
        for i, f in enumerate(fetches):
            a = np.asarray(f)
            # un-pad only fetches the artifact marked batch-major — a
            # reduced/global output whose leading dim coincidentally
            # equals the padded bucket must come back whole. Artifacts
            # predating the marker fall back to the shape heuristic.
            if self._fetch_batched is not None:
                batched = (i < len(self._fetch_batched)
                           and self._fetch_batched[i])
            else:
                batched = a.ndim >= 1 and a.shape[0] == cap
            if cap > b and batched and a.ndim >= 1 and a.shape[0] == cap:
                a = a[:b]
            out.append(a)
        return out

    Run = run

    def _run_export(self, cap, feeds):
        """One seam around the stored executable call (tests inject
        slow/faulty models here without touching the jax.export path)."""
        return self._fns[cap](self._state, feeds)

    def clone_to(self, device):
        """Replica placement: share the deserialized StableHLO modules,
        re-commit the state copy to `device`."""
        import jax
        device = _aot_degrade_mesh(device)
        p = object.__new__(AotPredictor)
        p._feed_names = list(self._feed_names)
        p._fetch_names = list(self._fetch_names)
        p._feed_specs = self._feed_specs
        p._fetch_batched = self._fetch_batched
        p._fns = self._fns
        p._device = device
        if device is not None:
            p._state = {n: jax.device_put(np.asarray(v), device)
                        for n, v in self._state.items()}
        else:
            p._state = self._state
        return p

    @property
    def device(self):
        return self._device

    @property
    def precision(self):
        """AOT artifacts are exported from the fp32 path today; the
        attribute exists so the serving registry's precision axis reads
        one surface across predictor types."""
        return "fp32"

    # ---- serving introspection (mirrors Predictor's) ----

    def batch_buckets(self):
        return tuple(sorted(self._fns))

    def feed_specs(self):
        return {n: (list(spec["shape"]), str(spec["dtype"]))
                for n, spec in self._feed_specs.items()}

    def batched_feed_names(self):
        return frozenset(
            n for n, spec in self._feed_specs.items()
            if spec["shape"] and int(spec["shape"][0]) == -1)

    def fetch_batched_flags(self):
        if self._fetch_batched is None:
            return None  # pre-marker artifact: scatter falls back to shape
        return list(self._fetch_batched)


def load_aot_predictor(dirname):
    """Open a `Predictor.save_aot` artifact (fresh-process serving)."""
    return AotPredictor(dirname)


def _tpu_available():
    import jax
    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except RuntimeError:
        return False


def create_paddle_predictor(config):
    """reference CreatePaddlePredictor (api_impl.cc:304)."""
    return Predictor(config)
