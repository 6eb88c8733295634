"""Executor + Scope.

Reference analogues: python/paddle/fluid/executor.py:256 (Executor: program
cache, feed/fetch, as_numpy :66, scope_guard :47) over C++
framework/executor.cc:183 (Executor::Run) and scope.h:41 (Scope).

TPU redesign: `run(program, feed, fetch_list)` functionalizes the block
(functionalizer.py), jits it once per (program version, feed signature,
fetch list) and replays the compiled XLA computation per step — the analogue
of the reference's ExecutorPrepareContext cache (executor.py:207) where the
cached object is a compiled HLO module instead of an op list. Parameters and
other persistable variables live in the Scope as jax Arrays and are threaded
through the jitted step functionally; on TPU the state buffers are donated so
updates are in-place at the XLA level.
"""

import threading
import time
import warnings

import numpy as np

from . import core
from .framework import Program, Variable, default_main_program
from . import functionalizer
from .pipeline import FetchFuture
from ..obs import tracing as obs_tracing

__all__ = ["Executor", "Scope", "global_scope", "scope_guard", "as_numpy",
           "StepWatchdogTimeout", "FetchFuture"]


class StepWatchdogTimeout(TimeoutError):
    """An executor step exceeded FLAGS.step_watchdog_secs of wall clock.
    The backend may be wedged (a hung dispatch blocks jax inside C
    forever); the hung dispatch keeps its worker thread, but the
    train loop gets an exception it can act on instead of hanging."""


def _watchdog_call(call, timeout, what="executor step"):
    """Run `call` on a worker thread and give up after `timeout` seconds
    (a hung XLA dispatch cannot be interrupted from Python, but it CAN be
    abandoned).  Zero overhead path is the caller's: only invoked when
    the watchdog flag is set."""
    box = {}
    done = threading.Event()

    def _worker():
        try:
            box["value"] = call()
        except BaseException as e:
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=_worker, daemon=True,
                         name="paddle-tpu-step-watchdog")
    t.start()
    if not done.wait(timeout):
        from ..obs import events as _obs_events
        from ..obs import flightrec as _obs_flightrec
        _obs_events.emit("watchdog_fire", what=str(what),
                         budget_s=round(float(timeout), 3))
        # a wedged backend is exactly what the flight recorder exists
        # for: the bundle's thread stacks show WHERE the abandoned
        # dispatch thread is stuck (no-op while FLAGS.flight_dir unset)
        _obs_flightrec.trigger("watchdog_fire", what=str(what),
                               budget_s=round(float(timeout), 3))
        raise StepWatchdogTimeout(
            "%s still running after %.1fs (FLAGS.step_watchdog_secs) — "
            "backend wedged or step pathologically slow; the dispatch "
            "thread is abandoned" % (what, timeout))
    if "error" in box:
        raise box["error"]
    return box.get("value")


class _TensorView:
    """Mimics fluid's `scope.find_var(name).get_tensor()` protocol."""

    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def get_tensor(self):
        return self._scope._vars[self._name]

    def set(self, value, place=None):
        import jax.numpy as jnp
        self._scope._vars[self._name] = jnp.asarray(value)


class Scope:
    """name -> device array map (reference scope.h:41). Mostly flat — the
    reference's parent-scope chain existed for per-op temporary locals,
    which the functional executor doesn't materialize — but `new_scope`
    keeps the kid-scope contract: reads fall through to the parent,
    writes stay local (scope.cc Scope::NewScope + parent lookup)."""

    def __init__(self):
        self._vars = {}
        self._parent = None
        self._kids = []

    def new_scope(self):
        """Create a kid scope (reference pybind Scope.new_scope —
        API.spec:412)."""
        kid = Scope()
        kid._parent = self
        self._kids.append(kid)
        return kid

    def var(self, name):
        if name not in self._vars:
            self._vars[name] = None
        return _TensorView(self, name)

    def find_var(self, name):
        if name in self._vars:
            return _TensorView(self, name)
        if self._parent is not None:
            return self._parent.find_var(name)
        return None

    def has(self, name):
        return name in self._vars or \
            (self._parent is not None and self._parent.has(name))

    def get(self, name):
        if name in self._vars:
            return self._vars[name]
        if self._parent is not None:
            return self._parent.get(name)
        return None

    def set(self, name, value):
        self._vars[name] = value

    def drop_kids(self):
        self._kids = []

    def keys(self):
        return self._vars.keys()


_global_scope = Scope()


class _ScopeStack(threading.local):
    """Per-thread scope stack rooted at the process-wide global scope —
    concurrent executors (pserver thread + trainer threads, reference
    test_dist_base style) must not see each other's scope_guard pushes."""

    def __init__(self):
        self.stack = [_global_scope]


_scope_stack_tls = _ScopeStack()


def global_scope():
    return _scope_stack_tls.stack[-1]


class scope_guard:
    def __init__(self, scope):
        self._scope = scope

    def __enter__(self):
        _scope_stack_tls.stack.append(self._scope)
        return self._scope

    def __exit__(self, *args):
        _scope_stack_tls.stack.pop()


def as_numpy(tensor):
    """reference executor.py:66"""
    if isinstance(tensor, (list, tuple)):
        return [as_numpy(t) for t in tensor]
    return np.asarray(tensor)


def _fetch_name(f):
    if isinstance(f, Variable):
        return f.name
    if isinstance(f, str):
        return f
    raise TypeError("bad fetch entry: %r" % (f,))


def _check_nan_inf(fetch_names, fetches, new_state):
    """FLAGS.check_nan_inf step-boundary check (reference operator.cc:29
    per-op check; eagerly-run host-op programs get per-op attribution in
    functionalizer._run_forward_op instead)."""
    bad = []
    for name, val in list(zip(fetch_names, fetches)) + \
            sorted(new_state.items()):
        if val is None:
            continue
        arr = np.asarray(val)
        if arr.dtype.kind == "f" and not np.all(np.isfinite(arr)):
            bad.append(name)
    if bad:
        raise FloatingPointError(
            "check_nan_inf: non-finite values in: %s (enable "
            "jax_debug_nans or run the program eagerly for per-op "
            "attribution)" % ", ".join(bad))


def prepare_feeds(program, feed, device_put=True):
    """numpy -> device arrays with var dtype; LoDTensor (ragged) feeds
    become padded [B, T, ...] + <name>@LOD_LEN lengths, with T bucketed
    to a power of two to bound recompiles. Shared by Executor and
    ParallelExecutor; the latter passes device_put=False so values stay
    host-side (or on their original device for jax.Array feeds) and the
    ONLY transfer is the sharded device_put over the mesh — committing
    a pod-global batch to device 0 first could OOM it."""
    import jax
    import jax.numpy as jnp
    put = jnp.asarray if device_put else np.asarray
    gb = program.global_block()
    feeds = {}
    for name, value in feed.items():
        v = gb._find_var_recursive(name)
        from .lod import LoDTensor, pad_lod_feed
        if isinstance(value, LoDTensor) and value.lod():
            padded, lengths, seg = pad_lod_feed(value)
            if v is not None and v.dtype is not None:
                want = core.convert_dtype_to_np(v.dtype)
                if padded.dtype != want and not (
                        padded.dtype.kind in "iu" and want.kind in "iu"):
                    padded = padded.astype(want)
            feeds[name] = put(padded)
            feeds[name + functionalizer.LOD_LEN_SUFFIX] = put(lengths)
            if seg is not None:
                feeds[name + functionalizer.LOD_SEG_SUFFIX] = put(seg)
            continue
        if isinstance(value, jax.Array):
            # already on device (PyReader double-buffer path) — do NOT
            # round-trip through numpy, that would force D2H + H2D
            arr = value
            if v is not None and v.dtype is not None:
                want = core.convert_dtype_to_np(v.dtype)
                if arr.dtype != want and not (
                        np.dtype(arr.dtype).kind in "iu"
                        and want.kind in "iu"):
                    arr = arr.astype(want)
            feeds[name] = arr
            continue
        arr = np.asarray(value)
        if v is not None and v.dtype is not None:
            want = core.convert_dtype_to_np(v.dtype)
            if arr.dtype != want and not (
                    arr.dtype.kind in "iu" and want.kind in "iu"):
                arr = arr.astype(want)
        feeds[name] = put(arr)
    return feeds


def _feed_bytes(feed, feeds):
    """(h2d_bytes, cast_bytes) of one prepared feed, for the
    `executor/feed` span: the bytes of the values that arrived as host
    arrays (what the feed uploads; a jax.Array feed counts 0), and of
    those the bytes that went through an `astype` on the host first."""
    import jax
    if isinstance(feed, (list, tuple)):     # per-device dicts, all host
        feed = {k: None for d in feed for k in d}
    h2d = cast = 0
    for name, value in feed.items():
        if isinstance(value, jax.Array):
            continue
        for key in (name, name + functionalizer.LOD_LEN_SUFFIX,
                    name + functionalizer.LOD_SEG_SUFFIX):
            if key in feeds:
                h2d += int(feeds[key].nbytes)
        src = getattr(value, "dtype", None)
        if src is not None and name in feeds \
                and np.dtype(src) != feeds[name].dtype:
            cast += int(feeds[name].nbytes)
    return h2d, cast


def _stamp_dispatched(t_in, t_fed, t_called, feed, feeds, state_in, step,
                      compiled):
    """`executor/feed` and `executor/dispatch` of one call, landed as soon
    as the jitted call has returned: the device is working then, so the
    counting (the feed's bytes, a pass over the state for leaves still on
    the host) costs the step nothing.  Returns the `step` its spans carry
    (the enclosing train span's, else the executor's own)."""
    step = obs_tracing.inherited("step", step)
    h2d, cast = _feed_bytes(feed, feeds)
    obs_tracing.stamp("executor/feed", t_in, t_fed, kind="train",
                      parent="executor/run", step=step, h2d_bytes=h2d,
                      cast_bytes=cast)
    obs_tracing.stamp(
        "executor/dispatch", t_fed, t_called, kind="train",
        parent="executor/run", step=step, compiled=int(compiled),
        state_host_bytes=sum(
            int(v.nbytes) for v in state_in.values()
            if isinstance(v, (np.ndarray, np.generic))))
    return step


def _stamp_returned(t_in, t_called, step, steps, path, out):
    """`executor/fetch` and the `executor/run` root, from the same
    contiguous stamps as `_stamp_dispatched` (entry, feed prepared, call
    returned, now) so that the children tile the root exactly.  `out` is
    what the call returns, or None for a dispatch whose fetch is left to a
    FetchFuture (no `executor/fetch` then)."""
    t_out = time.monotonic()
    if out is not None:
        obs_tracing.stamp(
            "executor/fetch", t_called, t_out, kind="train",
            parent="executor/run", step=step, d2h_bytes=sum(
                int(v.nbytes) for v in out if isinstance(v, np.ndarray)))
    obs_tracing.stamp("executor/run", t_in, t_out, kind="train", step=step,
                      steps=steps, path=path)


class Executor:
    """reference executor.py:256. `place` selects the jax backend; under jit
    there is no per-op placement, so CPUPlace/TPUPlace only choose where the
    compiled computation and the Scope arrays live."""

    def __init__(self, place=None):
        self.place = place if place is not None else core.TPUPlace(0)
        # trainers ride jax's persistent compilation cache too: a cold
        # process pays a whole-model XLA compile otherwise
        from .. import compile_cache
        compile_cache.ensure_jax_cache()
        self._cache = {}  # key -> jitted (or eager host-path) fn
        self._step_counters = {}  # program cache id -> step
        self._host_op_cache = {}  # (id, version) -> program has host ops

    def _device(self):
        return self.place.jax_device()

    def close(self):
        # reference: notifies pservers a trainer is leaving; collective-DP
        # TPU path has no pserver connection to close by default.
        self._cache.clear()

    def _get_jitted(self, program, feed_names, fetch_names, state_names):
        import jax
        from ..ops.registry import amp_enabled
        wga, remat = functionalizer.flags_ad_config()
        key = (id(program), program._version, feed_names, fetch_names,
               tuple(state_names), amp_enabled(), wga, remat)
        fn = self._cache.get(key)
        if fn is None:
            step_fn = functionalizer.build_step_fn(
                program, feed_names, fetch_names, state_names,
                whole_graph_ad=wga, remat_policy=remat)
            donate = (0,) if self._device().platform == "tpu" else ()
            fn = jax.jit(step_fn, donate_argnums=donate)
            self._cache[key] = fn
        return fn

    def _aot_cache_eligible(self, program):
        """True when the program is inference-shaped — single block, no
        *_grad ops, no optimizer ops (host ops are excluded by the
        caller's branch) — so its executable is a pure function of the
        Program content and safe to reuse from the persistent compile
        cache (COMPILE_CACHE.md; gated by FLAGS.executor_compile_cache).
        Memoized per (program identity, version)."""
        key = ("aot_ok", id(program), program._version)
        cached = self._host_op_cache.get(key)
        if cached is None:
            cached = len(program.blocks) == 1
            if cached:
                from ..ops.optimizer_ops import MERGEABLE_OPT_OPS
                opt = frozenset(MERGEABLE_OPT_OPS)
                for op in program.blocks[0].ops:
                    if op.type.endswith("_grad") or op.type in opt:
                        cached = False
                        break
            self._host_op_cache[key] = cached
        return cached

    def _get_aot_cached(self, program, feed_key, fetch_ext, persistables,
                        state_in, feeds):
        """Persistent-cache resolution for the jitted executor step:
        fingerprint the Program content + feed/state specs, deserialize
        a stored executable on a hit, export+commit on a miss.  Returns
        the step fn or None (caller falls back to _get_jitted) — the
        cache can only ever cost a recompile, never a failure."""
        import time as _time
        import jax
        from jax import export as jax_export
        from paddle_tpu import compile_cache as cc
        from ..ops.registry import amp_enabled
        if not cc.cache_enabled() or not self._aot_cache_eligible(program):
            return None
        dev = self._device()
        if dev.platform != jax.default_backend():
            return None
        wga, remat = functionalizer.flags_ad_config()
        sig = tuple((n, np.shape(v), str(np.asarray(v).dtype))
                    for n, v in sorted(feeds.items()))
        ssig = tuple((n, np.shape(v), str(v.dtype))
                     for n, v in sorted(state_in.items()))
        mkey = ("aotcc", id(program), program._version, sig, ssig,
                fetch_ext, persistables, amp_enabled(), wga, remat)
        fn = self._cache.get(mkey)
        if fn is False:
            return None
        if fn is not None:
            return fn
        try:
            fp = {
                "kind": "executor_step",
                "program": cc.program_fingerprint(program),
                "feeds": [[n, list(s), d] for n, s, d in sig],
                "state": [[n, list(s), d] for n, s, d in ssig],
                "fetches": list(fetch_ext),
                "persistables": list(persistables),
                "amp": bool(amp_enabled()),
                "wga": bool(wga),
                "remat": remat or "",
                "env": cc.environment_fingerprint(dev),
            }
            cache = cc.default_cache()
            blob = cache.get(fp) if cache is not None else None
            if blob is not None:
                try:
                    t0 = _time.monotonic()
                    fn = jax.jit(jax_export.deserialize(blob).call)
                    cc.note_deserialize_ms(
                        (_time.monotonic() - t0) * 1000.0)
                except Exception:
                    blob = None
            if blob is None:
                t0 = _time.monotonic()
                step_fn = functionalizer.build_step_fn(
                    program, feed_key, fetch_ext, persistables,
                    whole_graph_ad=wga, remat_policy=remat)
                f_spec = {n: jax.ShapeDtypeStruct(np.shape(v),
                                                  np.asarray(v).dtype)
                          for n, v in feeds.items()}
                s_spec = {n: jax.ShapeDtypeStruct(np.shape(v), v.dtype)
                          for n, v in state_in.items()}
                exp = jax_export.export(jax.jit(step_fn))(
                    s_spec, f_spec,
                    jax.ShapeDtypeStruct((), np.uint32))
                cc.note_compile_ms((_time.monotonic() - t0) * 1000.0)
                if cache is not None:
                    cache.put(fp, exp.serialize())
                fn = jax.jit(exp.call)
        except Exception:
            # ineligible in practice (host callback, exotic lowering):
            # remember per signature and fall back silently
            self._cache[mkey] = False
            return None
        self._cache[mkey] = fn
        return fn

    def _host_ops_cached(self, program):
        """(contains_host_ops, has_subblock_host_ops) memoized per
        (program identity, version)."""
        hkey = (id(program), program._version)
        cached = self._host_op_cache.get(hkey)
        if cached is None:
            cached = (functionalizer.contains_host_ops(program),
                      functionalizer.has_subblock_host_ops(program))
            self._host_op_cache[hkey] = cached
        return cached

    def _prepare_feeds(self, program, feed):
        return prepare_feeds(program, feed)

    @staticmethod
    def _dispatch(call, watchdog_secs, what="executor step"):
        """Run one device dispatch, under the wall-clock watchdog when
        FLAGS.step_watchdog_secs is set.  The watchdog forces a
        block_until_ready inside the watched call — async dispatch would
        otherwise return before the hang."""
        if watchdog_secs and watchdog_secs > 0:
            def _synced():
                import jax
                out = call()
                jax.block_until_ready(out)
                return out
            return _watchdog_call(_synced, watchdog_secs, what)
        return call()


    def run_loop(self, program=None, feed=None, fetch_list=None,
                 steps=1, scope=None, return_numpy=True):
        """Run `steps` training steps as ONE device computation — a
        lax.fori_loop over the jitted step body with a constant feed —
        and return the LAST step's fetches. The TPU-idiomatic device-side
        loop: one host->device dispatch per `steps` steps instead of per
        step, so throughput is not bounded by host round-trips
        (reference analogue: the while_op + reader-op training loops that
        kept the GPU busy without per-step feeds, fluid_benchmark.py
        --use_reader_op).

        The per-op RNG streams still fold the step counter, so dropout
        masks differ across iterations exactly as under run(). Programs
        containing host ops cannot run as one computation and are
        rejected loudly.
        """
        import jax
        import jax.numpy as jnp
        traced = obs_tracing.enabled()
        if traced:
            t_in = time.monotonic()
        if program is None:
            program = default_main_program()
        if feed is None:
            feed = {}
        if fetch_list is None:
            fetch_list = []
        if scope is None:
            scope = global_scope()
        steps = int(steps)
        if steps < 1:
            raise ValueError("run_loop: steps must be >= 1")
        from ..flags import FLAGS
        if FLAGS.verify_program:
            from ..analysis import verify_program_cached
            verify_program_cached(
                program, feeds=sorted(feed),
                fetches=[_fetch_name(f) for f in fetch_list],
                what="executor run_loop program")
        if FLAGS.check_nan_inf:
            raise RuntimeError(
                "run_loop: FLAGS.check_nan_inf needs per-op attribution, "
                "which requires per-step execution — use Executor.run")
        if self._host_ops_cached(program)[0]:
            raise RuntimeError(
                "run_loop: the program contains host ops (RPC/IO/python "
                "callbacks) and cannot run as one device computation — "
                "use Executor.run per step")

        fetch_names = tuple(_fetch_name(f) for f in fetch_list)
        feeds = self._prepare_feeds(program, feed)
        if traced:
            t_fed, n_built = time.monotonic(), len(self._cache)
        feed_key = tuple(sorted(feeds.keys()))
        lod_fetch = tuple(n + functionalizer.LOD_LEN_SUFFIX
                          for n in fetch_names)
        seg_fetch = tuple(n + functionalizer.LOD_SEG_SUFFIX
                          for n in fetch_names)
        fetch_ext = fetch_names + lod_fetch + seg_fetch
        persistables = tuple(functionalizer.persistable_names(program))
        state_in = {n: scope.get(n) for n in persistables
                    if scope.has(n) and scope.get(n) is not None}
        step0 = self._step_counters.get(id(program), 0)

        from ..ops.registry import amp_enabled
        wga, remat = functionalizer.flags_ad_config()
        key = ("loop", id(program), program._version, feed_key, fetch_ext,
               persistables, amp_enabled(), wga, remat)
        fn = self._cache.get(key)
        if fn is None:
            step_fn = functionalizer.build_step_fn(
                program, feed_key, fetch_ext, persistables,
                whole_graph_ad=wga, remat_policy=remat)
            fn = functionalizer.jit_loop(
                step_fn, self._device().platform == "tpu")
            self._cache[key] = fn
        # watchdog budget scales with the loop length: wd secs per step
        fetches, new_state = self._dispatch(
            lambda: fn(state_in, feeds, np.uint32(step0), np.int32(steps)),
            FLAGS.step_watchdog_secs * steps,
            "run_loop dispatch (%d steps)" % steps)
        # only a successful dispatch advances the counter — a build or
        # compile failure must not skew the RNG step fold for later runs
        self._step_counters[id(program)] = step0 + steps
        if traced:
            t_called = time.monotonic()
            step_attr = _stamp_dispatched(
                t_in, t_fed, t_called, feed, feeds, state_in, step0,
                len(self._cache) > n_built)
        if FLAGS.benchmark:
            jax.block_until_ready((fetches, new_state))
        for n, val in new_state.items():
            scope.set(n, val)
        out = self._post_fetches(fetch_names, lod_fetch, seg_fetch,
                                 fetches, return_numpy)
        if traced:
            _stamp_returned(t_in, t_called, step_attr, steps, "jit", out)
        return out

    def run(self, program=None, feed=None, fetch_list=None,
            feed_var_name="feed", fetch_var_name="fetch", scope=None,
            return_numpy=True, use_program_cache=True, as_future=False):
        """One training/eval step.  With `as_future=True` the step is
        DISPATCHED but not resolved: the return value is a FetchFuture
        holding the fetches as live device arrays, and the host sync
        (one batched jax.device_get) happens when the caller drains it
        via `.result()` — the in-flight dispatch mode of the async
        training pipeline (PIPELINE.md).  State updates land in the
        scope immediately as (unresolved) device arrays, so back-to-back
        dispatches chain on device without host round-trips.  Paths
        that are inherently synchronous (FLAGS.check_nan_inf, host-op
        programs, FLAGS.benchmark) still honor the contract by
        returning an already-resolved future."""
        traced = obs_tracing.enabled()
        if traced:
            t_in = time.monotonic()
        if program is None:
            program = default_main_program()
        if feed is None:
            feed = {}
        if fetch_list is None:
            fetch_list = []
        if scope is None:
            scope = global_scope()

        fetch_names = tuple(_fetch_name(f) for f in fetch_list)

        feeds = self._prepare_feeds(program, feed)
        if traced:
            t_fed, n_built = time.monotonic(), len(self._cache)
        feed_key = tuple(sorted(feeds.keys()))

        # for ragged fetches, also fetch the companion lengths (present in
        # env only when the value is actually ragged; None otherwise)
        lod_fetch = tuple(n + functionalizer.LOD_LEN_SUFFIX
                          for n in fetch_names)
        seg_fetch = tuple(n + functionalizer.LOD_SEG_SUFFIX
                          for n in fetch_names)
        fetch_ext = fetch_names + lod_fetch + seg_fetch

        # output state covers ALL persistables (startup programs create
        # params that are not yet in the scope); input state is whatever
        # already exists. The jit signature keys on the input dict structure.
        persistables = tuple(functionalizer.persistable_names(program))
        has_host, has_sub_host = self._host_ops_cached(program)
        hkey = (id(program), program._version)
        from ..flags import FLAGS
        if FLAGS.verify_program:
            # opt-in pre-run verification (ANALYSIS.md): memoized per
            # (program version, feeds, fetches) — the analysis runs at
            # build time, every later step costs one dict hit
            from ..analysis import verify_program_cached
            verify_program_cached(program, feeds=sorted(feed),
                                  fetches=fetch_names,
                                  what="executor program")
        state_in = {n: scope.get(n) for n in persistables
                    if scope.has(n) and scope.get(n) is not None}
        step = self._step_counters.get(id(program), 0)
        self._step_counters[id(program)] = step + 1

        if FLAGS.check_nan_inf or (has_host and has_sub_host):
            # Fully-eager interpretation, two cases:
            # (a) check_nan_inf debugging mode: every op's output is
            #     concrete so the first non-finite op is NAMED (reference
            #     FLAGS_check_nan_inf, operator.cc:29, per-op-sync cost);
            # (b) host ops buried in control-flow sub-blocks — they cannot
            #     be partitioned out at block-0 boundaries, so the whole
            #     block is interpreted (host ops see concrete values).
            ekey = ("eager", hkey, feed_key, fetch_ext, persistables)
            fn = self._cache.get(ekey)
            if fn is None:
                fn = functionalizer.build_step_fn(
                    program, feed_key, fetch_ext, persistables)
                self._cache[ekey] = fn
            fetches, new_state = self._dispatch(
                lambda: fn(state_in, feeds, np.uint32(step)),
                FLAGS.step_watchdog_secs, "eager executor step")
            path = "eager"
        elif has_host:
            # RPC / IO host ops do side effects, but the compute BETWEEN
            # them still runs from the XLA jit cache: the segmented runner
            # partitions the block at HOST_OPS boundaries (SURVEY §7 step
            # 3), jits each compute segment, and interprets host ops
            # eagerly in order (reference: ListenAndServOp/save_op kernels
            # ran on CPU between device kernels).
            runner = self._cache.get(("seg", hkey))
            if runner is None:
                runner = functionalizer.SegmentedProgramRunner(program)
                self._cache[("seg", hkey)] = runner
            env = {}
            env.update(state_in)
            env.update(feeds)
            self._dispatch(
                lambda: runner.run(env, np.uint32(step),
                                   fetch_names=fetch_ext),
                FLAGS.step_watchdog_secs, "segmented executor step")
            fetches = [env.get(n) for n in fetch_ext]
            new_state = {n: env[n] for n in persistables if n in env}
            path = "segmented"
        else:
            fn = None
            path = "aot"
            if FLAGS.executor_compile_cache:
                # inference-side persistent compile cache (opt-in): a
                # program whose fingerprint derives from its content
                # rides a stored executable across processes
                fn = self._get_aot_cached(program, feed_key, fetch_ext,
                                          persistables, state_in, feeds)
            if fn is None:
                path = "jit"
                fn = self._get_jitted(program, feed_key, fetch_ext,
                                      persistables)
            # in-flight mode: the dispatch is non-blocking by design and
            # the watchdog wraps the DRAIN (FetchFuture.result) instead
            # of forcing a block_until_ready inside every dispatch
            wd = 0 if as_future else FLAGS.step_watchdog_secs
            fetches, new_state = self._dispatch(
                lambda: fn(state_in, feeds, np.uint32(step)),
                wd, "jitted executor step")
        if traced:
            t_called = time.monotonic()
            step_attr = _stamp_dispatched(
                t_in, t_fed, t_called, feed, feeds, state_in, step,
                len(self._cache) > n_built)
        if FLAGS.benchmark:
            # reference FLAGS_benchmark: force device sync per step so
            # wall-clock timing around run() is honest (scope.cc:25)
            import jax as _jax
            _jax.block_until_ready((fetches, new_state))
        if FLAGS.check_nan_inf:
            _check_nan_inf(fetch_names, fetches, new_state)
        for n, val in new_state.items():
            scope.set(n, val)
        if as_future:
            post = (lambda vals, rn: self._post_fetches(
                fetch_names, lod_fetch, seg_fetch, vals, rn))
            fut = FetchFuture(fetches, post=post,
                              return_numpy=return_numpy,
                              what="executor step drain")
            if FLAGS.benchmark or FLAGS.check_nan_inf:
                # these modes already forced per-step sync semantics —
                # hand back a resolved future so the caller's drain is
                # a no-op rather than a second conversion site
                fut.result()
            out = fut
        else:
            out = self._post_fetches(fetch_names, lod_fetch, seg_fetch,
                                     fetches, return_numpy)
        if traced:
            _stamp_returned(t_in, t_called, step_attr, 1, path,
                            None if as_future else out)
        return out

    @staticmethod
    def _post_fetches(fetch_names, lod_fetch, seg_fetch, fetches,
                      return_numpy):
        """Reassemble fetched values; ragged ones (with @LOD_LEN
        companions) become LoDTensors, nested levels from @LOD_SEG.
        The device->host copy is ONE batched jax.device_get over every
        fetch of the step, not a per-item np.asarray loop — serial
        transfers cost a host round-trip each."""
        if return_numpy and any(f is not None for f in fetches):
            import jax
            fetches = jax.device_get(list(fetches))
        n_names = len(fetch_names)
        lens_by_name = dict(zip(lod_fetch,
                                fetches[n_names:n_names + len(lod_fetch)]))
        segs_by_name = dict(zip(seg_fetch,
                                fetches[n_names + len(lod_fetch):]))
        out = []
        for i, n in enumerate(fetch_names):
            val = fetches[i]
            lens = lens_by_name.get(n + functionalizer.LOD_LEN_SUFFIX)
            if lens is not None and val is not None:
                from .lod import unpad_to_lod_tensor
                t = unpad_to_lod_tensor(np.asarray(val), np.asarray(lens))
                seg = segs_by_name.get(n + functionalizer.LOD_SEG_SUFFIX)
                if seg is not None:
                    # nested: prepend the outer level — the companion IS
                    # the per-group inner-sequence counts
                    outer = [int(c) for c in np.asarray(seg)]
                    t.set_recursive_sequence_lengths(
                        [outer] + t.recursive_sequence_lengths())
                out.append(t)
            elif return_numpy:
                out.append(np.asarray(val))
            else:
                out.append(val)
        return out

    def segmented_runner(self, program):
        """The SegmentedProgramRunner used for `program` (None if the
        program has no host ops or hasn't run yet). Exposes cache_hits /
        cache_misses / num_compute_segments for observability + tests."""
        return self._cache.get(("seg", (id(program), program._version)))

    # ---- parity shims used by reference scripts ----
    def _run_startup(self, startup_program, scope=None):
        self.run(program=startup_program, scope=scope)
