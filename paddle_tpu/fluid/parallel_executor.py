"""ParallelExecutor — multi-chip data-parallel training.

Reference analogue: python/paddle/fluid/parallel_executor.py:32 wrapping C++
ParallelExecutor (parallel_executor.cc:69): per-device scopes, NCCLContextMap,
multi_devices_pass cloning ops per device + inserting ncclAllReduce handles
(details/all_reduce_op_handle.cc:48), ThreadedSSAGraphExecutor.

TPU redesign (SURVEY.md §2.10 row 1): the multi-device SSA graph is replaced
by ONE jitted step over a jax.sharding.Mesh — feeds are sharded on the batch
axis, parameters are replicated, and XLA's SPMD partitioner inserts the grad
all-reduce over ICI exactly where the reference's multi_devices_pass inserted
NCCL op handles. BuildStrategy/ExecutionStrategy are kept as first-class
config objects (pybind.cc:685,:772) — most knobs are advisory because the
compiler owns scheduling, but reduce-strategy and num-threads map to
sharding/compiler choices.

Param broadcast at construction (BCastParamsToDevices, parallel_executor.cc
:200) becomes re-device_put of scope arrays with a replicated sharding.
"""

import os
import time

import numpy as np

from . import core
from .executor import (global_scope, as_numpy, _fetch_name,
                       _stamp_dispatched, _stamp_returned)
from .pipeline import FetchFuture
from .framework import default_main_program
from . import functionalizer
from ..parallel.mesh import data_parallel_mesh, DATA_AXIS
from ..obs import tracing as obs_tracing

__all__ = ["ParallelExecutor", "ExecutionStrategy", "BuildStrategy"]


class ExecutionStrategy:
    """reference details/execution_strategy.h. Scheduling is XLA's job; these
    knobs are accepted for API parity and used where meaningful."""

    def __init__(self):
        self.num_threads = 0
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 100
        self.use_experimental_executor = False


class BuildStrategy:
    """reference details/build_strategy.h:95."""

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = \
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.debug_graphviz_path = ""
        self.enable_data_balance = False
        self.memory_optimize = False
        self.fuse_elewise_add_act_ops = False  # XLA fuses anyway


class ParallelExecutor:
    def __init__(self, use_cuda=True, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None,
                 build_strategy=None, num_trainers=1, trainer_id=0,
                 scope=None, mesh=None):
        import jax
        from .. import compile_cache
        compile_cache.ensure_jax_cache()
        self._main_program = main_program if main_program is not None \
            else default_main_program()
        self._scope = scope if scope is not None else global_scope()
        if share_vars_from is not None:
            self._scope = share_vars_from._scope
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._build_strategy = build_strategy or BuildStrategy()
        self._loss_name = loss_name
        # multi-host ("nccl2") data parallelism: after the startup
        # program's gen_collective_id has run jax.distributed.initialize,
        # jax.devices() spans every trainer process and the mesh below is
        # the cross-node NCCLContextMap analogue (nccl_helper.h:82,
        # parallel_executor.cc:113). Feeds stay process-local; run()
        # assembles them into global arrays.
        self._num_trainers = int(num_trainers or 1)
        self._trainer_id = int(trainer_id or 0)
        if self._num_trainers > 1:
            if jax.process_count() != self._num_trainers:
                raise RuntimeError(
                    "ParallelExecutor(num_trainers=%d) but the collective "
                    "world has %d processes — run gen_collective_id (the "
                    "collective-mode transpiler emits it into the startup "
                    "program) or set PADDLE_COORDINATOR before first "
                    "device use" % (self._num_trainers,
                                    jax.process_count()))
            if jax.process_index() != self._trainer_id:
                raise RuntimeError(
                    "trainer_id=%d does not match collective process "
                    "index %d" % (self._trainer_id, jax.process_index()))
            if mesh is None:
                from ..parallel.mesh import make_mesh
                devs = jax.devices()
                mesh = make_mesh({DATA_AXIS: len(devs)}, devs)
        self._mesh = mesh if mesh is not None else \
            data_parallel_mesh(use_cuda=use_cuda)
        self._num_devices = int(np.prod(list(self._mesh.shape.values())))
        self._cache = {}
        self._host_ops_flag = {}  # program version -> has host ops
        self._step = 0
        # BuildStrategy pass pipeline (reference build_strategy.cc:27
        # ParallelExecutorPassBuilder chains passes before graph build)
        from . import ir_passes
        if self._build_strategy.fuse_elewise_add_act_ops:
            ir_passes.get_pass("fuse_elewise_add_act_pass").apply(
                self._main_program)
        self._apply_gradient_scale_strategy()
        if self._build_strategy.debug_graphviz_path:
            ir_passes.get_pass(
                "graph_viz_pass",
                graph_viz_path=self._build_strategy.debug_graphviz_path
            ).apply(self._main_program)
        # BCastParamsToDevices analogue: replicate existing scope arrays
        self._replicate_state()

    def _apply_gradient_scale_strategy(self):
        """reference details/build_strategy.h:55 GradientScaleStrategy +
        scale_loss_grad_op_handle: how the loss-gradient seed relates to
        the device count.

        - CoeffNumDevice (default): each device seeds 1/num_devices and
          grads SUM-reduce — identical to this build's global formulation
          (one SPMD step over the global batch, loss already a global
          mean), so nothing changes.
        - One: each device seeds 1.0 and grads sum — net effect is grads
          num_devices x larger; encoded by rewriting the backward
          fill_constant seed (backward.py appends fill_constant(1) for
          <loss>@GRAD) to num_devices.
        - Customized: per-device user-supplied seeds have no analogue in
          the single-global-computation design — rejected explicitly.
        """
        strat = self._build_strategy.gradient_scale_strategy
        if strat == BuildStrategy.GradientScaleStrategy.CoeffNumDevice:
            return
        if strat == BuildStrategy.GradientScaleStrategy.Customized:
            raise NotImplementedError(
                "GradientScaleStrategy.Customized: supply a custom loss "
                "scale by scaling the loss itself (the SPMD step is one "
                "global computation; there is no per-device seed to feed)")
        if self._loss_name is None:
            return
        from .framework import grad_var_name
        target = grad_var_name(self._loss_name)
        for op in self._main_program.global_block().ops:
            if op.type == "fill_constant" and \
                    op.outputs.get("Out", [None])[0] == target:
                if not op.attrs.get("@grad_scale_applied"):
                    op.attrs["value"] = float(op.attrs.get("value", 1.0)) \
                        * self._num_devices
                    op.attrs["@grad_scale_applied"] = True
                    self._main_program._bump_version()
                break

    @property
    def device_count(self):
        return self._num_devices

    @property
    def mesh(self):
        """The jax.sharding.Mesh this executor shards over — handed to
        reader.prefetch_to_device(mesh=...) so the prefetch thread
        commits pre-sharded feeds (the sharded-prefetch pipeline mode,
        PIPELINE.md)."""
        return self._mesh

    def _replicated_sharding(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self._mesh, P())

    def _batch_sharding(self, ndim):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self._mesh,
                             P(DATA_AXIS, *([None] * (ndim - 1))))

    def _put(self, arr, sharding):
        """Place a process-local array under `sharding`. Across processes
        this is the BCast/split analogue: every process contributes its
        addressable shards (full array when replicated, the local batch
        shard when batch-sharded)."""
        import jax
        if self._num_trainers > 1:
            return jax.make_array_from_process_local_data(sharding, arr)
        return jax.device_put(arr, sharding)

    def _replicate_state(self):
        rep = self._replicated_sharding()
        for name in functionalizer.persistable_names(self._main_program):
            val = self._scope.get(name)
            if val is not None:
                self._scope.set(name, self._put(np.asarray(val), rep))

    def _get_jitted(self, feed_key, fetch_names, state_names):
        import jax
        from ..ops.registry import amp_enabled
        wga, remat = functionalizer.flags_ad_config()
        key = (feed_key, fetch_names, tuple(state_names),
               self._main_program._version, amp_enabled(), wga, remat)
        fn = self._cache.get(key)
        if fn is not None:
            return fn
        step_fn = functionalizer.build_step_fn(
            self._main_program, feed_key, fetch_names, state_names,
            mesh=self._mesh, whole_graph_ad=wga, remat_policy=remat)
        rep = self._replicated_sharding()

        def wrapped(state, feeds, step):
            return step_fn(state, feeds, step)

        donate = (0,) if any(d.platform == "tpu"
                             for d in self._mesh.devices.flat) else ()
        fn = jax.jit(wrapped, donate_argnums=donate,
                     out_shardings=None)
        self._cache[key] = fn
        return fn

    def _prepare_feeds(self, feed, feed_dict=None):
        """Merge per-device feed lists, then run the Executor's shared
        feed preparation (dtype casts; LoDTensor -> padded dense +
        @LOD_LEN companions) and shard every batch-dim array on the
        mesh's data axis."""
        import jax.numpy as jnp
        from .executor import prepare_feeds
        if feed is None:
            feed = feed_dict
        if feed is None:
            feed = {}
        if isinstance(feed, (list, tuple)):
            from .lod import LoDTensor
            merged = {}
            for k in feed[0]:
                vals = [d[k] for d in feed]
                if any(isinstance(v, LoDTensor) and v.lod() for v in vals):
                    # merge data AND lod — np.concatenate alone would
                    # strip the ragged structure via __array__: per
                    # level, sequence lengths concatenate (each level's
                    # offsets index rows of the next, and concatenation
                    # preserves that nesting)
                    if not all(isinstance(v, LoDTensor) and v.lod()
                               for v in vals):
                        raise ValueError(
                            "feed '%s': mixed LoDTensor and dense "
                            "entries across devices" % k)
                    depth = len(vals[0].lod())
                    if any(len(v.lod()) != depth for v in vals):
                        raise ValueError(
                            "feed '%s': inconsistent LoD depth across "
                            "devices" % k)
                    t = LoDTensor(np.concatenate(
                        [v.numpy() for v in vals], axis=0))
                    t.set_recursive_sequence_lengths(
                        [sum((v.recursive_sequence_lengths()[lv]
                              for v in vals), [])
                         for lv in range(depth)])
                    merged[k] = t
                else:
                    merged[k] = np.concatenate(
                        [np.asarray(v) for v in vals], axis=0)
            feed = merged
        import jax
        dense = prepare_feeds(self._main_program, feed, device_put=False)
        feeds = {}
        for name, arr in dense.items():
            if arr.ndim == 0:
                feeds[name] = jnp.asarray(arr)
                continue
            # @LOD_LEN/@LOD_SEG companions are batch-dim vectors and
            # shard with their payload. jax.Array feeds (PyReader
            # double-buffer) go straight to the sharded device_put —
            # no host round-trip — except in multi-trainer mode, where
            # make_array_from_process_local_data wants host data.
            target = self._batch_sharding(arr.ndim)
            if isinstance(arr, jax.Array):
                if arr.sharding == target:
                    # sharded prefetch (prefetch_to_device mesh mode)
                    # already committed this array on the mesh — the
                    # whole point is skipping the per-dispatch commit
                    feeds[name] = arr
                    continue
                if self._num_trainers > 1:
                    arr = np.asarray(arr)
            feeds[name] = self._put(arr, target)
        return feeds

    def run_loop(self, fetch_list, feed=None, steps=1, return_numpy=True):
        """`steps` SPMD training steps as ONE device computation — the
        multi-chip analogue of Executor.run_loop: lax.fori_loop over the
        mesh-sharded jitted step with a constant sharded feed, one
        dispatch per `steps` steps. Gradient all-reduces stay inside the
        single XLA computation, so a pod iterates without any host
        involvement between steps."""
        import jax
        import jax.numpy as jnp
        traced = obs_tracing.enabled()
        if traced:
            t_in = time.monotonic()
        steps = int(steps)
        if steps < 1:
            raise ValueError("run_loop: steps must be >= 1")
        from ..flags import FLAGS
        if FLAGS.check_nan_inf:
            raise RuntimeError(
                "run_loop: FLAGS.check_nan_inf needs per-op attribution, "
                "which requires per-step execution — use "
                "ParallelExecutor.run")
        if FLAGS.verify_program:
            from ..analysis import verify_program_cached
            verify_program_cached(
                self._main_program,
                feeds=sorted(feed) if isinstance(feed, dict) else None,
                fetches=[_fetch_name(f) for f in fetch_list],
                what="parallel executor run_loop program")
        hkey = self._main_program._version
        if self._host_ops_flag.get(hkey) is None:
            self._host_ops_flag[hkey] = \
                functionalizer.contains_host_ops(self._main_program)
        if self._host_ops_flag[hkey]:
            raise RuntimeError(
                "run_loop: the program contains host ops and cannot run "
                "as one device computation — use ParallelExecutor.run")
        fetch_names = tuple(_fetch_name(f) for f in fetch_list)
        feeds = self._prepare_feeds(feed)
        if traced:
            t_fed, n_built = time.monotonic(), len(self._cache)
        feed_key = tuple(sorted(feeds.keys()))
        persistables = tuple(
            functionalizer.persistable_names(self._main_program))
        from ..ops.registry import amp_enabled
        wga, remat = functionalizer.flags_ad_config()
        key = ("loop", feed_key, fetch_names, persistables,
               self._main_program._version, amp_enabled(), wga, remat)
        fn = self._cache.get(key)
        if fn is None:
            step_fn = functionalizer.build_step_fn(
                self._main_program, feed_key, fetch_names, persistables,
                mesh=self._mesh, whole_graph_ad=wga, remat_policy=remat)
            fn = functionalizer.jit_loop(
                step_fn, any(d.platform == "tpu"
                             for d in self._mesh.devices.flat))
            self._cache[key] = fn
        state_in = {n: self._scope.get(n) for n in persistables
                    if self._scope.get(n) is not None}
        fetches, new_state = fn(state_in, feeds,
                                np.uint32(self._step), np.int32(steps))
        if traced:
            t_called = time.monotonic()
            step_attr = _stamp_dispatched(
                t_in, t_fed, t_called, feed or {}, feeds, state_in,
                self._step, len(self._cache) > n_built)
        self._step += steps
        for n, val in new_state.items():
            self._scope.set(n, val)
        # one batched device->host copy for the whole fetch list —
        # a per-item np.asarray loop would serialize the transfers
        out = jax.device_get(list(fetches)) if return_numpy \
            else list(fetches)
        if traced:
            _stamp_returned(t_in, t_called, step_attr, steps, "jit", out)
        return out

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True,
            as_future=False):
        """reference parallel_executor.py:169. `feed` may be one dict (full
        global batch, split across devices — the reference's split path) or a
        list of per-device dicts (concatenated here, then sharded). In
        nccl2 multi-trainer mode each array is this trainer's LOCAL
        batch; the global array spans num_trainers x local (the
        reference's per-trainer reader semantics).

        `as_future=True` dispatches the SPMD step without resolving:
        the FetchFuture keeps the fetches as live (sharded) device
        arrays and the host sync is deferred to `.result()` — same
        in-flight contract as Executor.run (PIPELINE.md)."""
        traced = obs_tracing.enabled()
        if traced:
            t_in = time.monotonic()
        fetch_names = tuple(_fetch_name(f) for f in fetch_list)
        from ..flags import FLAGS
        if FLAGS.verify_program:
            from ..analysis import verify_program_cached
            verify_program_cached(
                self._main_program,
                feeds=sorted(feed) if isinstance(feed, dict) else None,
                fetches=fetch_names, what="parallel executor program")
        feeds = self._prepare_feeds(feed, feed_dict)
        if traced:
            t_fed, n_built = time.monotonic(), len(self._cache)
        feed_key = tuple(sorted(feeds.keys()))

        persistables = tuple(
            functionalizer.persistable_names(self._main_program))
        fn = self._get_jitted(feed_key, fetch_names, persistables)
        state_in = {n: self._scope.get(n) for n in persistables
                    if self._scope.get(n) is not None}
        fetches, new_state = fn(state_in, feeds, np.uint32(self._step))
        if traced:
            t_called = time.monotonic()
            step_attr = _stamp_dispatched(
                t_in, t_fed, t_called,
                feed if feed is not None else feed_dict or {}, feeds,
                state_in, self._step, len(self._cache) > n_built)
        self._step += 1
        for n, val in new_state.items():
            self._scope.set(n, val)
        if as_future:
            out = FetchFuture(fetches, return_numpy=return_numpy,
                              what="parallel executor step drain")
        elif return_numpy:
            # one batched device->host copy for the whole fetch list —
            # per-item np.asarray would serialize the gathers
            import jax
            out = jax.device_get(list(fetches))
        else:
            out = list(fetches)
        if traced:
            _stamp_returned(t_in, t_called, step_attr, 1, "jit",
                            None if as_future else out)
        return out
