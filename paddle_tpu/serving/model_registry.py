"""Named, versioned model registry with device-placed replicas and
atomic hot swap.

The multi-tenant half of the serving runtime: each model name maps to
versioned entries — now each entry holding N device-resident replica
predictors fronted by one DynamicBatcher whose router fans coalesced
micro-batch groups to the least-loaded replica lane; requests route
through a `latest` pointer.

Placement spec (`resolve_placement`): `FLAGS.serving_replicas` or a
per-load override — an int N (round-robin over local devices; 1 keeps
the single default-device replica), 'auto' (one replica per local
device — the whole-host serving shape), or an explicit device list
('0,2' local indices / 'cpu:0,tpu:3' platform:index / jax.Device
objects).  Each replica's params are `jax.device_put` on its assigned
device and its batch buckets compile and WARM there, so the first real
request on any replica runs at steady-state latency.

A hot swap follows the same commit discipline as the checkpoint vault
(fluid/checkpoint.py), extended per replica set: build ALL new replicas
completely — load artifact, clone+place per device, construct batcher,
warm every bucket on every replica — then flip `latest` under the
routing lock, and only afterwards drain and retire the displaced
replica set.  A request that resolved the old version before the flip
completes on whichever old replica its group was routed to (the drain
waits); a request after the flip runs the new set; no request is
dropped or answered twice.

Artifact detection: a directory containing `aot_meta.bin` is a
`save_aot` artifact (AotPredictor — no Program rebuild, no trace); any
other directory is treated as a `save_inference_model` dir served by a
live `Predictor` under `AnalysisConfig` (IR rewrites + AOT jit compile,
bucketed).
"""

import os
import threading
import time

import numpy as np

from ..flags import FLAGS
from ..obs import events as obs_events
from .batcher import DecodeBatcher, DynamicBatcher
from .metrics import ServingMetrics

__all__ = ["ModelRegistry", "ModelEntry", "open_predictor",
           "resolve_placement"]


def _pack_mesh_spec(s):
    """'mesh:N' / 'mesh:RxC' as the WHOLE placement spec: pack as many
    disjoint consecutive N-device (R*C-device) groups as the host's
    local devices allow — each group one logical mesh replica.  A
    1-device mesh is just the legacy one-replica-per-device shape."""
    import jax
    from ..parallel.mesh import MeshGroup
    body = s.split(":", 1)[1].strip()
    try:
        dims = tuple(int(p) for p in body.split("x")) if "x" in body \
            else (int(body),)
    except ValueError:
        raise ValueError(
            "bad mesh placement %r — expected 'mesh:N' or 'mesh:RxC'"
            % s)
    g = 1
    for d in dims:
        if d < 1:
            raise ValueError(
                "bad mesh placement %r — dimensions must be >= 1" % s)
        g *= d
    local = list(jax.local_devices())
    if g == 1:
        return list(local)
    n_groups = len(local) // g
    if n_groups < 1:
        raise ValueError(
            "mesh placement %r needs %d devices per replica, host has "
            "%d local device(s)" % (s, g, len(local)))
    return [MeshGroup(local[i * g:(i + 1) * g], dims)
            for i in range(n_groups)]


def resolve_placement(spec=None):
    """Turn a replica placement spec into a list of jax.Device /
    MeshGroup (or [None] for the single default-device replica).

    spec: None -> FLAGS.serving_replicas; int or digit-string N -> N
    replicas round-robin over jax.local_devices() (N == 1 -> [None],
    the pre-multichip single-replica behavior on the default device);
    'auto' -> one replica per local device; a comma list / sequence of
    local indices ('0,2'), 'platform:index' names ('cpu:0', 'tpu:3'),
    or jax.Device objects -> exactly those devices.

    Mesh replicas (SERVING.md "Mesh replicas"): 'mesh:N' / 'mesh:RxC'
    as the WHOLE spec packs the host into as many disjoint consecutive
    N-device groups as fit, each group ONE logical replica sharding
    the model across its members; '+'-joined members inside a list
    element ('tpu:0+tpu:1' or '0+1') place one explicit mesh replica
    and compose freely with plain elements.  A 1-member group
    collapses to the plain device.  A device may belong to at most one
    mesh group and never doubles as a plain replica — overlap is a
    placement error (plain single-device duplicates stay allowed: they
    multiply the fit estimate, not the sharding)."""
    import jax
    from ..parallel.mesh import MeshGroup
    if spec is None:
        spec = FLAGS.serving_replicas
    if isinstance(spec, (list, tuple)):
        local = list(jax.local_devices())
        by_key = {(d.platform, d.id): d for d in local}

        def one(tok):
            if hasattr(tok, "platform") and hasattr(tok, "id") \
                    and not isinstance(tok, str):
                return tok  # already a jax.Device
            t = str(tok).strip()
            if ":" in t:
                plat, _, idx = t.partition(":")
                dev = by_key.get((plat.strip(), int(idx)))
                if dev is None:
                    raise ValueError(
                        "no local device %r (have %s)" % (
                            t, sorted("%s:%d" % k for k in by_key)))
                return dev
            i = int(t)
            if i >= len(local):
                raise ValueError(
                    "device index %d out of range: %d local "
                    "device(s)" % (i, len(local)))
            return local[i]

        def key_of(d):
            return (getattr(d, "platform", None), getattr(d, "id", None))

        devs = []
        mesh_keys = set()   # devices claimed by a mesh group
        plain_keys = set()  # devices used as plain replicas
        for item in spec:
            if isinstance(item, MeshGroup):
                members = list(item.devices)
            elif not isinstance(item, str) and \
                    hasattr(item, "platform") and hasattr(item, "id"):
                members = [item]
            else:
                s = str(item).strip()
                if not s:
                    continue
                if s.startswith("mesh:"):
                    raise ValueError(
                        "'mesh:N' packs the WHOLE host and cannot be "
                        "combined with other placement elements — use "
                        "explicit '+'-joined groups (e.g. 'tpu:0+"
                        "tpu:1,tpu:2+tpu:3') to mix")
                members = [one(t) for t in s.split("+") if t.strip()]
            if not members:
                continue
            if len(members) == 1:
                dev = members[0]
                k = key_of(dev)
                if k in mesh_keys:
                    raise ValueError(
                        "device %s:%s is a mesh-group member and "
                        "cannot double as a plain replica" % k)
                plain_keys.add(k)
                devs.append(dev)
                continue
            keys = [key_of(d) for d in members]
            for k in keys:
                if k in mesh_keys or k in plain_keys:
                    raise ValueError(
                        "device %s:%s already placed — mesh-group "
                        "members must be exclusive" % k)
            mesh_keys.update(keys)
            devs.append(item if isinstance(item, MeshGroup)
                        else MeshGroup(members))
        if not devs:
            raise ValueError("empty replica device list")
        return devs
    if isinstance(spec, str):
        s = spec.strip()
        if s == "auto":
            return list(jax.local_devices())
        if s.startswith("mesh:") and "," not in s:
            return _pack_mesh_spec(s)
        if "," in s or ":" in s or "+" in s:
            return resolve_placement(
                [p for p in s.split(",") if p.strip()])
        spec = int(s)
    n = int(spec)
    if n < 1:
        raise ValueError("replica count must be >= 1, got %d" % n)
    if n == 1:
        # the pre-multichip contract: one replica floating on jax's
        # default device (uncommitted state, no forced transfers)
        return [None]
    local = list(jax.local_devices())
    return [local[i % len(local)] for i in range(n)]


def open_predictor(path, buckets=None, device=None,
                   kv_cache_dtype=None):
    """Open a serving artifact directory as the right predictor type,
    optionally pinned to `device` (a jax.Device).  Detection: a
    `decode_meta.bin` dir is an autoregressive decode artifact
    (GenerativePredictor — continuous-batching generation); an
    `aot_meta.bin` dir a save_aot artifact; anything else a
    save_inference_model dir.  `kv_cache_dtype` (decode artifacts
    only) overrides the artifact's KV-cache numerics pin
    (QUANTIZE.md "Quantized KV cache")."""
    from ..inference import AnalysisConfig, Predictor, AotPredictor
    from ..inference.decode import DECODE_META, GenerativePredictor
    if os.path.exists(os.path.join(path, DECODE_META)):
        return GenerativePredictor(path, device=device,
                                   kv_cache_dtype=kv_cache_dtype)
    if os.path.exists(os.path.join(path, "aot_meta.bin")):
        return AotPredictor(path, device=device)
    if not os.path.isdir(path):
        raise FileNotFoundError("no model artifact directory at %r" % path)
    config = AnalysisConfig(model_dir=path)
    if buckets:
        config.batch_size_buckets = tuple(sorted(int(b) for b in buckets))
    return Predictor(config, device=device)


def _build_replicas(path, buckets, devices, kv_cache_dtype=None):
    """One artifact load + (N-1) clone_to placements: the Program parse
    / StableHLO deserialize happens once, each replica gets its own
    device-committed param copy and compile cache."""
    first = open_predictor(path, buckets=buckets, device=devices[0],
                           kv_cache_dtype=kv_cache_dtype)
    preds = [first]
    for dev in devices[1:]:
        preds.append(first.clone_to(dev))
    return preds


class ModelEntry:
    """One (name, version): its replica predictors (device-placed), the
    batcher fronting them, and its path.  `predictor` stays the first
    replica — the introspection surface (buckets, feed specs) is
    identical across replicas by construction."""

    def __init__(self, name, version, path, predictor, batcher,
                 replicas=None, devices=None, precision="fp32",
                 resource=None, draft_path=None):
        self.name = name
        self.version = version
        self.path = path
        self.predictor = predictor
        self.batcher = batcher
        self.replicas = list(replicas) if replicas else [predictor]
        self.devices = list(devices) if devices else [None]
        # the numerics lane this version serves (QUANTIZE.md): 'int8'
        # for a PTQ artifact, 'fp32' otherwise — the axis the router
        # splits on and the metrics lane files under
        self.precision = str(precision or "fp32")
        # what THIS build+warm cost against the persistent compile
        # cache (compile_cache.stats_delta, set by load_model): a warm
        # flip shows misses == 0 — zero fresh compilations
        self.compile_cache = {}
        # the static ResourceReport the admission fit check ran on
        # (ANALYSIS.md) — what describe()/stats/Prometheus expose so a
        # fleet controller can place by cost; None when the artifact
        # could not be analyzed
        self.resource = resource
        # speculative decoding (SERVING.md): the draft artifact this
        # entry's lanes draft with, or None for target-only decode
        self.draft_path = draft_path

    def device_labels(self):
        from ..inference.predictor import _device_label
        return [_device_label(d) for d in self.devices]

    def mesh_sizes(self):
        """Members per replica, in route order: 1 for a plain device,
        N for a MeshGroup (SERVING.md "Mesh replicas")."""
        from ..parallel.mesh import as_mesh_group
        return [g.mesh_size if (g := as_mesh_group(d)) is not None
                else 1 for d in self.devices]

    @property
    def is_decode(self):
        return bool(getattr(self.predictor, "is_decode", False))

    def warm(self):
        """Run one zero dummy batch per bucket DIRECTLY on EVERY
        replica predictor (not through the batcher — warming must not
        mix with traffic).  After this, every bucket's executable is
        compiled/loaded on every replica's device and the first real
        request at any size on any lane runs at steady-state latency.
        The hot-swap commit discipline hinges on this covering the
        whole replica set BEFORE the `latest` flip.

        Decode models warm BOTH phases: every prompt-bucket prefill
        plus the fixed-shape slot-table decode step (one executable
        for every window of trips the lane dispatches), on a scratch
        session per replica (the lane sessions share the resolved
        executables, so the first real stream pays no compile), and
        every bucket's GROUP prefill a lane of this many slots can call
        (`DecodeBatcher._prefill_calls`), with the write that lands it."""
        if self.is_decode:
            from ..inference.decode import STEP_WINDOW, prefill_group
            n_slots = self.batcher.n_slots
            spec_k = getattr(self.batcher, "spec_k", 0)
            drafts = getattr(self.batcher, "draft_replicas", None)
            for i, pred in enumerate(self.replicas):
                sess = pred.new_session(n_slots)
                for bucket in pred.prefill_buckets():
                    # a prompt filling the whole cache is unservable
                    # (no room to generate), so the largest bucket is
                    # warmed with the longest SERVABLE prompt length
                    n = min(bucket, pred.max_seq_len - 1)
                    sess.prefill(0, [0] * n)
                    # the lane's ONE step executable: how many trips a
                    # dispatch runs is a runtime argument of it, so a
                    # one-trip round resolves every window the lane
                    # will run; a full window is run here all the same,
                    # so that whatever a window touches was touched
                    # before the flip
                    sess.decode()
                    sess.decode_fused(STEP_WINDOW)
                    sess.free(0)
                    # as many same-bucket prompts as the lane can admit
                    # in a pass, where that makes a group (a speculative
                    # lane prefills a prompt a call)
                    members = 1 if drafts and spec_k else prefill_group(
                        pred.prefill_width(bucket), n_slots)
                    if members > 1:
                        sess.launch_prefill(list(range(members)),
                                            [[0] * n] * members)
                        sess.fetch_prefill()
                        for slot in range(members):
                            sess.free(slot)
                if drafts and spec_k:
                    # spec lanes: force-resolve the verify executable
                    # plus the draft's phases so the first real stream
                    # pays no compile on EITHER side of the flip
                    pred.verify_fn(n_slots, spec_k)
                    if self.batcher.spec_fused:
                        pred.fused_spec_fn(drafts[i], n_slots, spec_k)
                    dsess = drafts[i].new_session(n_slots)
                    for bucket in drafts[i].prefill_buckets():
                        n = min(bucket, drafts[i].max_seq_len - 1)
                        dsess.prefill(0, [0] * n)
                        dsess.decode()
                        dsess.free(0)
            return self
        specs = self.predictor.feed_specs()
        buckets = self.predictor.batch_buckets() or (1,)
        batched = self.predictor.batched_feed_names()
        for pred in self.replicas:
            for cap in buckets:
                feeds = {}
                for fname, (shape, dtype) in specs.items():
                    if fname in batched:
                        s = [cap if d == -1 else d for d in shape]
                    else:
                        s = [1 if d == -1 else d for d in shape]
                    feeds[fname] = np.zeros(tuple(s),
                                            dtype=np.dtype(dtype))
                pred.run(feeds)
        return self


class ModelRegistry:
    """name -> {versions, latest} with hot swap and drain-on-retire."""

    def __init__(self, metrics=None, max_queue=None, deadline_ms=None,
                 workers=None, replicas=None):
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self._max_queue = max_queue
        self._deadline_ms = deadline_ms
        self._workers = workers
        self._replicas = replicas  # default placement spec for loads
        self._lock = threading.Lock()
        self._models = {}  # name -> {"versions": {v: entry}, "latest": v}
        # unload-to-spec (SERVING.md "Fleet controller"): every unload
        # persists how to REBUILD the exact lane set (per-lane load
        # specs + A/B weights); paged models additionally fault back in
        # on the next request.  One per-name lock serializes fault-ins
        # so a request burst rebuilds the model once.
        self._unload_specs = {}   # name -> {"lanes": [...], "ab": {...}}
        self._paged = {}          # name -> same record + "paged_at"
        self._fault_locks = {}    # name -> threading.Lock
        # last measured fault-in per model: {"ms", "trigger", "t_mono"}
        # — the fleet controller's fault_in_ms gauge reads this
        self.last_fault_in = {}

    # ------------------------------------------------------------------

    @staticmethod
    def _fit_check(name, path, placement, decode_slots=None,
                   draft_path=None, kv_cache_dtype=None):
        """Static admission gate (ANALYSIS.md): analyze the artifact,
        then check the per-replica peak estimate against every
        placement device's memory budget.  Returns the ResourceReport
        (None when the artifact defies analysis — advisory only);
        raises ResourceFitError on a placement that cannot fit.

        Replicas sharing one device (the [None] default-device spec
        with N > 1 never happens; explicit duplicate devices can)
        multiply the estimate on that device.

        `draft_path` (speculative decoding) adds the draft artifact's
        estimate — its weights AND its own KV slot table — to every
        replica's footprint: the draft lives on the same device as its
        target, so both must fit TOGETHER or the load is rejected
        before any build/warm work.

        A MeshGroup replica (SERVING.md "Mesh replicas") prices PER
        MEMBER device: params + KV shard at rest (~1/mesh_size each),
        the replicated-compute activation peak does not — so a model
        whose whole-footprint estimate exceeds any one chip's budget
        still ADMITS on a mesh whose members each fit their share.
        The draft rides the same group, priced the same way."""
        from ..analysis import ResourceFitError, check_fit, resources
        from ..parallel.mesh import as_mesh_group
        try:
            report = resources.analyze_artifact(
                path, decode_slots=decode_slots,
                kv_cache_dtype=kv_cache_dtype)
        except Exception:
            return None
        draft_report = None
        if draft_path:
            try:
                draft_report = resources.analyze_artifact(
                    draft_path, decode_slots=decode_slots)
            except Exception:
                draft_report = None
        by_dev = {}
        for dev in placement:
            key = id(dev) if dev is not None else None
            by_dev[key] = (dev, by_dev.get(key, (dev, 0))[1] + 1)
        what = "model %r (%s)" % (name, path)
        if draft_report is not None:
            what += " + draft (%s)" % (draft_path,)
        mesh_max = 1
        for dev, n in by_dev.values():
            group = as_mesh_group(dev)
            m = group.mesh_size if group is not None else 1
            mesh_max = max(mesh_max, m)
            members = group.devices if group is not None else (dev,)
            w = what if group is None else \
                "%s on mesh replica %s" % (what, group.label())
            est = avail = None
            for member in members:
                try:
                    est, avail = check_fit(
                        report, device=member, what=w, replicas=n,
                        mesh_size=m)
                    if draft_report is not None and avail is not None:
                        est += draft_report.per_device_bytes(m) * int(n)
                        if est > avail:
                            raise ResourceFitError(w, est, avail,
                                                   device=member)
                except ResourceFitError as e:
                    obs_events.emit(
                        "model_fit_rejected", model=name, path=path,
                        draft=draft_path or None,
                        est_bytes=e.estimated_bytes,
                        available_bytes=e.available_bytes,
                        mesh_size=int(m))
                    raise
            if avail is not None:
                obs_events.emit(
                    "model_fit_check", model=name, path=path,
                    draft=draft_path or None,
                    est_bytes=int(est), available_bytes=int(avail),
                    replicas=int(n), mesh_size=int(m),
                    step_bytes=int(report.per_device_step_bytes(
                        m, tp=bool(FLAGS.mesh_tp))))
        # stamp the placement's mesh shape (and the tensor-parallel
        # compute mode) on the stored report so describe()/stats (and
        # the fleet's placement-by-capacity math) read the per-device
        # resident estimate + per-member step traffic, not the
        # whole-model sums
        report.mesh_size = int(mesh_max)
        report.tp = bool(FLAGS.mesh_tp and mesh_max > 1)
        return report

    def load_model(self, name, path, version=None, warm=True,
                   buckets=None, drain_timeout=30.0, replicas=None,
                   devices=None, decode_slots=None, decode_mode=None,
                   precision=None, ab_weight=None, draft=None,
                   spec_k=None, kv_cache_dtype=None, fuse_steps=None):
        """Load (or hot-swap in) `path` as `name`.  Returns the entry.
        `replicas`/`devices` override the registry's default placement
        spec (see resolve_placement).  ALL replicas are built and
        warmed before the flip; the displaced latest version OF THE
        SAME PRECISION LANE, if any, is drained and retired AFTER the
        flip — in-flight requests on it complete.  Loading an int8
        sibling never touches the live fp32 lane (and vice versa):
        that's the A/B axis, not a hot swap.

        `precision` overrides the artifact's own lane (auto-detected
        from quant_meta.bin / the rewritten program — 'int8' vs
        'fp32'); `ab_weight` sets this lane's share of DEFAULT-routed
        traffic (requests carrying no explicit precision), e.g. 0.1
        canaries the quantized lane at 10%.  Without weights, default
        traffic stays on the fp32 lane — loading a quantized sibling
        must not silently move traffic.

        A decode artifact (decode_meta.bin) is fronted by a
        DecodeBatcher instead: per-replica slot tables of
        `decode_slots` (default FLAGS.serving_decode_slots) with
        continuous batching; `decode_mode="static"` keeps the
        static-batch baseline (bench comparison only).

        `draft`/`spec_k` (SERVING.md "Speculative decoding", decode
        artifacts only): `draft` names a vocab-compatible decode
        artifact (default FLAGS.serving_spec_draft — canonically the
        int8 twin) built on the SAME placement, one draft replica per
        target replica; each lane then drafts `spec_k` (default
        FLAGS.serving_spec_k) tokens per round and the target verifies
        them in one batched step, streams staying bit-identical to
        target-only decode.  The draft is fit-checked alongside the
        target before any build work.

        `kv_cache_dtype` (decode artifacts only, QUANTIZE.md
        "Quantized KV cache"): 'int8' stores this load's KV slot
        tables quantized (~0.25x cache bytes, in-graph quantized
        writes, in-register dequant reads); default resolves from the
        artifact's decode_meta pin then FLAGS.serving_kv_cache_dtype.
        The admission fit check prices the requested cache dtype, and
        the compile cache fingerprints it, so fp32 and int8 loads
        never share an executable.

        `fuse_steps` (decode artifacts only, SERVING.md "Fused
        multi-step decode"): pins the most decode steps one lane
        dispatch runs, at most the step executable's own window
        (`decode.STEP_WINDOW`, the default); 1 makes every dispatch one
        step.  How many a dispatch does run the lane decides from its
        slot table.  Streams are the same tokens whatever the value.
        With a draft, a value > 1 also fuses each speculative round
        into one dispatch."""
        from .. import compile_cache
        spec = devices if devices is not None else (
            replicas if replicas is not None else self._replicas)
        placement = resolve_placement(spec)
        is_decode_path = os.path.exists(
            os.path.join(path, "decode_meta.bin"))
        draft_path, spec_depth = None, 0
        if is_decode_path:
            # normalize/validate at admission so a bad wire value is a
            # typed error before any analysis or build work
            from ..inference.decode import normalize_kv_dtype
            if kv_cache_dtype is not None:
                kv_cache_dtype = normalize_kv_dtype(kv_cache_dtype)
            spec_depth = int(FLAGS.serving_spec_k if spec_k is None
                             else spec_k)
            draft_path = draft if draft is not None \
                else (FLAGS.serving_spec_draft or None)
            if not draft_path or spec_depth < 1:
                draft_path, spec_depth = None, 0
            if fuse_steps is not None:
                fuse_steps = max(int(fuse_steps), 1)
        else:
            kv_cache_dtype = None
            fuse_steps = None
        # admission fit check (ANALYSIS.md resource analysis): the
        # static per-replica peak estimate is checked against each
        # placement device's budget BEFORE any artifact build / clone /
        # warm work — an un-fittable placement fails fast with a
        # ResourceFitError naming the estimated and available bytes.
        # Analysis failures (not fit failures) must never block a load:
        # the estimate is advisory when it cannot be computed.
        report = self._fit_check(name, path, placement,
                                 decode_slots=decode_slots,
                                 draft_path=draft_path,
                                 kv_cache_dtype=kv_cache_dtype)
        cc_before = compile_cache.stats()
        preds = _build_replicas(path, buckets, placement,
                                kv_cache_dtype=kv_cache_dtype)
        precision = str(precision or getattr(preds[0], "precision",
                                             "fp32"))
        lane_metrics = self.metrics.model(name, precision)
        if getattr(preds[0], "is_decode", False):
            draft_preds = _build_replicas(draft_path, None, placement) \
                if draft_path else None
            batcher = DecodeBatcher(
                preds[0], replicas=preds, n_slots=decode_slots,
                max_queue=self._max_queue,
                metrics=lane_metrics,
                continuous=(decode_mode != "static"),
                draft_replicas=draft_preds, spec_k=spec_depth,
                fuse_steps=fuse_steps)
        else:
            batcher = DynamicBatcher(
                preds[0], max_queue=self._max_queue,
                deadline_ms=self._deadline_ms, workers=self._workers,
                metrics=lane_metrics, replicas=preds)
        entry = ModelEntry(name, version, path, preds[0], batcher,
                           replicas=preds, devices=placement,
                           precision=precision, resource=report,
                           draft_path=draft_path)
        # unload-to-spec record (SERVING.md "Fleet controller"): the
        # RESOLVED kwargs that rebuild exactly this lane — what
        # unload_model persists, fault_in replays, and resize_model
        # replays at a new placement.  Values are resolved (not the
        # FLAGS-dependent None defaults) so a later flag change cannot
        # silently rebuild a different lane.
        entry.load_spec = {
            "path": path,
            "buckets": list(buckets) if buckets else None,
            "precision": precision,
            "draft": draft_path,
            "spec_k": spec_depth,
            "decode_slots": (batcher.n_slots
                             if entry.is_decode else None),
            "decode_mode": decode_mode,
            "kv_cache_dtype": (str(getattr(preds[0], "kv_cache_dtype",
                                           "float32"))
                               if entry.is_decode else None),
            # as given: None is the lane's own window, a value a pin
            # (and, with a draft, fused speculative rounds)
            "fuse_steps": fuse_steps,
        }
        if placement == [None]:
            entry.load_spec["replicas"] = 1
        else:
            entry.load_spec["devices"] = entry.device_labels()
        if report is not None:
            lane_metrics.note_resource(report.peak_mb,
                                       report.total_flops)
        if warm:
            try:
                entry.warm()
            except BaseException:
                batcher.close(drain=False, timeout=1.0)
                raise
        # build+warm covered every (bucket, replica) executable — the
        # counter delta is exactly what this load/flip cost against the
        # persistent compile cache (load_model reply + metrics)
        entry.compile_cache = compile_cache.stats_delta(cc_before)
        lane_metrics.note_compile(entry.compile_cache)
        # the compile-cache delta is a lifecycle fact worth keeping: a
        # warm flip reads hits=N misses=0 in the event log forever,
        # even after the stats counters blur across later loads
        obs_events.emit("compile_cache_delta", model=name,
                        precision=precision,
                        hits=int(entry.compile_cache.get("hits", 0)),
                        misses=int(entry.compile_cache.get("misses", 0)))
        displaced = None
        with self._lock:
            slot = self._models.setdefault(
                name, {"versions": {}, "latest": None,
                       "latest_prec": {}, "ab": {}, "ab_credit": {}})
            if version is None:
                prev = [v for v in slot["versions"] if isinstance(v, int)]
                version = entry.version = (max(prev) + 1) if prev else 1
            # hot swap is per precision LANE: the displaced set is the
            # old latest of THIS lane, never the A/B sibling
            old_lane = slot.setdefault("latest_prec", {}).get(precision)
            if old_lane is not None and old_lane != version:
                displaced = slot["versions"].get(old_lane)
            replaced_same = slot["versions"].get(version)
            slot["versions"][version] = entry
            slot["latest"] = version  # the atomic flip
            slot["latest_prec"][precision] = version
            if ab_weight is not None:
                slot.setdefault("ab", {})[precision] = float(ab_weight)
            flipped_from = old_lane
            # the model is resident again: a load supersedes any
            # paged/unloaded spec record
            self._paged.pop(name, None)
            self._unload_specs.pop(name, None)
        # the new batcher owns the live replica/queue-depth hooks from
        # here on; the displaced set still drains below
        obs_events.emit("hot_swap", model=name, version=version,
                        from_version=flipped_from, precision=precision,
                        replicas=len(entry.replicas))
        for old in (displaced, replaced_same):
            if old is not None and old is not entry:
                old.batcher.close(drain=True, timeout=drain_timeout)
                with self._lock:
                    slot = self._models.get(name)
                    if slot and slot["versions"].get(old.version) is old:
                        del slot["versions"][old.version]
        return entry

    def set_ab_weights(self, name, weights):
        """Set the default-traffic split across precision lanes, e.g.
        ``{"fp32": 0.5, "int8": 0.5}``.  Requests carrying an explicit
        `precision` (or `version`) bypass the split.  Weights are
        absolute traffic fractions: a lane absent from the dict shares
        whatever fraction the named lanes leave unassigned (so one
        ``{"int8": 0.1}`` entry canaries int8 at 10% with fp32 keeping
        90%); weights summing >= 1 leave absent lanes nothing."""
        clean = {str(k): float(v) for k, v in dict(weights).items()
                 if float(v) > 0.0}
        with self._lock:
            slot = self._models.get(name)
            if slot is None:
                raise KeyError("no model %r" % name)
            slot["ab"] = clean
            slot["ab_credit"] = {}

    def _retire(self, name, drain_timeout, page):
        """Drop `name` from the routing table, persist its REBUILD
        record {"lanes": [per-lane load specs in route order], "ab":
        weights}, then drain the batchers.  The pop and the record
        insert happen under ONE lock acquisition, so a request racing
        a page-out always sees either the live entry or the paged
        record — never a no_model gap.  The load-spec persistence is
        the unload contract (SERVING.md "Fleet controller"): before
        it, an unloaded model kept no record of how to rebuild its
        lane set."""
        with self._lock:
            slot = self._models.pop(name, None)
            if slot is None:
                raise KeyError("no model %r" % name)
            record = {"lanes": [], "ab": dict(slot.get("ab") or {})}
            lanes = slot.get("latest_prec") or {}
            if not lanes and slot["latest"] is not None:
                lanes = {"fp32": slot["latest"]}
            # fp32 first (sorted), so the replay's default-routing
            # shape matches the original load order
            for prec, v in sorted(lanes.items()):
                entry = slot["versions"].get(v)
                spec = getattr(entry, "load_spec", None)
                if spec:
                    record["lanes"].append(dict(spec))
            if page:
                record["paged_at"] = time.monotonic()
                self._paged[name] = record
                self._unload_specs.pop(name, None)
            else:
                self._unload_specs[name] = record
                self._paged.pop(name, None)
        for entry in slot["versions"].values():
            entry.batcher.close(drain=True, timeout=drain_timeout)
        return record

    def unload_model(self, name, drain_timeout=30.0):
        """Remove `name`: new requests fail immediately, in-flight/
        queued ones drain first.  The load spec of every precision
        lane (artifact path, placement, precision, kv_cache_dtype,
        draft/spec_k) plus the A/B weights are persisted, so
        `fault_in` can reconstruct the exact lane set later — but an
        unloaded model does NOT fault in on traffic (that is
        `page_out`'s contract)."""
        record = self._retire(name, drain_timeout, page=False)
        self.metrics.drop(name)
        obs_events.emit("model_unloaded", model=name,
                        lanes=len(record["lanes"]))

    def page_out(self, name, drain_timeout=30.0, signal=None):
        """Page `name` out to its artifact path(s): the replica sets
        drain and free their device memory, the rebuild record is kept
        PAGED, and the next request (or the fleet controller, on
        rising burn) faults the exact lane set back in.  Metrics lanes
        survive paging — counters must not reset across a page/fault
        cycle."""
        record = self._retire(name, drain_timeout, page=True)
        # the triggering signal rides the event; the emitter's own
        # fields win on key collisions (e.g. the signal's 'model')
        fields = dict(signal or {})
        fields.update(model=name, lanes=len(record["lanes"]))
        obs_events.emit("fleet_paged_out", **fields)

    def paged_models(self):
        """{name: {"age_s", "lanes"}} for every currently-paged
        model."""
        now = time.monotonic()
        with self._lock:
            return {n: {"age_s": round(now - r.get("paged_at", now), 3),
                        "lanes": len(r["lanes"])}
                    for n, r in self._paged.items()}

    def fault_in(self, name, trigger="request", signal=None):
        """Rebuild a paged/unloaded model from its persisted load
        specs: every precision lane replays through load_model (fit
        check, build, warm, flip — the COMPILE_CACHE.md store makes
        this a reload, not a recompile) and the A/B weights are
        restored, so the reconstructed lane set answers bit-exactly
        like the original.  Idempotent and burst-safe: one per-name
        lock serializes concurrent fault-ins, later arrivals find the
        model live and return immediately.  The measured wall time
        lands in `last_fault_in` (the fleet fault_in_ms gauge) and on
        the model's metrics lane."""
        with self._lock:
            if name in self._models:
                return self._entry_locked(name, None)
            lock = self._fault_locks.setdefault(name, threading.Lock())
        with lock:
            with self._lock:
                if name in self._models:  # a concurrent fault-in won
                    return self._entry_locked(name, None)
                rec = self._paged.get(name)
                if rec is None and str(trigger) != "request":
                    # traffic only resurrects PAGED models; an
                    # operator unload stays unloaded until an explicit
                    # fault_in/load — but its spec is still here
                    rec = self._unload_specs.get(name)
            if rec is None or not rec["lanes"]:
                raise KeyError(
                    "no model %r (and no persisted load spec to fault "
                    "in)" % name)
            t0 = time.monotonic()
            entry = None
            for lane_spec in rec["lanes"]:
                kw = dict(lane_spec)
                entry = self.load_model(name, kw.pop("path"), **kw)
            if rec.get("ab"):
                self.set_ab_weights(name, rec["ab"])
            ms = (time.monotonic() - t0) * 1e3
            with self._lock:
                self._paged.pop(name, None)
                self._unload_specs.pop(name, None)
            self.last_fault_in[name] = {"ms": round(ms, 3),
                                        "trigger": str(trigger),
                                        "t_mono": time.monotonic()}
            first_prec = rec["lanes"][0].get("precision") or "fp32"
            self.metrics.model(name, first_prec).note_fault_in(ms)
            fields = dict(signal or {})
            fields.update(model=name, trigger=str(trigger),
                          fault_in_ms=round(ms, 3),
                          lanes=len(rec["lanes"]))
            obs_events.emit("fleet_fault_in", **fields)
            return entry

    def resize_model(self, name, replicas, precision=None, signal=None):
        """Scale one model's replica set to `replicas` by replaying
        its persisted load spec at the new placement through
        load_model — so every resize rides the build-warm-flip
        hot-swap discipline (zero-drop by construction) and the
        ANALYSIS.md fit check gates every grow BEFORE any build work.
        Returns the new entry (the current one when already at size)."""
        n = int(replicas)
        if n < 1:
            raise ValueError("replica count must be >= 1, got %d" % n)
        with self._lock:
            slot = self._models.get(name)
            if slot is None:
                raise KeyError("no model %r" % name)
            lanes = slot.get("latest_prec") or {}
            prec = str(precision) if precision is not None else (
                "fp32" if "fp32" in lanes
                else (sorted(lanes)[0] if lanes else None))
            v = lanes.get(prec, slot["latest"])
            entry = slot["versions"].get(v)
        spec = getattr(entry, "load_spec", None) if entry is not None \
            else None
        if not spec:
            raise KeyError("model %r has no rebuildable load spec"
                           % name)
        old_n = len(entry.replicas)
        if n == old_n:
            return entry
        kw = dict(spec)
        path = kw.pop("path")
        kw.pop("devices", None)
        m = max(entry.mesh_sizes() or [1])
        if m > 1:
            # a mesh entry resizes in whole GROUPS: n replicas of the
            # entry's mesh size, packed over disjoint consecutive local
            # devices — the same shard-at-rest shape the original fit
            # check admitted
            import jax
            local = list(jax.local_devices())
            if n * m > len(local):
                raise ValueError(
                    "resize of mesh model %r to %d replicas needs "
                    "%d x %d = %d devices, host has %d"
                    % (name, n, n, m, n * m, len(local)))
            kw["devices"] = [
                "+".join("%s:%d" % (d.platform, d.id)
                         for d in local[i * m:(i + 1) * m])
                for i in range(n)]
        else:
            kw["replicas"] = n
        new_entry = self.load_model(name, path, **kw)
        fields = dict(signal or {})
        fields.update(model=name, precision=new_entry.precision,
                      from_replicas=old_n, to_replicas=n)
        obs_events.emit(
            "fleet_scale_up" if n > old_n else "fleet_scale_down",
            **fields)
        return new_entry

    def model_names(self):
        with self._lock:
            return sorted(self._models)

    def describe(self):
        with self._lock:
            out = {}
            for name, slot in self._models.items():
                info = {"latest": slot["latest"],
                        "versions": sorted(slot["versions"])}
                lanes = slot.get("latest_prec") or {}
                if lanes:
                    # the precision axis: which version each numerics
                    # lane routes to, plus the default-traffic split
                    info["precisions"] = dict(sorted(lanes.items()))
                    if slot.get("ab"):
                        info["ab_weights"] = dict(
                            sorted(slot["ab"].items()))
                latest = slot["versions"].get(slot["latest"])
                if latest is not None:
                    info["buckets"] = list(
                        latest.predictor.batch_buckets())
                    info["replicas"] = len(latest.replicas)
                    info["devices"] = latest.device_labels()
                    info["precision"] = latest.precision
                    sizes = latest.mesh_sizes()
                    if any(s > 1 for s in sizes):
                        # mesh replicas (SERVING.md): members per
                        # replica, in route order — serving_top's MESH
                        # column and the load reply's resolved shape
                        info["mesh"] = sizes
                        info["mesh_size"] = max(sizes)
                        # tensor-parallel compute (FLAGS.mesh_tp +
                        # a TP-splittable model): the partitioned
                        # program instead of gather-and-replicate
                        info["mesh_tp"] = any(
                            getattr(p, "tp_active", False)
                            for p in latest.replicas)
                    if latest.resource is not None:
                        # the static cost the fleet controller places
                        # by (ANALYSIS.md): per-replica peak estimate
                        # + one-step FLOPs
                        info["est_peak_mb"] = round(
                            latest.resource.peak_mb, 3)
                        info["est_flops"] = int(
                            latest.resource.total_flops)
                        if int(getattr(latest.resource, "mesh_size",
                                       1)) > 1:
                            # what each mesh MEMBER holds resident —
                            # the number the per-device fit admitted on
                            info["est_per_device_mb"] = round(
                                latest.resource.per_device_mb, 3)
                    if latest.is_decode:
                        # decode entry: buckets above are the PROMPT
                        # prefill buckets; surface the generation shape
                        info["decode"] = True
                        info["decode_slots"] = latest.batcher.n_slots
                        info["max_seq_len"] = \
                            latest.predictor.max_seq_len
                        info["eos_id"] = latest.predictor.eos_id
                        info["kv_cache_dtype"] = str(getattr(
                            latest.predictor, "kv_cache_dtype",
                            "float32"))
                        info["fuse_steps"] = int(getattr(
                            latest.batcher, "fuse_steps", 1))
                        if getattr(latest.batcher, "spec_k", 0):
                            # speculative lanes: the draft + depth the
                            # operator tuned (SERVING.md)
                            info["spec_k"] = latest.batcher.spec_k
                            info["draft"] = latest.draft_path
                else:
                    info["buckets"] = []
                out[name] = info
            now = time.monotonic()
            for name, rec in self._paged.items():
                if name in out:
                    continue
                # paged models stay visible (SERVING.md "Fleet
                # controller"): resident nowhere, but one request away
                out[name] = {
                    "paged": True,
                    "paged_age_s": round(
                        now - rec.get("paged_at", now), 3),
                    "lanes": [s.get("precision", "fp32")
                              for s in rec["lanes"]]}
            return out

    def health(self):
        """Per-model liveness readout (the `health` RPC verb's
        ``models`` section): for each precision lane's routed version,
        the batcher's thread/lane liveness (router alive, workers
        alive, last-dispatch / last-decode-step age) plus queue depth.
        Snapshot the slots under the lock, read the batchers outside it
        — liveness reads must not serialize against a hot swap."""
        with self._lock:
            snap = []
            for name, slot in self._models.items():
                lanes = dict(slot.get("latest_prec") or {})
                if not lanes and slot["latest"] is not None:
                    lanes = {"fp32": slot["latest"]}
                snap.append((name, slot["latest"],
                             sorted(slot["versions"]),
                             [(prec, v, slot["versions"].get(v))
                              for prec, v in sorted(lanes.items())]))
        out = {}
        for name, latest, versions, lanes in snap:
            minfo = {"latest": latest, "versions": versions,
                     "lanes": {}}
            for prec, v, entry in lanes:
                if entry is None:
                    continue
                li = {"version": v,
                      "queue_depth": entry.batcher.queue_depth(),
                      "decode": entry.is_decode}
                try:
                    li["liveness"] = entry.batcher.lane_liveness()
                except Exception as e:
                    li["liveness"] = {"error": "%s: %s"
                                      % (type(e).__name__, e)}
                if entry.is_decode:
                    # the freshest decode-step age across this lane set
                    # — the "is anything still making progress" number
                    ages = [l.get("last_step_age_s")
                            for l in li["liveness"].get("lanes", [])
                            if l.get("last_step_age_s") is not None]
                    li["last_decode_step_age_s"] = min(ages) \
                        if ages else None
                minfo["lanes"][prec] = li
            out[name] = minfo
        return out

    # ------------------------------------------------------------------

    def _entry_locked(self, name, version, precision=None):
        slot = self._models.get(name)
        if slot is None:
            raise KeyError("no model %r" % name)
        if version is None:
            v = self._route_version_locked(slot, name, precision)
        else:
            v = version
        entry = slot["versions"].get(v)
        if entry is None:
            raise KeyError("model %r has no version %r" % (name, v))
        return entry

    def _route_version_locked(self, slot, name, precision):
        """The precision router (QUANTIZE.md A/B axis).  An explicit
        `precision` resolves to that lane's latest (KeyError when the
        lane was never loaded).  Default traffic: with A/B weights set
        (set_ab_weights / load_model ab_weight) the pick is a smooth
        weighted round-robin over the live lanes — deterministic, no
        RNG, exact shares over any window; without weights it stays on
        the fp32 lane when one exists (loading a quantized sibling
        must not move traffic by itself), else the overall latest."""
        lanes = slot.get("latest_prec") or {}
        if precision is not None:
            v = lanes.get(str(precision))
            if v is None:
                raise KeyError(
                    "model %r has no %r precision lane (have %s)"
                    % (name, precision, sorted(lanes) or ["fp32"]))
            return v
        ab = {p: w for p, w in (slot.get("ab") or {}).items()
              if p in lanes and w > 0.0}
        if len(lanes) > 1 and ab:
            # weights are absolute traffic fractions: lanes left out of
            # the dict share the UNASSIGNED remainder, so
            # load_model(ab_weight=0.1) canaries the new lane at 10%
            # with the fp32 lane keeping the other 90% (weights summing
            # >= 1 leave nothing for unweighted lanes)
            others = [p for p in lanes if p not in ab]
            rem = max(0.0, 1.0 - sum(ab.values()))
            if others and rem > 0.0:
                for p in others:
                    ab[p] = rem / len(others)
            credit = slot.setdefault("ab_credit", {})
            total = sum(ab.values())
            for p, w in ab.items():
                credit[p] = credit.get(p, 0.0) + w
            pick = max(sorted(ab), key=lambda p: credit.get(p, 0.0))
            credit[pick] -= total
            return lanes[pick]
        if len(lanes) > 1 and "fp32" in lanes:
            return lanes["fp32"]
        return slot["latest"]

    def _fault_pending(self, name):
        """True when `name` can be (or is being) faulted in by
        traffic: it is paged, or another thread's fault-in of it is in
        flight right now (the submit that lost the race must WAIT on
        the fault lock, not bounce with no_model)."""
        with self._lock:
            if name in self._paged:
                return True
            lock = self._fault_locks.get(name)
        return lock is not None and lock.locked()

    def _submit_entry(self, entry, name, feeds, deadline, priority,
                      trace_id, max_new_tokens, chunk_tokens):
        if entry.is_decode:
            if not isinstance(feeds, dict) or "tokens" not in feeds:
                raise ValueError(
                    "decode model %r takes feeds {'tokens': "
                    "int array}, got %s"
                    % (name, sorted(feeds) if isinstance(feeds, dict)
                       else type(feeds).__name__))
            return entry.batcher.submit(
                feeds["tokens"], max_new_tokens=max_new_tokens,
                deadline=deadline, priority=priority,
                trace_id=trace_id, chunk_tokens=chunk_tokens)
        return entry.batcher.submit(feeds, deadline=deadline,
                                    priority=priority,
                                    trace_id=trace_id)

    def submit(self, name, feeds, version=None, deadline=None,
               priority=0, trace_id=None, max_new_tokens=None,
               chunk_tokens=None, precision=None):
        """Route one request; returns the batcher Future.  Resolution
        and submit happen under ONE lock acquisition so a concurrent hot
        swap can never retire a version between the two (the no-dropped-
        request guarantee: the swap's drain only starts after the flip,
        and every pre-flip submit is already queued).  `trace_id` rides
        through to the batcher's stage spans (OBSERVABILITY.md).
        `precision` pins the request to one numerics lane ('fp32' /
        'int8'); None routes by the A/B weights (see load_model).

        A PAGED model (SERVING.md "Fleet controller") faults back in
        here: the first request pays the reload (warm compile cache —
        a deserialize, not a recompile), concurrent arrivals wait on
        the same per-name fault lock, and the rebuilt lane set answers
        every one of them.

        On a DECODE entry, `feeds` must carry the prompt as "tokens";
        the returned DecodeStream duck-types the batcher Future
        (`result()` -> [generated int32 tokens]), so one-shot `infer`
        callers work unchanged — streaming callers use submit_stream."""
        try:
            with self._lock:
                entry = self._entry_locked(name, version,
                                           precision=precision)
                return self._submit_entry(entry, name, feeds, deadline,
                                          priority, trace_id,
                                          max_new_tokens, chunk_tokens)
        except KeyError:
            if not self._fault_pending(name):
                raise
        self.fault_in(name, trigger="request")
        with self._lock:
            entry = self._entry_locked(name, version,
                                       precision=precision)
            return self._submit_entry(entry, name, feeds, deadline,
                                      priority, trace_id,
                                      max_new_tokens, chunk_tokens)

    def submit_stream(self, name, tokens, version=None,
                      max_new_tokens=None, deadline=None, priority=0,
                      trace_id=None, chunk_tokens=None):
        """Streaming generation entry point: returns the DecodeStream
        whose token chunks the server's `infer_stream` verb flushes to
        the wire as they decode.  Same single-lock resolution contract
        (and paged-model fault-in) as submit()."""
        try:
            with self._lock:
                entry = self._entry_locked(name, version)
                return self._stream_entry(entry, name, tokens,
                                          max_new_tokens, deadline,
                                          priority, trace_id,
                                          chunk_tokens)
        except KeyError:
            if not self._fault_pending(name):
                raise
        self.fault_in(name, trigger="request")
        with self._lock:
            entry = self._entry_locked(name, version)
            return self._stream_entry(entry, name, tokens,
                                      max_new_tokens, deadline,
                                      priority, trace_id, chunk_tokens)

    @staticmethod
    def _stream_entry(entry, name, tokens, max_new_tokens, deadline,
                      priority, trace_id, chunk_tokens):
        if not entry.is_decode:
            raise ValueError(
                "model %r is not a decode model — infer_stream "
                "serves autoregressive artifacts only" % name)
        return entry.batcher.submit(
            tokens, max_new_tokens=max_new_tokens,
            deadline=deadline, priority=priority,
            trace_id=trace_id, chunk_tokens=chunk_tokens)

    def infer(self, name, feeds, version=None, deadline=None,
              timeout=None, priority=0, precision=None):
        """Blocking submit+wait convenience for in-process callers."""
        return self.submit(name, feeds, version=version,
                           deadline=deadline, priority=priority,
                           precision=precision).result(timeout=timeout)

    def close_all(self, drain=True, timeout=30.0):
        with self._lock:
            slots = list(self._models.values())
            self._models.clear()
        for slot in slots:
            for entry in slot["versions"].values():
                entry.batcher.close(drain=drain, timeout=timeout)
