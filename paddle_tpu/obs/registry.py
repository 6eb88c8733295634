"""Unified metrics surface: one registry across training + serving.

Before this module, telemetry was fragmented per subsystem: serving kept
``ServingMetrics`` counters/histograms behind the ``stats`` RPC,
training kept an unsynchronized profiler table, and nothing exported
either in a form a scraper could ingest.  ``MetricsRegistry`` absorbs
them all into ONE exposition:

* its own counters / gauges / histograms (training-side code registers
  here directly);
* every attached ``ServingMetrics`` (the server attaches at start,
  detaches at shutdown) — their ``snapshot()`` dicts are flattened into
  labeled metric families at render time, so there is no double
  bookkeeping and a hot swap keeps its no-counter-reset semantics;
* span aggregates: the registry listens to the tracing ring
  (tracing.set_span_listener) and keeps per-(kind, name) call counts and
  total milliseconds — the per-step prefetch_wait / dispatch / drain /
  ckpt breakdown and the per-stage serving totals fall out of the spans
  already being recorded, no extra instrumentation;
* event-log totals, compile-cache store counters, and the tracing
  ring's own health (buffered/dropped).

``prometheus_text()`` renders the whole thing Prometheus-style
(``# TYPE`` headers, ``name{label="v"} value`` samples) — served by the
new ``metrics`` RPC verb on the inference server and by
``tools/metrics_dump.py``.
"""

import threading

__all__ = ["MetricsRegistry", "default"]

_PREFIX = "paddle_tpu_"

# ServingMetrics snapshot ints rendered as labeled counters
_SERVING_COUNTERS = ("requests", "responses", "errors", "shed",
                     "deadline_expired", "dispatches",
                     # generation counters (absent for one-shot models)
                     "streams", "prefills", "decode_tokens",
                     "decode_steps",
                     # fused multi-step decode (SERVING.md): dispatches
                     # issued — tokens/dispatches is the amortization
                     "decode_dispatches",
                     # speculative decoding (absent without a draft)
                     "spec_rounds", "draft_tokens", "accepted_tokens",
                     "spec_degraded")
# ... and floats rendered as labeled gauges
_SERVING_GAUGES = ("qps_recent", "qps_lifetime", "batch_fill",
                   "bucket_fill_ratio", "queue_depth",
                   # continuous-batching decode gauges (SERVING.md)
                   "tokens_per_sec", "slot_occupancy",
                   # measured KV slot-table bytes across lanes — reads
                   # ~0.25x under kv_cache_dtype=int8 (QUANTIZE.md
                   # "Quantized KV cache")
                   "kv_cache_bytes",
                   # lifetime draft accept fraction (SERVING.md
                   # speculative decoding — the speedup dial)
                   "spec_accept_rate")
_SERVING_HISTS = ("latency_ms", "queue_wait_ms", "ttft_ms",
                  "tokens_per_dispatch")
_QUANTILES = ("p50", "p95", "p99")


def _esc(v):
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
                 .replace("\n", "\\n")


def _labels(d):
    if not d:
        return ""
    return "{%s}" % ",".join('%s="%s"' % (k, _esc(v))
                             for k, v in sorted(d.items()))


def _num(v):
    if isinstance(v, float):
        return repr(round(v, 6))
    return str(v)


class MetricsRegistry(object):
    def __init__(self):
        self._lock = threading.Lock()
        self._counters = {}    # (name, labels-tuple) -> Counter
        self._gauges = {}      # name -> callable() -> value|dict|None
        self._hists = {}       # (name, labels-tuple) -> ReservoirHistogram
        self._serving = []     # attached ServingMetrics
        self._slo = []         # attached SLOMonitors (obs/slo.py)
        self._fleet = []       # attached FleetControllers (serving/fleet)
        self._federation = []  # attached FrontendServers (federation/)
        self._span_agg = {}    # (kind, name) -> [count, total_ms]

    # -- primitive instruments ---------------------------------------

    @staticmethod
    def _key(name, labels):
        return (name, tuple(sorted((labels or {}).items())))

    def counter(self, name, labels=None):
        # Counter/ReservoirHistogram live in serving.metrics but are
        # stdlib-only; importing them lazily keeps `import
        # paddle_tpu.obs` (and therefore every instrumented training
        # module) from dragging the serving package in
        from ..serving.metrics import Counter
        key = self._key(name, labels)
        with self._lock:
            c = self._counters.get(key)
            if c is None:
                c = self._counters[key] = Counter()
            return c

    def gauge(self, name, fn):
        """Register a live-read gauge: ``fn()`` -> number, or a dict of
        labels-tuple-free {label_value: number} rendered with one
        ``key`` label, or None to skip."""
        with self._lock:
            self._gauges[name] = fn

    def histogram(self, name, labels=None):
        from ..serving.metrics import ReservoirHistogram
        key = self._key(name, labels)
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = ReservoirHistogram()
            return h

    # -- absorbed sources --------------------------------------------

    def attach_serving(self, serving_metrics):
        with self._lock:
            if serving_metrics not in self._serving:
                self._serving.append(serving_metrics)

    def detach_serving(self, serving_metrics):
        with self._lock:
            if serving_metrics in self._serving:
                self._serving.remove(serving_metrics)

    def attach_slo(self, monitor):
        """Absorb one SLOMonitor: its burn-rate / compliance / state
        gauges render as first-class families (the fleet controller's
        health scrape — OBSERVABILITY.md "SLOs & burn rates")."""
        with self._lock:
            if monitor not in self._slo:
                self._slo.append(monitor)

    def detach_slo(self, monitor):
        with self._lock:
            if monitor in self._slo:
                self._slo.remove(monitor)

    def attach_fleet(self, controller):
        """Absorb one FleetController (serving/fleet.py): its
        fleet_replicas / fleet_state / fault_in_ms gauges render as
        first-class families — the actuation-side twin of the slo_*
        judgment families."""
        with self._lock:
            if controller not in self._fleet:
                self._fleet.append(controller)

    def detach_fleet(self, controller):
        with self._lock:
            if controller in self._fleet:
                self._fleet.remove(controller)

    def attach_federation(self, frontend):
        """Absorb one federation FrontendServer (federation/frontend):
        membership-by-state, placement/spillover/shed counters, and —
        when the global tier runs — the global_fleet_* families, all
        via the same [(metric, labels, value, type)] export rows."""
        with self._lock:
            if frontend not in self._federation:
                self._federation.append(frontend)

    def detach_federation(self, frontend):
        with self._lock:
            if frontend in self._federation:
                self._federation.remove(frontend)

    def note_span(self, span):
        """Tracing-ring listener: fold one completed span into the
        per-(kind, name) totals."""
        key = (span.kind, span.name)
        with self._lock:
            rec = self._span_agg.get(key)
            if rec is None:
                self._span_agg[key] = [1, span.dur_ms]
            else:
                rec[0] += 1
                rec[1] += span.dur_ms

    def span_totals(self, kind=None):
        """{(kind, name): {"count", "total_ms"}} — the per-stage time
        budget (trace_top's aggregate view reads this via metrics)."""
        with self._lock:
            return {k: {"count": v[0], "total_ms": round(v[1], 3)}
                    for k, v in self._span_agg.items()
                    if kind is None or k[0] == kind}

    # -- exposition ---------------------------------------------------

    @staticmethod
    def _model_labels(model_key, m, **extra):
        """Label set of one serving lane: the plain model name plus a
        ``precision`` label for non-fp32 lanes (the QUANTIZE.md A/B
        axis — an int8 lane keys as 'name@int8' in the snapshot but
        scrapes as model='name', precision='int8')."""
        labels = {"model": m.get("model", model_key)}
        prec = m.get("precision")
        if prec and prec != "fp32":
            labels["precision"] = prec
        labels.update(extra)
        return labels

    def _render_serving(self, lines):
        snaps = []
        with self._lock:
            serving = list(self._serving)
        for sm in serving:
            try:
                snaps.append(sm.snapshot())
            except Exception:
                continue
        for field in _SERVING_COUNTERS:
            mname = _PREFIX + "serving_%s_total" % field
            samples = []
            for snap in snaps:
                for model, m in sorted(snap.get("models", {}).items()):
                    if field in m:
                        samples.append(
                            (mname, self._model_labels(model, m),
                             m[field]))
            _family(lines, mname, "counter", samples)
        # tokens whose frame the servers' stream writers put on a socket
        # (a writer sends every model's streams, so no model label);
        # serving_decode_tokens_total minus it is what they still hold
        mname = _PREFIX + "serving_tokens_sent_total"
        _family(lines, mname, "counter",
                [(mname, {}, sum(snap["tokens_sent_total"]
                                 for snap in snaps))] if snaps else [])
        for field in _SERVING_GAUGES:
            mname = _PREFIX + "serving_" + field
            samples = []
            for snap in snaps:
                for model, m in sorted(snap.get("models", {}).items()):
                    if field in m:
                        samples.append(
                            (mname, self._model_labels(model, m),
                             m[field]))
            _family(lines, mname, "gauge", samples)
        for hist_field in _SERVING_HISTS:
            mname = _PREFIX + "serving_" + hist_field
            samples = []
            for snap in snaps:
                for model, m in sorted(snap.get("models", {}).items()):
                    if hist_field not in m:
                        continue  # e.g. ttft_ms on a one-shot model
                    h = m.get(hist_field) or {}
                    for q in _QUANTILES:
                        if h.get(q) is not None:
                            samples.append(
                                (mname,
                                 self._model_labels(model, m,
                                                    quantile=q),
                                 h[q]))
                    samples.append((mname + "_count",
                                    self._model_labels(model, m),
                                    h.get("count", 0)))
            _family(lines, mname, "summary", samples)
        # priority-shed + per-model compile-cache attribution
        samples = []
        for snap in snaps:
            for model, m in sorted(snap.get("models", {}).items()):
                for pri, n in sorted(
                        (m.get("shed_by_priority") or {}).items()):
                    samples.append((_PREFIX + "serving_shed_by_priority_"
                                    "total",
                                    self._model_labels(model, m,
                                                       priority=pri),
                                    n))
        _family(lines, _PREFIX + "serving_shed_by_priority_total",
                "counter", samples)
        # static resource estimates (ANALYSIS.md): the placement-by-
        # cost gauges the fleet controller scrapes — per-replica peak
        # HBM estimate and one-step FLOPs, set by the admission check
        for field, mname in (("est_peak_mb",
                              _PREFIX + "model_est_peak_mb"),
                             ("est_flops",
                              _PREFIX + "model_est_flops")):
            samples = []
            for snap in snaps:
                for model, m in sorted(snap.get("models", {}).items()):
                    if field in m:
                        samples.append(
                            (mname, self._model_labels(model, m),
                             m[field]))
            _family(lines, mname, "gauge", samples)
        # mesh shape per replica lane (SERVING.md "Mesh replicas"):
        # member-device count of each lane — 1 for a plain single-chip
        # replica; a dead mesh lane keeps exporting so a scraper can
        # still see the shape it lost
        samples = []
        for snap in snaps:
            for model, m in sorted(snap.get("models", {}).items()):
                for row in m.get("replicas") or []:
                    samples.append(
                        (_PREFIX + "replica_mesh_size",
                         self._model_labels(
                             model, m,
                             replica=str(row.get("replica", "")),
                             device=str(row.get("device", ""))),
                         int(row.get("mesh", 1) or 1)))
        _family(lines, _PREFIX + "replica_mesh_size", "gauge", samples)
        samples = []
        for snap in snaps:
            for model, m in sorted(snap.get("models", {}).items()):
                cc = m.get("compile_cache") or {}
                for f in ("hits", "misses"):
                    samples.append((_PREFIX + "serving_compile_cache_%s_"
                                    "total" % f,
                                    self._model_labels(model, m),
                                    cc.get(f, 0)))
        _family(lines, _PREFIX + "serving_compile_cache_total", "counter",
                samples)

    def _render_slo(self, lines):
        """Burn-rate / compliance / state families from every attached
        SLOMonitor (obs/slo.py export rows), and the fleet families
        (fleet_replicas / fleet_state / fault_in_ms) from every
        attached FleetController — both speak the same
        [(metric, labels, value, type)] export row shape."""
        with self._lock:
            monitors = (list(self._slo) + list(self._fleet)
                        + list(self._federation))
        by_name = {}
        for mon in monitors:
            try:
                rows = mon.export()
            except Exception:
                continue
            for metric, labels, value, mtype in rows:
                by_name.setdefault((metric, mtype), []).append(
                    (_PREFIX + metric, labels, value))
        for (metric, mtype), samples in sorted(by_name.items()):
            _family(lines, _PREFIX + metric, mtype, samples)

    def prometheus_text(self):
        """The one metrics surface, Prometheus text exposition."""
        lines = []
        # span aggregates: training per-stage breakdown + serving stages
        with self._lock:
            agg = sorted((k, list(v)) for k, v in self._span_agg.items())
            counters = sorted(self._counters.items())
            gauges = sorted(self._gauges.items())
            hists = sorted(self._hists.items())
        _family(lines, _PREFIX + "span_ms_total", "counter",
                [(_PREFIX + "span_ms_total",
                  {"kind": k or "none", "span": n}, round(v[1], 3))
                 for (k, n), v in agg])
        _family(lines, _PREFIX + "span_count_total", "counter",
                [(_PREFIX + "span_count_total",
                  {"kind": k or "none", "span": n}, v[0])
                 for (k, n), v in agg])
        for (name, labels), c in counters:
            _family(lines, _PREFIX + name, "counter",
                    [(_PREFIX + name, dict(labels), c.value)])
        for name, fn in gauges:
            try:
                v = fn()
            except Exception:
                continue
            if v is None:
                continue
            if isinstance(v, dict):
                _family(lines, _PREFIX + name, "gauge",
                        [(_PREFIX + name, {"key": k}, x)
                         for k, x in sorted(v.items())])
            else:
                _family(lines, _PREFIX + name, "gauge",
                        [(_PREFIX + name, {}, v)])
        for (name, labels), h in hists:
            s = h.summary()
            samples = [(_PREFIX + name + "_count", dict(labels),
                        s.get("count", 0))]
            for q in _QUANTILES:
                if s.get(q) is not None:
                    samples.append((_PREFIX + name,
                                    dict(labels, quantile=q), s[q]))
            _family(lines, _PREFIX + name, "summary", samples)
        self._render_serving(lines)
        self._render_slo(lines)
        # subsystem health: tracing ring, event log, compile-cache store
        # — each a FIRST-CLASS family (span drops, event drops, sink
        # state) so a scraper can alert on telemetry loss directly
        from . import events, tracing
        ts = tracing.stats()
        _family(lines, _PREFIX + "trace_spans_total", "counter",
                [(_PREFIX + "trace_spans_total", {}, ts["spans_total"])])
        _family(lines, _PREFIX + "trace_buffered", "gauge",
                [(_PREFIX + "trace_buffered", {}, ts["buffered"])])
        _family(lines, _PREFIX + "trace_dropped_total", "counter",
                [(_PREFIX + "trace_dropped_total", {}, ts["dropped"])])
        es = events.stats()
        _family(lines, _PREFIX + "events_total", "counter",
                [(_PREFIX + "events_total", {}, es["events_total"])])
        _family(lines, _PREFIX + "events_buffered", "gauge",
                [(_PREFIX + "events_buffered", {}, es["buffered"])])
        _family(lines, _PREFIX + "events_dropped_total", "counter",
                [(_PREFIX + "events_dropped_total", {}, es["dropped"])])
        _family(lines, _PREFIX + "events_rotations_total", "counter",
                [(_PREFIX + "events_rotations_total", {},
                  es["rotations"])])
        # 1 = a configured file sink has died (memory-only fallback);
        # 0 covers both "healthy sink" and "no sink configured"
        _family(lines, _PREFIX + "events_sink_dead", "gauge",
                [(_PREFIX + "events_sink_dead", {},
                  int(es["sink_dead"]))])
        try:
            from . import flightrec
            rec = flightrec.get_recorder()
            if rec is not None:
                fs = rec.stats()
                _family(lines, _PREFIX + "flight_dumps_total", "counter",
                        [(_PREFIX + "flight_dumps_total", {},
                          fs["dumps"])])
                _family(lines, _PREFIX + "flight_bundles", "gauge",
                        [(_PREFIX + "flight_bundles", {},
                          fs["bundles"])])
        except Exception:
            pass
        try:
            from .. import compile_cache
            cc = compile_cache.stats()
            for k, v in sorted(cc.items()):
                if isinstance(v, (int, float)):
                    n = _PREFIX + "compile_cache_%s" % k
                    _family(lines, n, "counter", [(n, {}, v)])
        except Exception:
            pass
        return "\n".join(lines) + "\n"


def _family(lines, name, mtype, samples):
    if not samples:
        return
    lines.append("# TYPE %s %s" % (name, mtype))
    for sname, labels, value in samples:
        lines.append("%s%s %s" % (sname, _labels(labels), _num(value)))


_default = None
_default_lock = threading.Lock()


def default():
    """The process-wide registry; first use wires it as the tracing
    ring's span listener so train/serving stage totals accumulate."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                reg = MetricsRegistry()
                from . import tracing
                tracing.set_span_listener(reg.note_span)
                _default = reg
    return _default
