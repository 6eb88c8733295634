"""Static resource & cost analysis — liveness-based memory planning and
a per-op FLOP/byte roofline model over the Program IR.

The PR 9 verifier proves a Program is *correct* before it runs; this
module answers the two questions every placement decision starts with —
does it FIT, and how fast can it possibly GO — without running it.  The
Julia-to-TPU compiler paper treats whole-program shape inference as a
compilability precondition; here the same static shapes are folded into
byte and FLOP counts, so ROOFLINE.md's *measured* ceilings get a
*predicted* twin per program (ANALYSIS.md "Resource analysis").

Three read-only passes on the fluid/ir_passes.py Pass substrate (same
AnalysisPass discipline as the verifier — never mutates, never bumps
the program version):

  analyze_liveness_pass     per-var lifetime intervals over the
      linearized global-block op order.  Persistables are pinned for
      the whole program (params/buffers the scope carries); feeds and
      data vars are live from op 0; everything else lives
      [first write, last read] (fetches extend to the end).  A
      sub-block's locals are LOOP-RESIDENT: a while/recurrent body's
      working set exists for the whole owning op, so the entire
      subtree's vars count at that op's point in the timeline.

  analyze_memory_plan_pass  folds the intervals into a per-op live-byte
      timeline and its peak: ``peak_bytes = param_bytes + max over ops
      of (live activations + loop-resident state)``.  Var bytes come
      from ``Variable.nbytes_hint`` — dtype-accurate, so an int8
      quantized program statically shows its ~0.3x weight footprint
      with zero special cases.

  analyze_cost_pass         per-op FLOP and HBM-byte estimates over the
      registered lowerings (a formula table for the matmul/conv-class
      ops; element-count defaults elsewhere), rolled up into a static
      roofline: arithmetic intensity, and a time lower bound
      ``max(flops/peak_flops, bytes/peak_bw)`` against the device peaks
      table below.

``analyze_program`` runs all three and returns a typed
:class:`ResourceReport`; ``analyze_artifact`` does the same for a saved
artifact dir — save_inference_model (fp32 or quantized) via its
Program, decode artifacts (decode_meta.bin) via their meta record plus
the slot-table KV-cache bytes, save_aot dirs via their state payload.
``check_fit`` is the serving admission gate model_registry.load_model
runs per replica BEFORE any build/warm work (SERVING.md).
"""

import json
import os

from ..fluid.ir_passes import register_pass
from .verifier import AnalysisPass

__all__ = [
    "ResourceReport", "ResourceFitError", "analyze_program",
    "analyze_artifact", "check_fit", "device_memory_bytes",
    "device_peaks", "rows_a_weight_read", "RESOURCE_PASSES",
]


# ---------------------------------------------------------------------------
# device peaks — the denominator of the static roofline
# ---------------------------------------------------------------------------

# (device_kind substring, peak FLOP/s dense bf16, HBM bytes/s, HBM
# capacity bytes).  The v5e rows are Google Cloud's published "TPU v5e"
# numbers (197 TFLOP/s bf16, 819 GB/s, 16 GB); the other TPU rows are
# public datasheet numbers.
_TPU_PEAKS = (
    ("v5 lite", 197e12, 819e9, 16 << 30),
    ("v5e", 197e12, 819e9, 16 << 30),
    ("v5p", 459e12, 2765e9, 95 << 30),
    ("v4", 275e12, 1228e9, 32 << 30),
    ("v3", 123e12, 900e9, 32 << 30),
    ("v2", 45e12, 700e9, 8 << 30),
)
# a deliberately round placeholder for platform == "cpu" ONLY:
# predictions on the CPU exercise the machinery, they are not believed
_CPU_PEAKS = (1e11, 20e9, 0)


def device_peaks(device=None):
    """{kind, peak_flops, hbm_bytes_per_s, hbm_bytes} for `device` (a
    jax.Device or None for the default device).  The cpu placeholder is
    returned for the cpu platform only; an accelerator kind the table
    does not hold raises — a made-up denominator is worse than none."""
    if device is None:
        import jax
        device = jax.devices()[0]
    platform = str(getattr(device, "platform", ""))
    kind = "%s %s" % (platform, getattr(device, "device_kind", ""))
    if platform == "cpu":
        flops, bw, mem = _CPU_PEAKS
        return {"kind": kind, "peak_flops": flops,
                "hbm_bytes_per_s": bw, "hbm_bytes": mem}
    low = kind.lower()
    for sub, flops, bw, mem in _TPU_PEAKS:
        if sub in low:
            return {"kind": kind, "peak_flops": flops,
                    "hbm_bytes_per_s": bw, "hbm_bytes": mem}
    raise ValueError(
        "no peaks known for device kind %r — add its published numbers "
        "(with their source) to analysis/resources.py" % kind)


def rows_a_weight_read(kind="v5e"):
    """The rows of a bfloat16 matmul whose product takes the TPU `kind` as
    long as the read of the matrix they multiply: peak FLOP/s over HBM
    bytes/s (2 FLOPs a multiply-add, 2 bytes a weight), 240 on a v5e.  What
    a kernel's shape is chosen by at trace time, where the program is
    traced for a chip this process may not hold
    (`inference.decode.held_cap`)."""
    flops, bw = next(row[1:3] for row in _TPU_PEAKS if row[0] == kind)
    return flops / bw


def device_memory_bytes(device=None):
    """Per-replica memory budget for the admission fit check, or None
    when no budget is known (the check then passes trivially).

    Resolution order: ``FLAGS.serving_device_mem_mb`` (> 0: the
    operator's configured budget — the deterministic/testable path);
    the device's own ``memory_stats()['bytes_limit']`` when the backend
    exposes one; the peaks table's HBM capacity for recognized TPU
    kinds.  CPU with no configured flag returns None — host RAM is not
    a serving budget."""
    from ..flags import FLAGS
    mb = int(FLAGS.serving_device_mem_mb)
    if mb > 0:
        return mb << 20
    try:
        if device is not None and hasattr(device, "memory_stats"):
            stats = device.memory_stats() or {}
            limit = stats.get("bytes_limit")
            if limit:
                return int(limit)
    except Exception:
        pass
    peaks = device_peaks(device)
    return int(peaks["hbm_bytes"]) or None


class ResourceFitError(RuntimeError):
    """A model's static per-replica peak-memory estimate exceeds the
    device budget — raised by the serving admission gate BEFORE any
    build/warm work.  Carries ``estimated_bytes`` / ``available_bytes``
    and names both in the message."""

    def __init__(self, what, estimated_bytes, available_bytes,
                 device=None):
        self.what = what
        self.estimated_bytes = int(estimated_bytes)
        self.available_bytes = int(available_bytes)
        self.device = device
        super().__init__(
            "%s does not fit: estimated peak %.1f MiB exceeds the "
            "%.1f MiB device budget%s (estimate %d bytes vs %d "
            "available; raise FLAGS.serving_device_mem_mb or shrink "
            "the placement)"
            % (what, estimated_bytes / (1 << 20),
               available_bytes / (1 << 20),
               " on %s" % device if device is not None else "",
               self.estimated_bytes, self.available_bytes))


# ---------------------------------------------------------------------------
# the typed report
# ---------------------------------------------------------------------------

class ResourceReport:
    """What the static analyzer says about one program/artifact.

    Bytes:  ``param_bytes`` (persistables, dtype-accurate),
    ``activation_peak_bytes`` (max live non-persistable bytes over the
    timeline), ``kv_cache_bytes`` (decode slot table; 0 elsewhere),
    ``peak_bytes`` = params + activation peak + kv cache.
    ``actual_param_bytes`` is filled by ``analyze_artifact`` from the
    on-disk payloads so est-vs-actual is one subtraction.

    Cost:  ``total_flops``, ``total_bytes`` (estimated HBM traffic of
    one step), ``arithmetic_intensity``, ``est_step_ms`` — the roofline
    time lower bound against ``device`` (peaks table row).

    Tables:  ``ops`` (one row per op: block, index, type, est_flops,
    est_bytes, live_bytes), ``per_block`` roll-ups, and
    ``top_contributors`` — the vars holding the most bytes at the peak
    op.  Everything is plain data; ``to_dict()`` is wire-encodable.
    """

    __slots__ = ("what", "batch", "param_bytes", "activation_peak_bytes",
                 "kv_cache_bytes", "actual_param_bytes", "total_flops",
                 "total_bytes", "device", "ops", "per_block",
                 "top_contributors", "peak_op", "n_ops", "precision",
                 "mesh_size", "tp")

    def __init__(self, what="program", batch=1):
        self.what = what
        self.batch = int(batch)
        self.param_bytes = 0
        self.activation_peak_bytes = 0
        self.kv_cache_bytes = 0
        self.actual_param_bytes = None
        self.total_flops = 0
        self.total_bytes = 0
        self.device = device_peaks(None)
        self.ops = []
        self.per_block = []
        self.top_contributors = []
        self.peak_op = None
        self.n_ops = 0
        self.precision = "fp32"
        # devices per replica (SERVING.md "Mesh replicas"): params + KV
        # shard at rest over the mesh, so the PER-DEVICE resident
        # estimate divides by this while activations (replicated
        # compute) do not
        self.mesh_size = 1
        # tensor-parallel compute (SERVING.md "Tensor-parallel
        # compute"): when True, per-STEP traffic also divides by the
        # mesh — each member streams only its resident shard per token,
        # instead of gathering and re-reading the whole model
        self.tp = False

    @property
    def peak_bytes(self):
        return (self.param_bytes + self.activation_peak_bytes
                + self.kv_cache_bytes)

    @property
    def peak_mb(self):
        return self.peak_bytes / float(1 << 20)

    def per_device_bytes(self, mesh_size=None):
        """Estimated resident bytes on EACH member device of a
        `mesh_size`-device replica (default: the report's own
        ``mesh_size``): params + KV cache shard ~1/mesh (ceil), the
        replicated-compute activation peak does not.  mesh_size 1 is
        exactly ``peak_bytes`` — the single-device admission number."""
        m = max(int(self.mesh_size if mesh_size is None else mesh_size),
                1)
        if m == 1:
            return int(self.peak_bytes)
        sharded = int(self.param_bytes) + int(self.kv_cache_bytes)
        return -(-sharded // m) + int(self.activation_peak_bytes)

    @property
    def per_device_mb(self):
        return self.per_device_bytes() / float(1 << 20)

    def per_device_step_bytes(self, mesh_size=None, tp=None):
        """Estimated per-STEP HBM traffic on EACH member device of a
        `mesh_size`-device replica (defaults: the report's own stamped
        ``mesh_size`` / ``tp``).

        Gather mode (tp False — PR 18's replicate-compute contract):
        every member materializes and streams the WHOLE model per step,
        so the per-member traffic is ``total_bytes`` regardless of
        mesh size — sharding at rest buys capacity, not bandwidth.
        Tensor-parallel (tp True): the partitioned program touches only
        the member's resident shard — ceil(total_bytes / m).  This is
        the decode-bandwidth roofline column (ROOFLINE.md) and the
        modeled-bytes basis of bench_serving's --mesh_tp A/B."""
        m = max(int(self.mesh_size if mesh_size is None else mesh_size),
                1)
        t = self.tp if tp is None else bool(tp)
        total = int(self.total_bytes)
        if m == 1 or not t:
            return total
        return -(-total // m)

    def per_device_step_ms(self, mesh_size=None, tp=None):
        """Per-member roofline time lower bound for one step.  Under
        tensor parallelism both the FLOPs and the streamed bytes divide
        by the mesh (each member computes its head/column slice on its
        resident shard); gather mode keeps the single-device number —
        every member does the full step."""
        m = max(int(self.mesh_size if mesh_size is None else mesh_size),
                1)
        t = self.tp if tp is None else bool(tp)
        flops = self.total_flops / float(m if (t and m > 1) else 1)
        t_flop = flops / max(self.device["peak_flops"], 1.0)
        t_mem = (self.per_device_step_bytes(m, t)
                 / max(self.device["hbm_bytes_per_s"], 1.0))
        return max(t_flop, t_mem) * 1000.0

    @property
    def arithmetic_intensity(self):
        if not self.total_bytes:
            return 0.0
        return self.total_flops / float(self.total_bytes)

    @property
    def est_step_ms(self):
        """Roofline time lower bound for one step: whichever of the
        compute and memory ceilings binds."""
        t_flop = self.total_flops / max(self.device["peak_flops"], 1.0)
        t_mem = self.total_bytes / max(self.device["hbm_bytes_per_s"],
                                       1.0)
        return max(t_flop, t_mem) * 1000.0

    def mfu_cap(self):
        """The MFU ceiling this traffic level allows (ROOFLINE.md's
        intensity / machine-balance ratio), in [0, 1]."""
        balance = (self.device["peak_flops"]
                   / max(self.device["hbm_bytes_per_s"], 1.0))
        if not balance:
            return 0.0
        return min(1.0, self.arithmetic_intensity / balance)

    def op_cost(self, block_idx, op_index):
        """(est_flops, est_bytes) for one op, or None — the debugger's
        per-op column hook (fluid/debugger.py costs=)."""
        for row in self.ops:
            if row["block"] == block_idx and row["index"] == op_index:
                return row["est_flops"], row["est_bytes"]
        return None

    def to_dict(self):
        return {
            "what": self.what,
            "batch": self.batch,
            "precision": self.precision,
            "n_ops": self.n_ops,
            "param_bytes": int(self.param_bytes),
            "activation_peak_bytes": int(self.activation_peak_bytes),
            "kv_cache_bytes": int(self.kv_cache_bytes),
            "peak_bytes": int(self.peak_bytes),
            "peak_mb": round(self.peak_mb, 3),
            "mesh_size": int(self.mesh_size),
            "tp": bool(self.tp),
            "per_device_bytes": int(self.per_device_bytes()),
            "per_device_mb": round(self.per_device_mb, 3),
            "per_device_step_bytes": int(self.per_device_step_bytes()),
            "per_device_step_ms": round(self.per_device_step_ms(), 6),
            "actual_param_bytes": self.actual_param_bytes,
            "total_flops": int(self.total_flops),
            "total_bytes": int(self.total_bytes),
            "arithmetic_intensity": round(self.arithmetic_intensity, 3),
            "est_step_ms": round(self.est_step_ms, 6),
            "mfu_cap": round(self.mfu_cap(), 4),
            "device": dict(self.device),
            "peak_op": self.peak_op,
            "per_block": list(self.per_block),
            "top_contributors": list(self.top_contributors),
        }

    def render(self, top_n=5):
        """Human table for lint_program --report."""
        d = self.to_dict()
        lines = [
            "%s  (batch=%d, %s, %d ops, device %s)"
            % (self.what, self.batch, self.precision, self.n_ops,
               self.device["kind"] or "?"),
            "  params      %10.2f MiB%s"
            % (self.param_bytes / (1 << 20),
               "" if self.actual_param_bytes is None else
               "   (actual %.2f MiB, delta %+.1f%%)"
               % (self.actual_param_bytes / (1 << 20),
                  100.0 * (self.param_bytes - self.actual_param_bytes)
                  / max(self.actual_param_bytes, 1))),
            "  activations %10.2f MiB peak"
            % (self.activation_peak_bytes / (1 << 20)),
        ]
        if self.kv_cache_bytes:
            lines.append("  kv cache    %10.2f MiB"
                         % (self.kv_cache_bytes / (1 << 20)))
        lines += [
            "  peak HBM    %10.2f MiB" % self.peak_mb,
            "  cost        %.3f GFLOP, %.2f MiB moved, intensity "
            "%.1f FLOP/B" % (self.total_flops / 1e9,
                             self.total_bytes / (1 << 20),
                             self.arithmetic_intensity),
            "  roofline    >= %.3f ms/step, MFU cap %.1f%%"
            % (self.est_step_ms, 100.0 * self.mfu_cap()),
        ]
        if self.mesh_size > 1:
            lines.append(
                "  per member  %10.2f MiB resident, %.2f MiB moved"
                "/step, >= %.3f ms/step  (mesh=%d, %s)"
                % (self.per_device_mb,
                   self.per_device_step_bytes() / (1 << 20),
                   self.per_device_step_ms(), self.mesh_size,
                   "tensor-parallel" if self.tp else "gather"))
        if len(self.per_block) > 1:
            lines.append("  per block:")
            for row in self.per_block:
                lines.append(
                    "    block %-3d %5d ops  %10.3f GFLOP  %10.2f MiB"
                    % (row["block"], row["ops"],
                       row["est_flops"] / 1e9,
                       row["est_bytes"] / (1 << 20)))
        if self.top_contributors:
            lines.append("  top peak contributors:")
            for row in self.top_contributors[:top_n]:
                lines.append("    %-32s %10.2f MiB  [%s]"
                             % (row["var"], row["bytes"] / (1 << 20),
                                row["kind"]))
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# liveness
# ---------------------------------------------------------------------------

def _subtree_var_bytes(block, batch, acc):
    """Sum of nbytes of every non-persistable var DECLARED in `block`'s
    subtree (loop-resident working set of a sub-block op), recording
    each into `acc` for the contributor table."""
    total = 0
    for name, v in block.vars.items():
        if v.persistable:
            continue
        nb = v.nbytes_hint(batch=batch)
        if nb:
            total += nb
            acc[name] = max(acc.get(name, 0), nb)
    for op in block.ops:
        sub = op.attrs.get("sub_block")
        if sub is not None:
            total += _subtree_var_bytes(sub, batch, acc)
    return total


@register_pass
class AnalyzeLivenessPass(AnalysisPass):
    """Computes ``intervals``: {var_name: (start, end, bytes, kind)}
    over the linearized global-block op order, plus ``resident``:
    {op_index: loop-resident sub-block bytes} and ``resident_vars``
    per-op contributor maps.  Results land in the pass attrs (read by
    analyze_program / the memory-plan pass); the diagnostics list stays
    empty — resource analysis reports numbers, not findings."""

    name = "analyze_liveness_pass"

    def analyze(self, program, diagnostics):
        batch = int(self.get("batch") or 1)
        feeds = frozenset(self.get("feeds") or ())
        fetches = frozenset(self.get("fetches") or ())
        blk = program.global_block()
        n = len(blk.ops)
        first_write, last_touch = {}, {}
        resident, resident_vars = {}, {}
        for i, op in enumerate(blk.ops):
            reads = [x for x in op.input_arg_names if x]
            writes = [x for x in op.output_arg_names if x]
            sub = op.attrs.get("sub_block")
            if sub is not None:
                reads.extend(x for x in self._external_reads(sub) if x)
                writes.extend(x for x in self._subtree_writes(sub) if x)
                acc = {}
                resident[i] = _subtree_var_bytes(sub, batch, acc)
                resident_vars[i] = acc
            for x in reads:
                last_touch[x] = i
            for x in writes:
                first_write.setdefault(x, i)
                last_touch[x] = i
        params, intervals = {}, {}
        for v in program.list_vars():
            if v.persistable:
                nb = v.nbytes_hint(batch=batch) or 0
                # shared global-block Parameters appear once per name
                params[v.name] = max(params.get(v.name, 0), nb)
        for name, v in blk.vars.items():
            if v.persistable or name not in last_touch:
                continue
            nb = v.nbytes_hint(batch=batch)
            if not nb:
                continue
            if v.is_data or name in feeds:
                start, kind = 0, "feed"
            else:
                start, kind = first_write.get(name, 0), "activation"
            end = last_touch[name]
            if name in fetches:
                end = max(end, n - 1 if n else 0)
            intervals[name] = (start, end, nb, kind)
        self.attrs["intervals"] = intervals
        self.attrs["param_bytes_by_var"] = params
        self.attrs["resident"] = resident
        self.attrs["resident_vars"] = resident_vars
        self.attrs["n_ops"] = n


# ---------------------------------------------------------------------------
# memory plan
# ---------------------------------------------------------------------------

@register_pass
class AnalyzeMemoryPlanPass(AnalysisPass):
    """Folds the liveness intervals into the per-op live-byte timeline:
    ``timeline`` [live activation+resident bytes per global op],
    ``param_bytes``, ``activation_peak_bytes``, ``peak_op`` and the
    ``top_contributors`` at the peak.  Expects the liveness pass attrs
    under ``liveness`` (analyze_program wires them through)."""

    name = "analyze_memory_plan_pass"

    def analyze(self, program, diagnostics):
        live = self.get("liveness") or {}
        intervals = live.get("intervals") or {}
        params = live.get("param_bytes_by_var") or {}
        resident = live.get("resident") or {}
        resident_vars = live.get("resident_vars") or {}
        n = live.get("n_ops") or 0
        # sweep-line: +bytes at start, -bytes after end
        delta = [0] * (n + 1)
        for (start, end, nb, _kind) in intervals.values():
            delta[start] += nb
            if end + 1 <= n:
                delta[end + 1] -= nb
        timeline, cur, peak, peak_op = [], 0, 0, None
        for i in range(n):
            cur += delta[i]
            total = cur + resident.get(i, 0)
            timeline.append(total)
            if total > peak:
                peak, peak_op = total, i
        top = []
        if peak_op is not None:
            for name, (start, end, nb, kind) in intervals.items():
                if start <= peak_op <= end:
                    top.append({"var": name, "bytes": nb, "kind": kind})
            for name, nb in (resident_vars.get(peak_op) or {}).items():
                top.append({"var": name, "bytes": nb, "kind": "loop"})
        for name, nb in params.items():
            top.append({"var": name, "bytes": nb, "kind": "param"})
        top.sort(key=lambda r: (-r["bytes"], r["var"]))
        self.attrs["param_bytes"] = sum(params.values())
        self.attrs["activation_peak_bytes"] = peak
        self.attrs["timeline"] = timeline
        self.attrs["peak_op"] = peak_op
        self.attrs["top_contributors"] = top


# ---------------------------------------------------------------------------
# per-op FLOP / byte cost model
# ---------------------------------------------------------------------------

def _numel(shape, batch):
    n = 1
    for d in shape or ():
        n *= int(batch) if (d is None or int(d) < 0) else int(d)
    return int(n)


def _shape_of(block, name, batch):
    v = block._find_var_recursive(name)
    if v is None or v.shape is None:
        return None
    return tuple(int(batch) if (d is None or int(d) < 0) else int(d)
                 for d in v.shape)


def _first_in(op, slot):
    names = op.inputs.get(slot) or []
    return names[0] if names else None


def _out_numel(op, block, batch):
    total = 0
    for names in op.outputs.values():
        for x in names:
            s = _shape_of(block, x, batch)
            if s is not None:
                total += _numel(s, batch)
    return total


def _flops_mul(op, block, batch):
    # X [.., K] x Y [K, N]: 2*M*K*N = 2 * out_elems * K
    y = _shape_of(block, _first_in(op, "Y"), batch)
    k = y[0] if y else 1
    return 2 * _out_numel(op, block, batch) * k


def _flops_matmul(op, block, batch):
    x = _shape_of(block, _first_in(op, "X"), batch)
    if not x or len(x) < 2:
        return _out_numel(op, block, batch)
    k = x[-2] if op.attrs.get("transpose_X") else x[-1]
    return 2 * _out_numel(op, block, batch) * k


def _flops_conv(op, block, batch):
    # Filter [O, I/g, kh, kw]: 2 * out_elems * (I/g * kh * kw) — exact
    # for grouped and depthwise convs alike
    f = _shape_of(block, _first_in(op, "Filter"), batch)
    if not f or len(f) < 4:
        return _out_numel(op, block, batch)
    return 2 * _out_numel(op, block, batch) * f[1] * f[2] * f[3]


def _flops_conv_transpose(op, block, batch):
    # Filter [I, O/g, kh, kw]: every input element scatters into
    # O/g * kh * kw outputs
    f = _shape_of(block, _first_in(op, "Filter"), batch)
    x = _shape_of(block, _first_in(op, "Input") or _first_in(op, "X"),
                  batch)
    if not f or len(f) < 4 or not x:
        return _out_numel(op, block, batch)
    return 2 * _numel(x, batch) * f[1] * f[2] * f[3]


def _flops_flash_attention(op, block, batch):
    q = _shape_of(block, _first_in(op, "Q"), batch)
    if not q or len(q) < 4:
        return _out_numel(op, block, batch)
    b, s, h, d = q[0], q[1], q[2], q[3]
    return 4 * b * h * s * s * d          # QK^T + PV, 2 FLOP/MAC each


def _flops_pool(op, block, batch):
    k = op.attrs.get("ksize") or op.attrs.get("pool_size") or (1,)
    if isinstance(k, (int, float)):
        k = (int(k),)
    win = 1
    for d in k:
        win *= int(d)
    return _out_numel(op, block, batch) * win


def _in_numel(op, block, batch):
    total = 0
    for names in op.inputs.values():
        for x in names:
            s = _shape_of(block, x, batch)
            if s is not None:
                total += _numel(s, batch)
    return total


# op type -> flops(op, block, batch).  The contraction class gets exact
# formulas; normalization/softmax get a small per-element constant; the
# default (absent here) is one FLOP per output element — elementwise /
# activation / copy ops are all bandwidth-bound anyway, so the BYTES
# side (below) is what prices them.
_FLOP_MODELS = {
    "mul": _flops_mul,
    "dequant_mul": _flops_mul,
    "matmul": _flops_matmul,
    "conv2d": _flops_conv,
    "depthwise_conv2d": _flops_conv,
    "conv3d": _flops_conv,
    "dequant_conv2d": _flops_conv,
    "conv2d_transpose": _flops_conv_transpose,
    "conv3d_transpose": _flops_conv_transpose,
    "flash_attention": _flops_flash_attention,
    "pool2d": _flops_pool,
    "softmax": lambda op, blk, b: 5 * _out_numel(op, blk, b),
    "log_softmax": lambda op, blk, b: 5 * _out_numel(op, blk, b),
    "sequence_softmax": lambda op, blk, b: 5 * _out_numel(op, blk, b),
    "softmax_with_cross_entropy":
        lambda op, blk, b: 6 * _in_numel(op, blk, b),
    "batch_norm": lambda op, blk, b: 8 * _out_numel(op, blk, b),
    "layer_norm": lambda op, blk, b: 8 * _out_numel(op, blk, b),
    "group_norm": lambda op, blk, b: 8 * _out_numel(op, blk, b),
    "reduce_sum": lambda op, blk, b: _in_numel(op, blk, b),
    "reduce_mean": lambda op, blk, b: _in_numel(op, blk, b),
    "mean": lambda op, blk, b: _in_numel(op, blk, b),
    "sum": lambda op, blk, b: _in_numel(op, blk, b),
    # gathers move bytes, they do not multiply
    "lookup_table": lambda op, blk, b: 0,
    "dequant_lookup_table": lambda op, blk, b: 0,
}


def _op_bytes(op, block, batch):
    """Estimated HBM traffic of one op: bytes of every distinct input
    var read + every output var written.  lookup_table-class gathers
    count the GATHERED rows, not the whole table (the table itself is
    priced once in param_bytes, and a step touches only ids x D of
    it)."""
    from ..fluid import core as fcore
    seen, total = set(), 0
    gather = op.type in ("lookup_table", "dequant_lookup_table")
    for slot, names in op.inputs.items():
        for x in names:
            if not x or x in seen:
                continue
            seen.add(x)
            v = block._find_var_recursive(x)
            if v is None or v.shape is None:
                continue
            if gather and slot == "W":
                ids = _shape_of(block, _first_in(op, "Ids"), batch)
                rows = _numel(ids, batch) if ids else 1
                width = _numel(v.shape[1:], batch)
                total += rows * width * fcore.dtype_size(v.dtype)
                continue
            total += v.nbytes_hint(batch=batch) or 0
    for names in op.outputs.values():
        for x in names:
            if not x or x in seen:
                continue
            seen.add(x)
            v = block._find_var_recursive(x)
            if v is not None:
                total += v.nbytes_hint(batch=batch) or 0
    return total


class _GradShim:
    """A ``<base>_grad`` op viewed through its forward op's slot
    layout: the generated grad ops carry the forward inputs under
    their original slot names plus ``Out:<slot>`` (forward outputs)
    and ``GRAD:<slot>`` companions (fluid/backward.py), so the base
    FLOP formula evaluates directly — the backward of a contraction
    costs ~2x the forward (dgrad + wgrad)."""

    __slots__ = ("type", "inputs", "outputs", "attrs")

    def __init__(self, op):
        self.type = op.type[:-len("_grad")]
        self.inputs = {k: v for k, v in op.inputs.items()
                       if not k.startswith(("Out:", "GRAD:"))}
        self.outputs = {k[len("Out:"):]: v
                        for k, v in op.inputs.items()
                        if k.startswith("Out:")}
        self.attrs = op.attrs


def _op_flops(op, block, batch):
    model = _FLOP_MODELS.get(op.type)
    if model is not None:
        return int(model(op, block, batch))
    if op.type.endswith("_grad"):
        base = _FLOP_MODELS.get(op.type[:-len("_grad")])
        if base is not None:
            return 2 * int(base(_GradShim(op), block, batch))
    return _out_numel(op, block, batch)


@register_pass
class AnalyzeCostPass(AnalysisPass):
    """Per-op FLOP/byte estimates over EVERY block (sub-block bodies
    count once — trip counts are not static knowledge), rolled up per
    block and in total.  Results in attrs: ``op_costs`` (list of row
    dicts), ``per_block``, ``total_flops``, ``total_bytes``."""

    name = "analyze_cost_pass"

    def analyze(self, program, diagnostics):
        batch = int(self.get("batch") or 1)
        rows, per_block = [], []
        total_flops = total_bytes = 0
        for block in program.blocks:
            b_flops = b_bytes = 0
            for idx, op in enumerate(block.ops):
                try:
                    flops = _op_flops(op, block, batch)
                except Exception:
                    flops = 0
                nbytes = _op_bytes(op, block, batch)
                rows.append({"block": block.idx, "index": idx,
                             "type": op.type, "est_flops": flops,
                             "est_bytes": nbytes})
                b_flops += flops
                b_bytes += nbytes
            per_block.append({"block": block.idx, "ops": len(block.ops),
                              "est_flops": b_flops,
                              "est_bytes": b_bytes})
            total_flops += b_flops
            total_bytes += b_bytes
        self.attrs["op_costs"] = rows
        self.attrs["per_block"] = per_block
        self.attrs["total_flops"] = total_flops
        self.attrs["total_bytes"] = total_bytes


RESOURCE_PASSES = (
    "analyze_liveness_pass",
    "analyze_memory_plan_pass",
    "analyze_cost_pass",
)


# ---------------------------------------------------------------------------
# surfaces
# ---------------------------------------------------------------------------

def analyze_program(program, feeds=None, fetches=None, batch=1,
                    device=None, what="program"):
    """Run the three resource passes; returns a :class:`ResourceReport`.

    `batch` substitutes every dynamic (-1) dim — pass the serving
    bucket / training batch for honest numbers (the default 1 gives
    the per-sample floor).  `device` (jax.Device or None) selects the
    roofline denominator."""
    from ..fluid.ir_passes import get_pass
    live = get_pass("analyze_liveness_pass", batch=batch,
                    feeds=tuple(feeds or ()),
                    fetches=tuple(fetches or ()))
    live.apply(program)
    mem = get_pass("analyze_memory_plan_pass", liveness=live.attrs)
    mem.apply(program)
    cost = get_pass("analyze_cost_pass", batch=batch)
    cost.apply(program)

    rep = ResourceReport(what=what, batch=batch)
    rep.device = device_peaks(device)
    rep.param_bytes = int(mem.attrs["param_bytes"])
    rep.activation_peak_bytes = int(mem.attrs["activation_peak_bytes"])
    rep.peak_op = mem.attrs["peak_op"]
    rep.top_contributors = mem.attrs["top_contributors"][:16]
    # live_bytes column: join the timeline onto the global-block rows
    timeline = mem.attrs["timeline"]
    rep.ops = cost.attrs["op_costs"]
    for row in rep.ops:
        if row["block"] == 0 and row["index"] < len(timeline):
            row["live_bytes"] = int(timeline[row["index"]])
    rep.per_block = cost.attrs["per_block"]
    rep.total_flops = int(cost.attrs["total_flops"])
    rep.total_bytes = int(cost.attrs["total_bytes"])
    rep.n_ops = sum(len(b.ops) for b in program.blocks)
    rep.precision = "int8" if any(
        op.type.startswith("dequant_")
        for op in program.global_block().ops) else "fp32"
    return rep


def _decode_report(path, meta, decode_slots, device, what,
                   kv_cache_dtype=None, fuse_steps=None):
    """Resource report for a decode artifact (no Program IR): weights
    from the state payload, the slot-table KV cache from the meta
    geometry — the bytes that bound decode slots (SERVING.md).

    ``fuse_steps`` prices the FUSED decode dispatch (SERVING.md "Fused
    multi-step decode"): one dispatch runs up to N steps on-device, so
    ``total_flops`` / ``total_bytes`` scale by N while the PEAK is
    unchanged — the while_loop carries the same one-token working set
    and the same slot table through every trip, so fusing never moves
    the admission gate, only the per-dispatch work it amortizes.

    The cache prices at its DTYPE's width (QUANTIZE.md "Quantized KV
    cache"): `kv_cache_dtype` (a load_model override) > the artifact's
    decode_meta pin > FLAGS.serving_kv_cache_dtype > fp32 — the same
    resolution the GenerativePredictor makes, so the admission fit
    check statically reads ~0.25x KV bytes for an int8-cache load
    (int8 slots + the per-(layer,head) fp32 scale table)."""
    import numpy as np
    from ..flags import FLAGS
    from ..inference import slot_state
    from ..inference.decode import block_of, normalize_kv_dtype
    n_slots = int(decode_slots or FLAGS.serving_decode_slots)
    L = int(meta["n_layers"])
    D = int(meta["d_model"])
    kv_dtype = normalize_kv_dtype(
        kv_cache_dtype if kv_cache_dtype is not None
        else (meta.get("kv_cache_dtype")
              or FLAGS.serving_kv_cache_dtype))
    rep = ResourceReport(what=what, batch=n_slots)
    rep.device = device_peaks(device)
    state_path = os.path.join(path, "decode_state.bin")
    try:
        from ..native import wire
        with open(state_path, "rb") as f:
            state = wire.decode(f.read())
        rep.param_bytes = sum(int(np.asarray(v).nbytes)
                              for v in state.values())
        rep.actual_param_bytes = rep.param_bytes
        n_params = sum(int(np.asarray(v).size) for v in state.values())
    except Exception:
        rep.param_bytes = os.path.getsize(state_path) \
            if os.path.exists(state_path) else 0
        rep.actual_param_bytes = rep.param_bytes
        n_params = rep.param_bytes // 4
    # the slot state of every kind, priced by the record that shapes it
    # (`slot_state.state_bytes`): GenerativePredictor.kv_cache_bytes reads
    # the same numbers (pinned by tests/test_resources.py)
    _, slot_bytes = slot_state.state_bytes(meta, block_of(meta), n_slots,
                                           device, kv_dtype)
    rep.kv_cache_bytes = slot_bytes["kv_cache_bytes"]
    # decode-step working set: one token's activations per slot, and the
    # conv layers' carried state
    rep.activation_peak_bytes = n_slots * D * 4 * (L + 2) \
        + slot_bytes["conv_state_bytes"]
    # one decode step: every weight multiplies once per slot, and the
    # whole KV cache streams through the attention gather; a fused
    # dispatch is N such steps back-to-back at the same peak
    fuse = max(int(fuse_steps or 1), 1)
    rep.total_flops = 2 * n_params * n_slots * fuse
    rep.total_bytes = (rep.param_bytes + rep.kv_cache_bytes) * fuse
    rep.n_ops = 0
    return rep


def _with_mesh(rep, mesh_size, tp=None):
    """Stamp a replica mesh size (and tensor-parallel compute mode) on
    a report (SERVING.md "Mesh replicas" / "Tensor-parallel compute")
    — makes ``per_device_bytes`` the 1/mesh sharded-at-rest estimate
    the per-member fit check admits on, and ``per_device_step_bytes``
    the per-member traffic the bandwidth roofline prices."""
    if mesh_size:
        rep.mesh_size = max(int(mesh_size), 1)
    if tp is not None:
        rep.tp = bool(tp)
    return rep


def analyze_artifact(path, batch=1, decode_slots=None, device=None,
                     kv_cache_dtype=None, fuse_steps=None,
                     mesh_size=None, tp=None):
    """Static resource report for a saved artifact dir — the admission
    gate's input, and lint_program --report's row source.

    save_inference_model dirs (fp32 or quantized) analyze their
    serialized Program and also total the on-disk payload bytes into
    ``actual_param_bytes``; decode artifacts (decode_meta.bin) come
    from their meta geometry + KV slot table priced at the cache dtype
    (`kv_cache_dtype` overrides the artifact's pin — the load_model
    knob, and ``fuse_steps`` prices the N-step fused dispatch at N·step
    FLOPs/bytes with the peak unchanged); save_aot dirs (aot_meta.bin)
    from their state payload + feed specs.  ``mesh_size`` stamps a
    mesh-replica shape on the report: total bytes are unchanged, but
    ``per_device_bytes`` (what `check_fit` prices per mesh member)
    reads params + KV at ~1/mesh_size.  ``tp`` marks tensor-parallel
    compute (FLAGS.mesh_tp): ``per_device_step_bytes`` /
    ``per_device_step_ms`` then divide the per-step traffic roofline
    by the mesh too."""
    from ..inference.decode import DECODE_META
    dm = os.path.join(path, DECODE_META)
    if os.path.exists(dm):
        from ..native import wire
        with open(dm, "rb") as f:
            meta = wire.decode(f.read())
        return _with_mesh(
            _decode_report(path, meta, decode_slots, device, path,
                           kv_cache_dtype=kv_cache_dtype,
                           fuse_steps=fuse_steps), mesh_size, tp=tp)
    am = os.path.join(path, "aot_meta.bin")
    if os.path.exists(am):
        from ..native import wire
        with open(am, "rb") as f:
            meta = wire.decode(f.read())
        rep = ResourceReport(what=path, batch=batch)
        rep.device = device_peaks(device)
        state_path = os.path.join(path, "aot_state.bin")
        if os.path.exists(state_path):
            rep.param_bytes = os.path.getsize(state_path)
            rep.actual_param_bytes = rep.param_bytes
        import numpy as np
        act = 0
        for name, spec in (meta.get("feed_specs") or {}).items():
            shape = [int(batch) if int(d) < 0 else int(d)
                     for d in spec["shape"]]
            act += int(np.prod(shape)) * np.dtype(spec["dtype"]).itemsize
        rep.activation_peak_bytes = act
        rep.total_bytes = rep.param_bytes + act
        rep.total_flops = (rep.param_bytes // 4) * 2 * int(batch)
        return _with_mesh(rep, mesh_size, tp=tp)
    model_file = os.path.join(path, "__model__")
    if not os.path.exists(model_file):
        raise FileNotFoundError(
            "%s: no __model__ / aot_meta.bin / decode_meta.bin — not a "
            "serving artifact directory" % path)
    from ..fluid.framework import Program
    with open(model_file) as f:
        meta = json.load(f)
    program = Program.parse_from_string(meta["program"])
    rep = analyze_program(program, feeds=meta["feed_names"],
                          fetches=meta["fetch_names"], batch=batch,
                          device=device, what=path)
    actual = 0
    gb = program.global_block()
    for name, v in gb.vars.items():
        if not v.persistable:
            continue
        fpath = os.path.join(path, name.replace("/", "__") + ".npy")
        if os.path.exists(fpath):
            # .npy header is ~128 bytes of metadata, not payload
            actual += max(os.path.getsize(fpath) - 128, 0)
    if actual:
        rep.actual_param_bytes = actual
    return _with_mesh(rep, mesh_size, tp=tp)


def check_fit(report, device=None, what=None, replicas=1,
              mesh_size=None):
    """Serving admission gate: raise :class:`ResourceFitError` when the
    report's per-replica peak exceeds the device budget
    (``device_memory_bytes``).  Returns (estimated, available) — with
    available None (no known budget) the check passes trivially.

    ``replicas`` multiplies the estimate for placements putting several
    replicas on ONE device (the [None] single-default-device spec).

    ``mesh_size`` > 1 (SERVING.md "Mesh replicas") prices the
    PER-MEMBER estimate — params + KV shard ~1/mesh at rest, the
    replicated-compute activation peak does not — against ONE member
    device's budget (`device` should be that member): how a model too
    big for any single chip admits on a mesh.  Default: the report's
    own stamped ``mesh_size``."""
    avail = device_memory_bytes(device)
    est = int(report.per_device_bytes(mesh_size)) \
        * max(int(replicas), 1)
    if avail is not None and est > avail:
        raise ResourceFitError(what or report.what, est, avail,
                               device=device)
    return est, avail
