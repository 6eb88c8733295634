"""Device-mesh helpers — the TPU analogue of the reference's device lists +
NCCLContextMap (platform/nccl_helper.h:82, parallel_executor.cc:113).

A Mesh over ICI replaces per-device CUDA streams and NCCL communicators:
collectives are compiled into the step by XLA's SPMD partitioner. Axis
conventions (used across the framework):

  data   — batch/data parallelism (grad allreduce ≅ all_reduce_op_handle)
  model  — tensor parallelism for sharded weights/embeddings
  seq    — sequence/context parallelism (ring attention milestone)
  pipe   — pipeline stages
  expert — MoE expert parallelism
"""

import os
import re

import numpy as np

__all__ = ["make_mesh", "data_parallel_mesh", "local_device_count",
           "MeshGroup", "MeshMemberLost", "as_mesh_group",
           "set_member_poison", "check_member_poison",
           "tp_param_pspec", "tp_supported",
           "DATA_AXIS", "MODEL_AXIS", "SEQ_AXIS", "PIPE_AXIS", "EXPERT_AXIS"]

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"


def _accel_devices():
    """Device list behind the fluid `use_cuda` flag: ALWAYS the default
    JAX backend (TPU on silicon, CPU on the virtual test mesh). The
    reference's flag picks CUDA vs host-CPU places; this framework has
    no CUDA backend, and `use_cuda=False` (the only spelling the fluid
    API has for "no CUDA") must NOT silently demote a TPU program to
    host-CPU execution — that bug cost 195x on the measured
    ParallelExecutor throughput. Callers that genuinely want a host-CPU
    mesh on an accelerator host pass an explicit mesh (see
    tools/debug_parity.py)."""
    import jax
    return jax.devices()


def local_device_count(use_cuda=True):
    """Device count, honoring CPU_NUM like the reference's parallel_executor.py
    (python wrapper :32 builds places from CUDA_VISIBLE_DEVICES / CPU_NUM)."""
    devs = _accel_devices()
    if not use_cuda and devs and devs[0].platform == "cpu":
        cpu_num = int(os.environ.get("CPU_NUM", len(devs)))
        return min(cpu_num, len(devs)) or 1
    return len(devs)


def make_mesh(axis_sizes, devices=None):
    """axis_sizes: dict axis-name -> size (row-major over the device list)."""
    import jax
    from jax.sharding import Mesh
    if devices is None:
        devices = jax.devices()
    names = tuple(axis_sizes.keys())
    sizes = tuple(axis_sizes.values())
    n = int(np.prod(sizes))
    if n > len(devices):
        raise ValueError("mesh needs %d devices, have %d" %
                         (n, len(devices)))
    dev_array = np.array(devices[:n]).reshape(sizes)
    return Mesh(dev_array, names)


def data_parallel_mesh(num_devices=None, use_cuda=True):
    devs = _accel_devices()
    if num_devices is None:
        num_devices = local_device_count(use_cuda)
    return make_mesh({DATA_AXIS: num_devices}, devs[:num_devices])


def shard_map_no_rep_check(fn, mesh, in_specs, out_specs):
    """shard_map with replication checking disabled — required for shard
    bodies that invoke Pallas kernels (jax has no replication rule for
    pallas_call)."""
    from jax import shard_map
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


# ---------------------------------------------------------------------------
# serving mesh groups: one replica = a device mesh (SERVING.md "Mesh
# replicas")
# ---------------------------------------------------------------------------


class MeshMemberLost(RuntimeError):
    """A device inside a serving mesh group stopped answering: the whole
    group is one logical replica, so losing ONE member kills the lane
    (marked dead, never wedged) — in-flight requests on that lane fail
    with this type while sibling lanes keep serving, and the fleet
    controller rebuilds the lane from its persisted spec (the chaos
    `mesh-member-loss` scenario pins this contract)."""


class MeshGroup:
    """An ordered group of >= 2 local devices acting as ONE logical
    serving device: the placement unit `model_registry.resolve_placement`
    emits for `mesh:RxC` / `a+b` specs and the serving predictors build
    against.

    Ducks the `jax.Device` attribute surface the serving stack touches
    (`platform`, `id`, `device_kind`), so everything that merely labels
    or fingerprints a placement keeps working; code that MOVES data
    branches on `isinstance(dev, MeshGroup)` and uses the sharding
    helpers below.

    Sharding discipline — two compute modes over the same at-rest
    layout family (SERVING.md "Mesh replicas"):

    * shard-at-rest (default, PR 18): parameters and the decode KV slot
      table are SHARDED AT REST over the 1-D `model` axis (per-device
      resident bytes ~ 1/mesh_size — the fit-check unlock); compute
      runs REPLICATED — every traced phase gathers its operands back to
      replicated before any math (see the predictors' `_mesh_wrap`), so
      no float reduction is ever reordered across members and a mesh
      replica's stream is bit-identical to a single-device replica's.
      HBM capacity scales with the mesh; per-step traffic does not.

    * tensor-parallel (`FLAGS.mesh_tp`, SERVING.md "Tensor-parallel
      compute"): the program lowers as one shard_map'd executable over
      this mesh — weights placed by `tp_param_pspec` (Megatron
      column->row pairs, one psum per pair), attention head-parallel
      on the resident KV shard, embedding row-sharded over vocab.
      Params and KV never materialize unsharded, so per-step HBM
      traffic per member drops ~1/mesh_size too (the decode-roofline
      win). Streams are top-1 identical; activations downstream of a
      row-split matmul carry psum reduction-order noise at float
      tolerance (the documented demotion from bit-exact).

    Both are the MLPerf pods paper's weight-update-sharding blueprint
    applied to inference; TP adds the Megatron intra-layer split."""

    __slots__ = ("devices", "shape", "_mesh")

    def __init__(self, devices, shape=None):
        devices = tuple(devices)
        if len(devices) < 2:
            raise ValueError(
                "a mesh group needs >= 2 devices, got %d (a 1-device "
                "mesh is just the device — resolve_placement collapses "
                "it)" % len(devices))
        seen = set()
        for d in devices:
            key = (getattr(d, "platform", None), getattr(d, "id", None))
            if key in seen:
                raise ValueError(
                    "duplicate device %s:%s in mesh group" % key)
            seen.add(key)
        if shape is None:
            shape = (len(devices),)
        shape = tuple(int(s) for s in shape)
        if int(np.prod(shape)) != len(devices):
            raise ValueError(
                "mesh shape %r does not cover %d devices"
                % (shape, len(devices)))
        self.devices = devices
        self.shape = shape
        self._mesh = None

    # -- jax.Device duck surface (labels / fingerprints only) -----------

    @property
    def platform(self):
        return getattr(self.devices[0], "platform", "cpu")

    @property
    def id(self):
        return getattr(self.devices[0], "id", 0)

    @property
    def device_kind(self):
        # namespaced per mesh size so a meshed executable fingerprint
        # can never collide with a single-device one
        return "%s/mesh%d" % (
            getattr(self.devices[0], "device_kind", ""), len(self.devices))

    # -- group surface --------------------------------------------------

    @property
    def mesh_size(self):
        return len(self.devices)

    @property
    def primary(self):
        """The first member — where mesh-incapable callers (serialized
        AOT exports) degrade to."""
        return self.devices[0]

    def label(self):
        """'cpu:0+cpu:1' — the wire/spec spelling; resolve_placement
        parses it back, which is what makes page-out / fault-in / resize
        replay a mesh lane spec verbatim."""
        return "+".join("%s:%d" % (getattr(d, "platform", "cpu"),
                                   getattr(d, "id", 0))
                        for d in self.devices)

    def member_labels(self):
        return [lbl for lbl in self.label().split("+")]

    def __repr__(self):
        return "MeshGroup(%s)" % self.label()

    def __eq__(self, other):
        return isinstance(other, MeshGroup) and \
            self.devices == other.devices

    def __hash__(self):
        return hash(self.devices)

    def mesh(self):
        """The jax.sharding.Mesh (1-D over MODEL_AXIS, lazily built)."""
        if self._mesh is None:
            from jax.sharding import Mesh
            self._mesh = Mesh(np.array(self.devices), (MODEL_AXIS,))
        return self._mesh

    def replicated(self):
        """NamedSharding replicating an array on every member."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh(), P())

    def _axis_sharding(self, ndim, axis):
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = [None] * ndim
        spec[axis] = MODEL_AXIS
        return NamedSharding(self.mesh(), P(*spec))

    def axis_sharding(self, ndim, axis):
        """NamedSharding splitting `axis` of an ndim-rank array over the
        group's `model` axis — the public spelling TP compute uses for
        activations (e.g. head-sharded q/k/v)."""
        return self._axis_sharding(int(ndim), int(axis))

    def tp_param_sharding(self, name, shape):
        """At-rest sharding for one NAMED decode parameter under
        tensor-parallel compute: `tp_param_pspec`'s axis grammar bound
        to this group's mesh. Unlike `param_sharding` (which scans for
        any divisible axis), placement here is dictated by the op's
        role in the partitioned program — a row-parallel weight MUST
        shard its input axis or the local matmul shapes are wrong."""
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh(), tp_param_pspec(name, shape))

    def param_sharding(self, shape):
        """At-rest sharding for one parameter: the last axis whose size
        divides the mesh (output-column parallel for the common [in,
        out] case), scanning right to left; small / indivisible arrays
        (biases, norms) replicate."""
        n = self.mesh_size
        shape = tuple(int(s) for s in shape)
        for ax in range(len(shape) - 1, -1, -1):
            if shape[ax] >= n and shape[ax] % n == 0:
                return self._axis_sharding(len(shape), ax)
        return self.replicated()

    def kv_sharding(self, shape):
        """At-rest sharding for a [L, n_slots, S, H * Dh] KV slot table
        (a position one flat row, its heads' features side by side): the
        row's axis first (where the mesh divides the heads a member holds
        whole heads, its contiguous H / m * Dh lanes: the per-head
        independence axis the decode kernel already respects), then
        slots, then layers; replicate only when nothing divides."""
        n = self.mesh_size
        shape = tuple(int(s) for s in shape)
        if len(shape) != 4:
            return self.param_sharding(shape)
        for ax in (3, 1, 0):
            if shape[ax] >= n and shape[ax] % n == 0:
                return self._axis_sharding(4, ax)
        return self.replicated()


# ---------------------------------------------------------------------------
# tensor-parallel compute grammar (SERVING.md "Tensor-parallel compute")
# ---------------------------------------------------------------------------

# Megatron-style intra-layer split of the decode transformer, by
# parameter family (the layer prefix 'l<N>_' is stripped before lookup):
#
#   column-parallel  [in, out/m]   wq wk wv (head split), w1, lm_head
#   row-parallel     [in/m, out]   wo, w2 — one psum closes each
#                                  column->row pair; b2 adds after it
#   vocab-row        [V/m, D]      embed — local masked gather + psum
#                                  (exact: one member owns each row,
#                                  the rest contribute true zeros —
#                                  parallel/sharded_embedding.py)
#   sharded bias     [4D/m]        b1 — rides its column pair
#   replicated                     pos, layer norms, b2, lnf
_TP_COLUMN = frozenset(("wq", "wk", "wv", "w1", "lm_head"))
_TP_ROW = frozenset(("wo", "w2", "embed"))
_TP_BIAS = frozenset(("b1",))
_LAYER_PREFIX = re.compile(r"^l\d+_")


def tp_param_pspec(name, shape):
    """jax PartitionSpec for one named decode parameter under
    tensor-parallel compute. Names outside the decode state grammar
    (and wrong-rank shapes) replicate — the safe default, since the
    partitioned program only ever consumes local shards of the families
    above."""
    from jax.sharding import PartitionSpec as P
    base = _LAYER_PREFIX.sub("", str(name))
    ndim = len(tuple(shape))
    if base in _TP_COLUMN and ndim == 2:
        return P(None, MODEL_AXIS)
    if base in _TP_ROW and ndim == 2:
        return P(MODEL_AXIS, None)
    if base in _TP_BIAS and ndim == 1:
        return P(MODEL_AXIS)
    return P()


def tp_supported(mesh_size, n_heads, d_model, vocab_size, d_ff=None):
    """True when the decode dims split evenly over `mesh_size` members —
    the gate `GenerativePredictor` checks before placing state TP.
    Every sharded family must divide exactly: heads for attention/KV,
    d_model for the row-parallel contractions, vocab for the embedding
    rows and lm_head columns, d_ff for the MLP pair."""
    m = int(mesh_size)
    if m < 2:
        return False
    dims = [int(n_heads), int(d_model), int(vocab_size)]
    if d_ff:
        dims.append(int(d_ff))
    return all(d >= m and d % m == 0 for d in dims)


def as_mesh_group(device):
    """`device` as (MeshGroup | None): the isinstance probe the
    predictors use without importing jax at module import time."""
    return device if isinstance(device, MeshGroup) else None


# chaos hook (tools/chaos.py mesh-member-loss scenario): poisoning a
# member device label makes every dispatch on a mesh group CONTAINING
# that member raise MeshMemberLost — the in-process stand-in for a chip
# dropping off the ICI mid-stream.  Lanes on meshes that do not include
# the member (and plain single-device lanes) are untouched.
_MEMBER_POISON = {"label": None}


def set_member_poison(device_label=None):
    """Arm (a 'platform:id' member label) or disarm (None) the
    mesh-member-loss chaos injection."""
    _MEMBER_POISON["label"] = (str(device_label)
                               if device_label is not None else None)


def check_member_poison(group):
    """Raise MeshMemberLost if the poisoned member sits in `group`
    (called at every mesh dispatch edge)."""
    lbl = _MEMBER_POISON["label"]
    if lbl is None or not isinstance(group, MeshGroup):
        return
    if lbl in group.member_labels():
        raise MeshMemberLost(
            "mesh member %s lost (chaos poison) — mesh replica %s is "
            "down" % (lbl, group.label()))
