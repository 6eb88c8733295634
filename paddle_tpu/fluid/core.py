"""Device/place and dtype plumbing — the TPU-native analogue of the reference's
paddle/fluid/platform/place.h (CPUPlace/CUDAPlace variants, place.h:26,37,52) and
the dtype enum in framework.proto:105 (VarType).

On TPU there is no user-managed device context: XLA owns streams and memory
(SURVEY.md §2.5 note). A Place therefore just names a jax.Device (or the
host-CPU backend used for testing with a forced multi-device topology).
"""

import numpy as np

__all__ = [
    "CPUPlace", "TPUPlace", "CUDAPlace", "CUDAPinnedPlace", "VarDesc",
    "is_compiled_with_tpu", "get_tpu_device_count",
]


class Place:
    """Base device designator. Resolves lazily to a jax.Device so that merely
    importing the framework never initialises the backend."""

    _backend = None  # subclass override

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def jax_device(self):
        import jax
        devs = jax.devices(self._backend) if self._backend else jax.devices()
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                "%r names device %d but the %s backend has %d device(s)"
                % (self, self.device_id, devs[0].platform, len(devs)))
        return devs[self.device_id]

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)


class CPUPlace(Place):
    _backend = "cpu"


class TPUPlace(Place):
    """The TPU analogue of CUDAPlace (reference place.h:37). Resolves to
    the DEFAULT jax backend, so the same scripts run under a forced
    host-platform topology (the CPU-mesh tests depend on it) — which also
    means a TPUPlace on a host without a chip is a CPU device. Code that
    must be on a chip asserts `jax_device().platform` itself
    (chip_smoke.py does)."""
    _backend = None


# The reference's benchmark scripts say CUDAPlace; accept the name and route it
# to the accelerator backend so scripts run unmodified (BASELINE.json north star).
CUDAPlace = TPUPlace


class CUDAPinnedPlace(CPUPlace):
    pass


def is_compiled_with_tpu():
    import jax
    try:
        return any(d.platform not in ("cpu",) for d in jax.devices())
    except RuntimeError:
        return False


# Kept for API parity with fluid scripts that call core.get_cuda_device_count().
def get_tpu_device_count():
    import jax
    return len(jax.devices())


get_cuda_device_count = get_tpu_device_count


class VarDesc:
    """Mirror of framework.proto:105 VarType enum (the dtype/var-kind tags)."""

    class VarType:
        # var kinds
        LOD_TENSOR = 7
        SELECTED_ROWS = 8
        FEED_MINIBATCH = 9
        FETCH_LIST = 10
        STEP_SCOPES = 11
        LOD_RANK_TABLE = 12
        LOD_TENSOR_ARRAY = 13
        READER = 15
        RAW = 17
        # dtypes
        BOOL = 0
        INT16 = 1
        INT32 = 2
        INT64 = 3
        FP16 = 4
        FP32 = 5
        FP64 = 6
        UINT8 = 20
        INT8 = 21
        BF16 = 22


_DTYPE_TO_NP = {
    VarDesc.VarType.BOOL: np.bool_,
    VarDesc.VarType.INT16: np.int16,
    VarDesc.VarType.INT32: np.int32,
    VarDesc.VarType.INT64: np.int64,
    VarDesc.VarType.FP16: np.float16,
    VarDesc.VarType.FP32: np.float32,
    VarDesc.VarType.FP64: np.float64,
    VarDesc.VarType.UINT8: np.uint8,
    VarDesc.VarType.INT8: np.int8,
}


def convert_np_dtype_to_dtype_(np_dtype):
    """numpy dtype / string -> VarType enum (reference framework.py behavior)."""
    if isinstance(np_dtype, int):
        return np_dtype
    if str(np_dtype) == "bfloat16":
        return VarDesc.VarType.BF16
    dtype = np.dtype(np_dtype)
    for enum, nd in _DTYPE_TO_NP.items():
        if np.dtype(nd) == dtype:
            return enum
    raise ValueError("Not supported numpy dtype %s" % dtype)


def convert_dtype_to_np(dtype):
    """VarType enum / string -> canonical numpy-compatible dtype object.

    BF16 maps to ml_dtypes.bfloat16 (jax's numpy-compatible bfloat16)."""
    if dtype == VarDesc.VarType.BF16 or str(dtype) == "bfloat16":
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    if isinstance(dtype, int):
        return np.dtype(_DTYPE_TO_NP[dtype])
    return np.dtype(dtype)


def dtype_size(dtype):
    """Bytes per element of a VarType enum / numpy dtype / string —
    the static byte accounting the resource analyzer (analysis/
    resources.py) sums var shapes with.  BF16 is 2 bytes, INT8 one (the
    quantized lane's weight-footprint win reads straight from this)."""
    if dtype == VarDesc.VarType.BF16 or str(dtype) == "bfloat16":
        return 2
    return int(convert_dtype_to_np(dtype).itemsize)
