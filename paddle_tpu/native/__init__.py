"""ctypes bindings for the C++ runtime pieces (native/*.cc).

The reference's native layer (recordio C++, LoDTensorBlockingQueue, tensor
serde in save_op.cc) maps here: we dlopen libpaddle_tpu_native.so (built
from native/ via make; pybind11 is not available in this image, so the ABI
is a plain C API). The library is untracked (.gitignore): it is built on
first import when it is missing OR older than any native/*.cc, so a
checkout never runs a library its sources have moved past. If it cannot
be built, pure-Python fallbacks keep everything functional (slower).
"""

import ctypes
import glob
import os
import subprocess
import threading

import numpy as np

__all__ = ["lib", "available", "RecordIOWriter", "RecordIOScanner",
           "NativeBlockingQueue", "serialize_tensor", "deserialize_tensor"]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libpaddle_tpu_native.so")

lib = None


def _try_build():
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                       capture_output=True, timeout=120)
        return True
    except (OSError, subprocess.SubprocessError):
        return False


def _stale():
    if not os.path.exists(_LIB_PATH):
        return True
    built = os.path.getmtime(_LIB_PATH)
    return any(os.path.getmtime(src) > built
               for src in glob.glob(os.path.join(_NATIVE_DIR, "*.cc")))


def _load():
    global lib
    if _stale() and not _try_build():
        return None
    try:
        l = ctypes.CDLL(_LIB_PATH)
    except OSError:
        return None
    # ---- signatures ----
    l.rio_writer_open.restype = ctypes.c_void_p
    l.rio_writer_open.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                  ctypes.c_long]
    l.rio_writer_write.restype = ctypes.c_int
    l.rio_writer_write.argtypes = [ctypes.c_void_p,
                                   ctypes.POINTER(ctypes.c_uint8),
                                   ctypes.c_long]
    l.rio_writer_close.restype = ctypes.c_int
    l.rio_writer_close.argtypes = [ctypes.c_void_p]
    l.rio_scanner_open.restype = ctypes.c_void_p
    l.rio_scanner_open.argtypes = [ctypes.c_char_p]
    l.rio_scanner_next.restype = ctypes.c_long
    l.rio_scanner_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8))]
    l.rio_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
    l.rio_scanner_close.argtypes = [ctypes.c_void_p]

    l.bq_create.restype = ctypes.c_void_p
    l.bq_create.argtypes = [ctypes.c_long]
    l.bq_push.restype = ctypes.c_int
    l.bq_push.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8),
                          ctypes.c_long, ctypes.c_long]
    l.bq_pop.restype = ctypes.c_long
    l.bq_pop.argtypes = [ctypes.c_void_p,
                         ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
                         ctypes.c_long]
    l.bq_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
    l.bq_size.restype = ctypes.c_long
    l.bq_size.argtypes = [ctypes.c_void_p]
    l.bq_close.argtypes = [ctypes.c_void_p]
    l.bq_destroy.argtypes = [ctypes.c_void_p]

    l.ts_serialize.restype = ctypes.c_long
    l.ts_serialize.argtypes = [
        ctypes.c_uint32, ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8))]
    l.ts_parse_header.restype = ctypes.c_int
    l.ts_parse_header.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_long,
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64)]
    l.ts_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
    return l


lib = _load()


def available():
    return lib is not None


def _as_u8p(data):
    return ctypes.cast(ctypes.c_char_p(data),
                       ctypes.POINTER(ctypes.c_uint8))


# ---------------------------------------------------------------------------
# RecordIO
# ---------------------------------------------------------------------------

class RecordIOWriter:
    """reference recordio/writer.h; native-backed with Python fallback."""

    def __init__(self, path, max_chunk_records=1000,
                 max_chunk_bytes=32 << 20):
        self._path = path
        self._native = None
        self._py = None
        if lib is not None:
            self._native = lib.rio_writer_open(
                path.encode(), max_chunk_records, max_chunk_bytes)
        if not self._native:
            from . import pyrio
            self._py = pyrio.PyWriter(path, max_chunk_records,
                                      max_chunk_bytes)

    def write(self, record):
        record = bytes(record)
        if self._native:
            rc = lib.rio_writer_write(self._native, _as_u8p(record),
                                      len(record))
            if rc != 0:
                raise IOError("recordio write failed: %s" % self._path)
        else:
            self._py.write(record)

    def close(self):
        if self._native:
            rc = lib.rio_writer_close(self._native)
            self._native = None
            if rc != 0:
                raise IOError("recordio close failed: %s" % self._path)
        elif self._py:
            self._py.close()
            self._py = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


class RecordIOScanner:
    """reference recordio/scanner.h:26."""

    def __init__(self, path):
        self._path = path
        self._native = None
        self._py = None
        if lib is not None:
            self._native = lib.rio_scanner_open(path.encode())
        if not self._native:
            from . import pyrio
            self._py = pyrio.PyScanner(path)

    def __iter__(self):
        return self

    def __next__(self):
        if self._native:
            out = ctypes.POINTER(ctypes.c_uint8)()
            n = lib.rio_scanner_next(self._native, ctypes.byref(out))
            if n == -1:
                raise StopIteration
            if n == -2:
                raise IOError("recordio corruption in %s" % self._path)
            data = ctypes.string_at(out, n)
            lib.rio_free(out)
            return data
        return self._py.next()

    def close(self):
        if self._native:
            lib.rio_scanner_close(self._native)
            self._native = None
        elif self._py:
            self._py.close()
            self._py = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


# ---------------------------------------------------------------------------
# Blocking queue
# ---------------------------------------------------------------------------

class NativeBlockingQueue:
    """reference operators/reader/lod_tensor_blocking_queue.h:31 — bounded
    byte-buffer queue whose waits happen in C++ (GIL released during ctypes
    calls)."""

    def __init__(self, capacity):
        self._capacity = capacity
        self._native = lib.bq_create(capacity) if lib is not None else None
        if self._native is None:
            import queue
            self._py = queue.Queue(maxsize=capacity)
            self._closed = threading.Event()

    def push(self, data, timeout_ms=-1):
        data = bytes(data)
        if self._native:
            rc = lib.bq_push(self._native, _as_u8p(data), len(data),
                             timeout_ms)
            if rc == -1:
                raise EOFError("queue closed")
            if rc == -2:
                raise TimeoutError("queue push timeout")
            return
        if self._closed.is_set():
            raise EOFError("queue closed")
        self._py.put(data, timeout=None if timeout_ms < 0
                     else timeout_ms / 1000.0)

    def pop(self, timeout_ms=-1):
        if self._native:
            out = ctypes.POINTER(ctypes.c_uint8)()
            n = lib.bq_pop(self._native, ctypes.byref(out), timeout_ms)
            if n == -1:
                raise EOFError("queue closed")
            if n == -2:
                raise TimeoutError("queue pop timeout")
            data = ctypes.string_at(out, n)
            lib.bq_free(out)
            return data
        import queue as pyq
        while True:
            try:
                return self._py.get(timeout=0.1)
            except pyq.Empty:
                if self._closed.is_set():
                    raise EOFError("queue closed")
                if timeout_ms >= 0:
                    raise TimeoutError("queue pop timeout")

    def size(self):
        if self._native:
            return lib.bq_size(self._native)
        return self._py.qsize()

    def close(self):
        if self._native:
            lib.bq_close(self._native)
        else:
            self._closed.set()

    def __del__(self):
        if getattr(self, "_native", None):
            try:
                lib.bq_destroy(self._native)
            except Exception:
                pass
            self._native = None


# ---------------------------------------------------------------------------
# Tensor serde (save/load op format)
# ---------------------------------------------------------------------------

_DTYPE_CODES = {
    np.dtype(np.float32): 0, np.dtype(np.float64): 1,
    np.dtype(np.int32): 2, np.dtype(np.int64): 3,
    np.dtype(np.float16): 4, np.dtype(np.uint8): 5,
    np.dtype(np.int8): 6, np.dtype(np.bool_): 7,
}
_CODE_DTYPES = {v: k for k, v in _DTYPE_CODES.items()}


def serialize_tensor(arr, lod=None):
    """save_op.cc tensor serialization (+LoD levels)."""
    arr = np.ascontiguousarray(arr)
    code = _DTYPE_CODES[np.dtype(arr.dtype)]
    lod = lod or []
    if lib is not None:
        dims = (ctypes.c_uint64 * max(arr.ndim, 1))(*arr.shape)
        data = arr.tobytes()
        lod_lens = (ctypes.c_uint64 * max(len(lod), 1))(
            *[len(l) for l in lod])
        flat = [x for l in lod for x in l]
        lod_flat = (ctypes.c_uint64 * max(len(flat), 1))(*flat)
        out = ctypes.POINTER(ctypes.c_uint8)()
        n = lib.ts_serialize(code, dims, arr.ndim, _as_u8p(data),
                             len(data), lod_lens, len(lod), lod_flat,
                             ctypes.byref(out))
        if n < 0:
            raise MemoryError("ts_serialize failed")
        buf = ctypes.string_at(out, n)
        lib.ts_free(out)
        return buf
    # python fallback
    import struct
    parts = [struct.pack("<III", 1, code, arr.ndim)]
    parts.append(struct.pack("<%dQ" % arr.ndim, *arr.shape))
    raw = arr.tobytes()
    parts.append(struct.pack("<Q", len(raw)))
    parts.append(raw)
    parts.append(struct.pack("<I", len(lod)))
    for l in lod:
        parts.append(struct.pack("<Q", len(l)))
        parts.append(struct.pack("<%dQ" % len(l), *l) if l else b"")
    return b"".join(parts)


def deserialize_tensor(buf):
    """Returns (ndarray, lod)."""
    import struct
    version, code, ndim = struct.unpack_from("<III", buf, 0)
    if version != 1:
        raise ValueError("bad tensor record version %d" % version)
    off = 12
    dims = struct.unpack_from("<%dQ" % ndim, buf, off)
    off += 8 * ndim
    (nbytes,) = struct.unpack_from("<Q", buf, off)
    off += 8
    dtype = _CODE_DTYPES[code]
    arr = np.frombuffer(buf, dtype=dtype, count=nbytes // dtype.itemsize,
                        offset=off).reshape(dims).copy()
    off += nbytes
    lod = []
    if off < len(buf):
        (levels,) = struct.unpack_from("<I", buf, off)
        off += 4
        for _ in range(levels):
            (n,) = struct.unpack_from("<Q", buf, off)
            off += 8
            lod.append(list(struct.unpack_from("<%dQ" % n, buf, off)))
            off += 8 * n
    return arr, lod
