"""Typed wire codec — the no-pickle message format for every socket and
snapshot path (native/wire.cc; reference analogue grpc_serde.cc +
send_recv.proto.in VariableMessage).

`encode(obj)` / `decode(buf)` round-trip None/bool/int/float/str/bytes/
list/tuple/dict(str keys)/np.ndarray. Decoding validates every offset,
length, count, and depth in C++ before any Python object is built, so a
malformed or hostile frame raises `WireError` — it can never execute
code, which is the whole point of replacing pickle on sockets. A pure-
Python codec implements the identical format when the native library is
unavailable (same validation, slower).
"""

import ctypes
import struct

import numpy as np

from . import lib, _as_u8p

__all__ = ["encode", "decode", "WireError"]

_MAGIC = 0x31575450  # "PTW1"
_VERSION = 1
_MAX_DEPTH = 64
_MAX_NDIM = 8

_NONE, _BOOL, _INT, _FLOAT, _STR, _BYTES, _LIST, _TUPLE, _DICT, _TENSOR = \
    range(10)

# dtype codes: ONE table with tensor_serde (native/__init__) so the wire
# format and the save/load-op format can never diverge on codes 0-7;
# wire-only extensions start at 8
from . import _DTYPE_CODES as _BASE_DTYPE_CODES

_DTYPE_CODES = dict(_BASE_DTYPE_CODES)
_DTYPE_CODES.update({
    np.dtype(np.uint32): 9, np.dtype(np.uint64): 10,
    np.dtype(np.int16): 11, np.dtype(np.uint16): 12,
    np.dtype(np.complex64): 13, np.dtype(np.complex128): 14,
})
try:
    import ml_dtypes
    _DTYPE_CODES[np.dtype(ml_dtypes.bfloat16)] = 8
except ImportError:  # pragma: no cover
    pass
_CODE_DTYPES = {v: k for k, v in _DTYPE_CODES.items()}


class WireError(ValueError):
    """Malformed frame (truncated, bad magic, bad tag, lying counts...)."""


_HAS_NATIVE = lib is not None and hasattr(lib, "wirb_new")

if _HAS_NATIVE and lib.wirb_new.restype is not ctypes.c_void_p:
    lib.wirb_new.restype = ctypes.c_void_p
    lib.wirb_none.argtypes = [ctypes.c_void_p]
    lib.wirb_bool.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.wirb_int.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.wirb_float.argtypes = [ctypes.c_void_p, ctypes.c_double]
    for _fn in (lib.wirb_str, lib.wirb_bytes, lib.wirb_key):
        _fn.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8),
                        ctypes.c_uint32]
    for _fn in (lib.wirb_list, lib.wirb_tuple, lib.wirb_dict):
        _fn.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.wirb_tensor.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_uint32, ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64]
    lib.wirb_finish.restype = ctypes.c_long
    lib.wirb_finish.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8))]
    lib.wirb_abort.argtypes = [ctypes.c_void_p]
    lib.wire_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
    lib.wirp_new.restype = ctypes.c_void_p
    lib.wirp_new.argtypes = [ctypes.POINTER(ctypes.c_uint8), ctypes.c_long]
    lib.wirp_tag.restype = ctypes.c_int
    lib.wirp_tag.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.wirp_int.restype = ctypes.c_int
    lib.wirp_int.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                             ctypes.POINTER(ctypes.c_int64)]
    lib.wirp_float.restype = ctypes.c_int
    lib.wirp_float.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                               ctypes.POINTER(ctypes.c_double)]
    lib.wirp_payload.restype = ctypes.c_int
    lib.wirp_payload.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                 ctypes.POINTER(ctypes.c_uint64),
                                 ctypes.POINTER(ctypes.c_uint64)]
    lib.wirp_count.restype = ctypes.c_long
    lib.wirp_count.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.wirp_child.restype = ctypes.c_long
    lib.wirp_child.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                               ctypes.c_uint32]
    lib.wirp_key.restype = ctypes.c_int
    lib.wirp_key.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                             ctypes.c_uint32,
                             ctypes.POINTER(ctypes.c_uint64),
                             ctypes.POINTER(ctypes.c_uint32)]
    lib.wirp_tensor.restype = ctypes.c_int
    lib.wirp_tensor.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64)]
    lib.wirp_free.argtypes = [ctypes.c_void_p]


def _tensor_parts(obj):
    # ascontiguousarray promotes 0-d to 1-d; reshape restores the rank
    arr = np.ascontiguousarray(obj).reshape(np.shape(obj))
    code = _DTYPE_CODES.get(arr.dtype)
    if code is None:
        raise WireError("unsupported tensor dtype %s" % arr.dtype)
    if arr.ndim > _MAX_NDIM:
        # the parser (both C++ and python) caps rank at _MAX_NDIM —
        # refusing HERE keeps encode/decode a round trip instead of
        # writing frames our own decoder calls malformed
        raise WireError("tensor rank %d exceeds the wire format's max "
                        "of %d" % (arr.ndim, _MAX_NDIM))
    return arr, code


def _encode_native(obj):
    h = lib.wirb_new()
    try:
        _build_native(h, obj, 0)
    except Exception:
        lib.wirb_abort(h)
        raise
    out = ctypes.POINTER(ctypes.c_uint8)()
    n = lib.wirb_finish(h, ctypes.byref(out))
    if n < 0:
        raise MemoryError("wire encode failed")
    if n < (1 << 31):
        buf = ctypes.string_at(out, n)
    else:
        # string_at's size is a C int, and a decode artifact's state
        # passes 2 GiB (one layer of OLMoE-1B-7B is 1.7 GB)
        buf = bytes((ctypes.c_uint8 * n).from_address(
            ctypes.addressof(out.contents)))
    lib.wire_free(out)
    return buf


def _check_i64(v):
    if not (-(1 << 63) <= v < (1 << 63)):
        raise WireError("int %d outside the wire int64 range" % v)
    return v


def _build_native(h, obj, depth):
    if depth > _MAX_DEPTH:
        raise WireError("wire value nested too deep")
    if obj is None:
        lib.wirb_none(h)
    elif isinstance(obj, (bool, np.bool_)):
        lib.wirb_bool(h, int(obj))
    elif isinstance(obj, (int, np.integer)):
        lib.wirb_int(h, _check_i64(int(obj)))
    elif isinstance(obj, (float, np.floating)):
        lib.wirb_float(h, float(obj))
    elif isinstance(obj, str):
        raw = obj.encode("utf-8")
        lib.wirb_str(h, _as_u8p(raw), len(raw))
    elif isinstance(obj, (bytes, bytearray)):
        raw = bytes(obj)
        lib.wirb_bytes(h, _as_u8p(raw), len(raw))
    elif isinstance(obj, np.ndarray):
        arr, code = _tensor_parts(obj)
        dims = (ctypes.c_uint64 * max(arr.ndim, 1))(*arr.shape)
        raw = arr.tobytes()
        lib.wirb_tensor(h, code, dims, arr.ndim, _as_u8p(raw), len(raw))
    elif isinstance(obj, (list, tuple)):
        (lib.wirb_list if isinstance(obj, list) else lib.wirb_tuple)(
            h, len(obj))
        for item in obj:
            _build_native(h, item, depth + 1)
    elif isinstance(obj, dict):
        lib.wirb_dict(h, len(obj))
        for k, v in obj.items():
            if not isinstance(k, str):
                raise WireError("dict keys must be str, got %r" % (k,))
            raw = k.encode("utf-8")
            lib.wirb_key(h, _as_u8p(raw), len(raw))
            _build_native(h, v, depth + 1)
    else:
        raise WireError("unsupported wire type %s" % type(obj).__name__)


def _decode_native(buf):
    buf = bytes(buf)
    h = lib.wirp_new(_as_u8p(buf), len(buf))
    if not h:
        raise WireError("malformed wire frame (%d bytes)" % len(buf))
    try:
        return _read_native(h, buf, 0)
    finally:
        lib.wirp_free(h)


def _read_native(h, buf, idx):
    tag = lib.wirp_tag(h, idx)
    if tag == _NONE:
        return None
    if tag in (_BOOL, _INT):
        v = ctypes.c_int64()
        if lib.wirp_int(h, idx, ctypes.byref(v)) != 0:
            raise WireError("bad scalar node")
        return bool(v.value) if tag == _BOOL else v.value
    if tag == _FLOAT:
        v = ctypes.c_double()
        if lib.wirp_float(h, idx, ctypes.byref(v)) != 0:
            raise WireError("bad float node")
        return v.value
    if tag in (_STR, _BYTES):
        off, ln = ctypes.c_uint64(), ctypes.c_uint64()
        if lib.wirp_payload(h, idx, ctypes.byref(off),
                            ctypes.byref(ln)) != 0:
            raise WireError("bad payload node")
        raw = buf[off.value:off.value + ln.value]
        if tag == _STR:
            try:
                return raw.decode("utf-8")
            except UnicodeDecodeError:
                raise WireError("non-utf8 string payload")
        return raw
    if tag in (_LIST, _TUPLE, _DICT):
        n = lib.wirp_count(h, idx)
        if n < 0:
            raise WireError("bad container node")
        if tag == _DICT:
            out = {}
            for i in range(n):
                koff, klen = ctypes.c_uint64(), ctypes.c_uint32()
                if lib.wirp_key(h, idx, i, ctypes.byref(koff),
                                ctypes.byref(klen)) != 0:
                    raise WireError("bad dict key")
                try:
                    key = buf[koff.value:koff.value + klen.value] \
                        .decode("utf-8")
                except UnicodeDecodeError:
                    raise WireError("non-utf8 dict key")
                out[key] = _read_native(h, buf, lib.wirp_child(h, idx, i))
            return out
        items = [_read_native(h, buf, lib.wirp_child(h, idx, i))
                 for i in range(n)]
        return items if tag == _LIST else tuple(items)
    if tag == _TENSOR:
        dtype, ndim = ctypes.c_uint32(), ctypes.c_uint32()
        dims = (ctypes.c_uint64 * _MAX_NDIM)()
        off, nbytes = ctypes.c_uint64(), ctypes.c_uint64()
        if lib.wirp_tensor(h, idx, ctypes.byref(dtype), ctypes.byref(ndim),
                           dims, ctypes.byref(off),
                           ctypes.byref(nbytes)) != 0:
            raise WireError("bad tensor node")
        dt = _CODE_DTYPES.get(dtype.value)
        if dt is None:
            raise WireError("unknown tensor dtype code %d" % dtype.value)
        shape = tuple(dims[i] for i in range(ndim.value))
        count = 1
        for d in shape:
            count *= d
        if count * dt.itemsize != nbytes.value:
            raise WireError("tensor shape/bytes mismatch")
        return np.frombuffer(buf, dtype=dt, count=count,
                             offset=off.value).reshape(shape).copy()
    raise WireError("bad tag %d" % tag)


# ---------------------------------------------------------------------------
# Pure-Python codec (same format, used when the .so is unavailable)
# ---------------------------------------------------------------------------

def _encode_py(obj):
    parts = [struct.pack("<II", _MAGIC, _VERSION)]
    _build_py(parts, obj, 0)
    return b"".join(parts)


def _build_py(parts, obj, depth):
    if depth > _MAX_DEPTH:
        raise WireError("wire value nested too deep")
    if obj is None:
        parts.append(bytes([_NONE]))
    elif isinstance(obj, (bool, np.bool_)):
        parts.append(struct.pack("<BB", _BOOL, int(obj)))
    elif isinstance(obj, (int, np.integer)):
        parts.append(struct.pack("<Bq", _INT, _check_i64(int(obj))))
    elif isinstance(obj, (float, np.floating)):
        parts.append(struct.pack("<Bd", _FLOAT, float(obj)))
    elif isinstance(obj, str):
        raw = obj.encode("utf-8")
        parts.append(struct.pack("<BI", _STR, len(raw)))
        parts.append(raw)
    elif isinstance(obj, (bytes, bytearray)):
        parts.append(struct.pack("<BI", _BYTES, len(obj)))
        parts.append(bytes(obj))
    elif isinstance(obj, np.ndarray):
        arr, code = _tensor_parts(obj)
        raw = arr.tobytes()
        parts.append(struct.pack("<BII", _TENSOR, code, arr.ndim))
        parts.append(struct.pack("<%dQ" % arr.ndim, *arr.shape)
                     if arr.ndim else b"")
        parts.append(struct.pack("<Q", len(raw)))
        parts.append(raw)
    elif isinstance(obj, (list, tuple)):
        parts.append(struct.pack(
            "<BI", _LIST if isinstance(obj, list) else _TUPLE, len(obj)))
        for item in obj:
            _build_py(parts, item, depth + 1)
    elif isinstance(obj, dict):
        parts.append(struct.pack("<BI", _DICT, len(obj)))
        for k, v in obj.items():
            if not isinstance(k, str):
                raise WireError("dict keys must be str, got %r" % (k,))
            raw = k.encode("utf-8")
            parts.append(struct.pack("<I", len(raw)))
            parts.append(raw)
            _build_py(parts, v, depth + 1)
    else:
        raise WireError("unsupported wire type %s" % type(obj).__name__)


class _PyCursor:
    __slots__ = ("buf", "pos")

    def __init__(self, buf, pos):
        self.buf = buf
        self.pos = pos

    def take(self, n):
        if n < 0 or len(self.buf) - self.pos < n:
            raise WireError("truncated wire frame")
        raw = self.buf[self.pos:self.pos + n]
        self.pos += n
        return raw

    def unpack(self, fmt):
        size = struct.calcsize(fmt)
        return struct.unpack(fmt, self.take(size))


def _decode_py(buf):
    buf = bytes(buf)
    if len(buf) < 9:
        raise WireError("malformed wire frame (%d bytes)" % len(buf))
    magic, version = struct.unpack_from("<II", buf, 0)
    if magic != _MAGIC or version != _VERSION:
        raise WireError("bad wire magic/version")
    c = _PyCursor(buf, 8)
    obj = _read_py(c, 0)
    if c.pos != len(buf):
        raise WireError("trailing junk after wire frame")
    return obj


def _read_py(c, depth):
    if depth > _MAX_DEPTH:
        raise WireError("wire frame nested too deep")
    (tag,) = c.unpack("<B")
    if tag == _NONE:
        return None
    if tag == _BOOL:
        (v,) = c.unpack("<B")
        if v > 1:
            raise WireError("bad bool")
        return bool(v)
    if tag == _INT:
        return c.unpack("<q")[0]
    if tag == _FLOAT:
        return c.unpack("<d")[0]
    if tag in (_STR, _BYTES):
        (n,) = c.unpack("<I")
        raw = c.take(n)
        if tag == _STR:
            try:
                return raw.decode("utf-8")
            except UnicodeDecodeError:
                raise WireError("non-utf8 string payload")
        return raw
    if tag in (_LIST, _TUPLE):
        (n,) = c.unpack("<I")
        items = [_read_py(c, depth + 1) for _ in range(n)]
        return items if tag == _LIST else tuple(items)
    if tag == _DICT:
        (n,) = c.unpack("<I")
        out = {}
        for _ in range(n):
            (klen,) = c.unpack("<I")
            try:
                key = c.take(klen).decode("utf-8")
            except UnicodeDecodeError:
                raise WireError("non-utf8 dict key")
            out[key] = _read_py(c, depth + 1)
        return out
    if tag == _TENSOR:
        code, ndim = c.unpack("<II")
        if ndim > _MAX_NDIM:
            raise WireError("tensor ndim too large")
        shape = c.unpack("<%dQ" % ndim) if ndim else ()
        (nbytes,) = c.unpack("<Q")
        dt = _CODE_DTYPES.get(code)
        if dt is None:
            raise WireError("unknown tensor dtype code %d" % code)
        count = 1
        for d in shape:
            count *= d
        if count * dt.itemsize != nbytes:
            raise WireError("tensor shape/bytes mismatch")
        raw = c.take(nbytes)
        return np.frombuffer(raw, dtype=dt, count=count).reshape(shape) \
            .copy()
    raise WireError("bad tag %d" % tag)


def encode(obj):
    """Serialize a wire-encodable value to a framed bytes object."""
    if _HAS_NATIVE:
        return _encode_native(obj)
    return _encode_py(obj)


def decode(buf):
    """Parse a frame; raises WireError on anything malformed."""
    if _HAS_NATIVE:
        return _decode_native(buf)
    return _decode_py(buf)
