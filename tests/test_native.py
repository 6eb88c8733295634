"""C++ native layer tests: recordio round-trip, blocking queue,
tensor serde (reference recordio tests + blocking_queue_test.cc)."""

import threading

import numpy as np
import pytest

from paddle_tpu import native
from paddle_tpu.native import (RecordIOWriter, RecordIOScanner,
                               NativeBlockingQueue, serialize_tensor,
                               deserialize_tensor)
from paddle_tpu.fluid.recordio_writer import (
    convert_reader_to_recordio_file, recordio_reader)


def test_native_lib_builds():
    # the C++ toolchain is present in this image; the lib must be real
    assert native.available(), "libpaddle_tpu_native.so failed to build"


def test_library_is_stale_when_missing_or_older_than_a_source(
        tmp_path, monkeypatch):
    """The .so is untracked: it is rebuilt when missing or when any
    native/*.cc is newer, so a checkout never runs a stale library."""
    import os
    lib = tmp_path / "libpaddle_tpu_native.so"
    src = tmp_path / "wire.cc"
    monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_LIB_PATH", str(lib))
    src.write_text("// source")
    assert native._stale()                      # missing
    lib.write_bytes(b"elf")
    os.utime(src, (1000, 1000))
    os.utime(lib, (2000, 2000))
    assert not native._stale()                  # newer than every source
    os.utime(src, (3000, 3000))
    assert native._stale()                      # a source moved past it


def test_recordio_roundtrip(tmp_path):
    path = str(tmp_path / "data.rio")
    records = [b"hello", b"", b"x" * 10000, b"tail"]
    with RecordIOWriter(path, max_chunk_records=2) as w:
        for r in records:
            w.write(r)
    with RecordIOScanner(path) as s:
        got = list(s)
    assert got == records


def test_recordio_crc_detects_corruption(tmp_path):
    path = str(tmp_path / "bad.rio")
    with RecordIOWriter(path) as w:
        w.write(b"a" * 1000)
    raw = bytearray(open(path, "rb").read())
    raw[-10] ^= 0xFF  # flip a payload byte
    open(path, "wb").write(bytes(raw))
    with pytest.raises((IOError, StopIteration)):
        with RecordIOScanner(path) as s:
            list(s)


def test_blocking_queue_producer_consumer():
    q = NativeBlockingQueue(capacity=4)
    items = [("item%d" % i).encode() for i in range(100)]
    got = []

    def consume():
        while True:
            try:
                got.append(q.pop())
            except EOFError:
                return

    t = threading.Thread(target=consume)
    t.start()
    for it in items:
        q.push(it)
    q.close()
    t.join(timeout=10)
    assert got == items


def test_blocking_queue_capacity_blocks():
    q = NativeBlockingQueue(capacity=2)
    q.push(b"a")
    q.push(b"b")
    with pytest.raises(TimeoutError):
        q.push(b"c", timeout_ms=100)
    assert q.pop() == b"a"
    q.push(b"c")
    assert q.size() == 2


def test_tensor_serde_roundtrip():
    arr = np.random.RandomState(0).randn(3, 4, 5).astype(np.float32)
    buf = serialize_tensor(arr, lod=[[0, 2, 3]])
    back, lod = deserialize_tensor(buf)
    np.testing.assert_array_equal(back, arr)
    assert lod == [[0, 2, 3]]


def test_tensor_serde_dtypes():
    for dt in (np.float32, np.float64, np.int32, np.int64, np.float16,
               np.uint8, np.bool_):
        arr = np.zeros((2, 3), dtype=dt)
        back, _ = deserialize_tensor(serialize_tensor(arr))
        assert back.dtype == arr.dtype and back.shape == arr.shape


def test_convert_reader_to_recordio(tmp_path):
    path = str(tmp_path / "samples.rio")

    def reader():
        rng = np.random.RandomState(1)
        for i in range(10):
            yield rng.randn(4).astype(np.float32), np.int64(i)

    n = convert_reader_to_recordio_file(path, reader)
    assert n == 10
    got = list(recordio_reader(path)())
    assert len(got) == 10
    ref = list(reader())
    for (gx, gy), (rx, ry) in zip(got, ref):
        np.testing.assert_array_equal(gx, rx)
        assert gy == ry
