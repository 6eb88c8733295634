"""Reader decorators (reference python/paddle/reader/decorator.py)."""

import itertools
import queue
import random
import threading

__all__ = [
    "map_readers", "buffered", "compose", "chain", "shuffle", "firstn",
    "xmap_readers", "cache", "ComposeNotAligned",
    "multiprocess_reader", "PipeReader", "Fake", "retry_reader",
    "prefetch_to_device", "ReaderWorkerFailed",
]


class ReaderWorkerFailed(RuntimeError):
    """A reader worker (thread or process) died mid-stream.  Raised to
    the consumer instead of hanging on a sentinel that will never come
    or silently truncating the epoch; `cause_repr` carries the worker's
    exception (string form — it may have crossed a process boundary)."""

    def __init__(self, message, cause_repr=None):
        super(ReaderWorkerFailed, self).__init__(message)
        self.cause_repr = cause_repr


class _WorkerError(object):
    """In-band error marker a failing worker emits before exiting; must
    be pickle-stable so it survives the multiprocessing pipe/queue."""

    def __init__(self, exc):
        self.exc_repr = repr(exc)

    def __reduce__(self):
        w = _WorkerError.__new__(_WorkerError)
        w.exc_repr = self.exc_repr
        return (_rebuild_worker_error, (self.exc_repr,))


def _rebuild_worker_error(exc_repr):
    w = _WorkerError.__new__(_WorkerError)
    w.exc_repr = exc_repr
    return w


class ComposeNotAligned(ValueError):
    pass


def map_readers(func, *readers):
    """Apply func to the items of several readers zipped together
    (reference decorator.py:36)."""

    def reader():
        rs = [r() for r in readers]
        for vals in zip(*rs):
            yield func(*vals)

    return reader


def shuffle(reader, buf_size):
    """Buffered shuffle (reference decorator.py:60)."""

    def data_reader():
        buf = []
        for e in reader():
            buf.append(e)
            if len(buf) >= buf_size:
                random.shuffle(buf)
                for b in buf:
                    yield b
                buf = []
        if buf:
            random.shuffle(buf)
            for b in buf:
                yield b

    return data_reader


def chain(*readers):
    """Concatenate readers (reference decorator.py:88)."""

    def reader():
        rs = [r() for r in readers]
        for e in itertools.chain(*rs):
            yield e

    return reader


def compose(*readers, **kwargs):
    """Zip readers into tuple samples (reference decorator.py:118);
    check_alignment raises ComposeNotAligned on length mismatch."""
    check_alignment = kwargs.pop("check_alignment", True)

    def make_tuple(x):
        if isinstance(x, tuple):
            return x
        return (x,)

    def reader():
        rs = [r() for r in readers]
        if not check_alignment:
            for outputs in zip(*rs):
                yield sum(list(map(make_tuple, outputs)), ())
        else:
            for outputs in itertools.zip_longest(*rs):
                if any(o is None for o in outputs):
                    raise ComposeNotAligned(
                        "outputs of readers are not aligned")
                yield sum(list(map(make_tuple, outputs)), ())

    return reader


def buffered(reader, size):
    """Background-thread prefetch buffer (reference decorator.py:180)."""

    class _End:
        pass

    def data_reader():
        r = reader()
        q = queue.Queue(maxsize=size)

        def fill():
            try:
                for d in r:
                    q.put(d)
            finally:
                q.put(_End)

        t = threading.Thread(target=fill, daemon=True)
        t.start()
        while True:
            e = q.get()
            if e is _End:
                return
            yield e

    return data_reader


def firstn(reader, n):
    """First n samples (reference decorator.py:230)."""

    def firstn_reader():
        for i, item in enumerate(reader()):
            if i == n:
                return
            yield item

    return firstn_reader


def cache(reader):
    """Materialize once, replay from memory."""
    all_data = []
    filled = []

    def cache_reader():
        if not filled:
            all_data.extend(reader())
            filled.append(True)
        for d in all_data:
            yield d

    return cache_reader


def xmap_readers(mapper, reader, process_num, buffer_size, order=False):
    """Parallel map over a reader with worker threads (reference
    decorator.py xmap_readers). Order-preserving mode tags samples with
    sequence ids and reorders on the output side."""

    class _End:
        pass

    def data_reader():
        in_q = queue.Queue(buffer_size)
        out_q = queue.Queue(buffer_size)

        def feed():
            try:
                for i, sample in enumerate(reader()):
                    in_q.put((i, sample))
            except Exception as e:
                # the source reader died: tell the CONSUMER directly —
                # workers may be blocked on in_q and the consumer must
                # not wait forever for sentinels that will never come
                out_q.put(_WorkerError(e))
            finally:
                for _ in range(process_num):
                    in_q.put(_End)

        def work():
            while True:
                item = in_q.get()
                if item is _End:
                    out_q.put(_End)
                    return
                i, sample = item
                try:
                    mapped = mapper(sample)
                except Exception as e:
                    # a mapper crash mid-stream surfaces to the consumer
                    # (reference xmap handled exceptions by re-raising in
                    # the output thread) — never a silent short epoch
                    out_q.put(_WorkerError(e))
                    out_q.put(_End)
                    return
                out_q.put((i, mapped))

        def _raise(err):
            raise ReaderWorkerFailed(
                "xmap_readers worker failed mid-stream: %s" % err.exc_repr,
                cause_repr=err.exc_repr)

        threading.Thread(target=feed, daemon=True).start()
        for _ in range(process_num):
            threading.Thread(target=work, daemon=True).start()

        finished = 0
        if not order:
            while finished < process_num:
                item = out_q.get()
                if item is _End:
                    finished += 1
                    continue
                if isinstance(item, _WorkerError):
                    _raise(item)
                yield item[1]
        else:
            next_id = 0
            held = {}
            while finished < process_num or held:
                if next_id in held:
                    yield held.pop(next_id)
                    next_id += 1
                    continue
                if finished >= process_num:
                    # drain remaining out-of-order items
                    if not held:
                        break
                    continue
                item = out_q.get()
                if item is _End:
                    finished += 1
                    continue
                if isinstance(item, _WorkerError):
                    _raise(item)
                i, mapped = item
                if i == next_id:
                    yield mapped
                    next_id += 1
                else:
                    held[i] = mapped

    return data_reader


class _EndOfStream(object):
    """Pickle-stable end sentinel for multiprocess_reader — a plain None
    would truncate streams whose readers legitimately yield None."""

    def __reduce__(self):
        return (_EndOfStream, ())


def multiprocess_reader(readers, use_pipe=True, queue_size=1000):
    """Merge readers, one OS process each (reference decorator.py:338).
    Each child streams items; the parent interleaves until every child
    has sent its end sentinel.  A child whose reader raises ships the
    exception in-band (a `_WorkerError` before its sentinel) and the
    parent raises ReaderWorkerFailed; a child that dies without ANY
    sentinel (kill -9, segfault) is detected at EOF and also raises —
    an epoch is never silently truncated."""
    import multiprocessing
    import sys
    assert isinstance(readers, (list, tuple)) and len(readers) > 0

    def _raise(err):
        raise ReaderWorkerFailed(
            "multiprocess_reader worker failed mid-stream: %s"
            % err.exc_repr, cause_repr=err.exc_repr)

    def _feed(reader, q):
        try:
            for item in reader():
                q.put(item)
        except Exception as e:
            q.put(_WorkerError(e))
        finally:
            q.put(_EndOfStream())

    def queue_reader():
        q = multiprocessing.Queue(queue_size)
        procs = [multiprocessing.Process(target=_feed, args=(r, q))
                 for r in readers]
        for p in procs:
            p.daemon = True
            p.start()
        finished = 0
        while finished < len(readers):
            item = q.get()
            if isinstance(item, _EndOfStream):
                finished += 1
            elif isinstance(item, _WorkerError):
                _raise(item)
            else:
                yield item
        for p in procs:
            p.join()

    def pipe_reader():
        from multiprocessing.connection import wait
        conns = []
        procs = []
        for r in readers:
            parent, child = multiprocessing.Pipe(duplex=False)

            def _feed_pipe(reader, conn):
                try:
                    for item in reader():
                        conn.send(item)
                except Exception as e:
                    try:
                        conn.send(_WorkerError(e))
                    except (ValueError, OSError):
                        pass  # unpicklable/broken pipe: EOF path catches
                finally:
                    try:
                        conn.send(_EndOfStream())
                        conn.close()
                    except OSError:
                        pass
            p = multiprocessing.Process(target=_feed_pipe,
                                        args=(r, child))
            p.daemon = True
            p.start()
            child.close()   # parent must drop its copy or EOF never fires
            conns.append(parent)
            procs.append(p)
        live = list(conns)
        while live:
            for conn in wait(live):
                try:
                    item = conn.recv()
                except EOFError:   # child died before its sentinel
                    idx = conns.index(conn)
                    procs[idx].join(timeout=5.0)
                    code = procs[idx].exitcode
                    raise ReaderWorkerFailed(
                        "multiprocess_reader worker %d died before its "
                        "end-of-stream sentinel (exitcode %r) — epoch "
                        "would have been silently truncated" % (idx, code))
                if isinstance(item, _EndOfStream):
                    live.remove(conn)
                elif isinstance(item, _WorkerError):
                    _raise(item)
                else:
                    yield item
        for p in procs:
            p.join()

    if sys.platform == "win32":
        raise NotImplementedError("multiprocess_reader: POSIX only")
    return pipe_reader if use_pipe else queue_reader


def _default_device_prepare(item):
    """Stage one batch on device: feed dicts get a (async, non-blocking)
    jax.device_put per array value; anything else passes through so the
    prefetch thread still overlaps the host-side work of producing it."""
    import numpy as np
    import jax
    if isinstance(item, dict):
        out = {}
        for k, v in item.items():
            if isinstance(v, jax.Array):
                out[k] = v          # already on device
            elif isinstance(v, np.ndarray) or np.isscalar(v):
                out[k] = jax.device_put(v)
            else:
                out[k] = v          # LoDTensor etc: caller's prepare job
        return out
    return item


def _mesh_shard_prepare(mesh):
    """Sharded prefetch (PIPELINE.md follow-up): commit each prepared
    feed array as a mesh-global jax.Array ON THE PREFETCH THREAD via
    jax.make_array_from_process_local_data, so a ParallelExecutor step
    receives pre-sharded arrays and its dispatch path's own sharded
    commit becomes a no-op re-put.  Batch-dim arrays shard on the
    mesh's data axis (DATA_AXIS when present, else the first axis);
    scalars replicate."""
    import numpy as np
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..parallel.mesh import DATA_AXIS
    axis = DATA_AXIS if DATA_AXIS in mesh.axis_names \
        else mesh.axis_names[0]

    def shard(item):
        if not isinstance(item, dict):
            return item
        out = {}
        for k, v in item.items():
            if isinstance(v, jax.Array):
                out[k] = v          # already committed
            elif isinstance(v, np.ndarray) or np.isscalar(v):
                arr = np.asarray(v)
                spec = P() if arr.ndim == 0 else \
                    P(axis, *([None] * (arr.ndim - 1)))
                out[k] = jax.make_array_from_process_local_data(
                    NamedSharding(mesh, spec), arr)
            else:
                out[k] = v          # LoDTensor etc: caller's prepare job
        return out
    return shard


def prefetch_to_device(reader, depth=2, prepare=None, mesh=None):
    """Device prefetch queue (the tentpole of the async training
    pipeline, PIPELINE.md): a bounded background thread pulls batches
    from `reader` and runs `prepare` — by default a per-array
    jax.device_put; the Trainer passes ``prepare_feeds`` so dtype casts,
    LoD padding and the (sharded) device_put for the NEXT batch all
    happen while the current step computes.  jax device_put is
    asynchronous, so the H2D copy itself overlaps device execution —
    the reference's double_buffer / py_reader infeed overlap
    (operators/reader/create_double_buffer_reader_op.cc,
    buffered_reader.cc) rebuilt host-side.

    `mesh` (sharded prefetch): a jax.sharding.Mesh — after `prepare`,
    every batch array is committed as a mesh-global sharded jax.Array
    (make_array_from_process_local_data) still on the prefetch thread,
    so ParallelExecutor.run receives pre-sharded feeds and pays no
    per-dispatch shard commit on the main thread
    (fluid_benchmark --parallel --prefetch_depth wires this).

    Semantics the tests pin down:

    * bounded backpressure — at most `depth` prepared batches wait in
      the queue (plus one in the worker's hand), so prefetch cannot run
      away from a slow consumer or pin unbounded device memory;
    * clean shutdown — closing the returned generator (or just letting
      the epoch end) stops the worker and joins it; a half-consumed
      epoch leaks no thread;
    * worker death — an exception in the source reader OR in `prepare`
      surfaces to the consumer as ReaderWorkerFailed, never a hang on a
      sentinel that will never come or a silently short epoch.
    """
    depth = max(int(depth), 1)
    if mesh is not None:
        # the sharded commit replaces the default single-device
        # device_put; an explicit host-side `prepare` still runs first
        host_prep = prepare if prepare is not None else (lambda x: x)
        shard = _mesh_shard_prepare(mesh)
        prep = lambda item: shard(host_prep(item))  # noqa: E731
    else:
        prep = prepare if prepare is not None else _default_device_prepare

    class _End(object):
        pass

    def data_reader():
        q = queue.Queue(maxsize=depth)
        stop = threading.Event()

        def _put(item):
            # bounded put that still honors shutdown: a worker blocked
            # on a full queue must notice the consumer has gone away
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                for item in reader():
                    if stop.is_set():
                        return
                    if not _put(prep(item)):
                        return
            except Exception as e:
                _put(_WorkerError(e))
                return
            _put(_End)

        t = threading.Thread(target=worker, daemon=True,
                             name="paddle-tpu-prefetch")
        t.start()
        try:
            import time as _time
            from ..obs import tracing as _obs_tracing
            # prefetch_wait: how long the train loop blocked on the
            # queue per batch (0 when prefetch is hiding the host work
            # — the per-step breakdown's first column, PIPELINE.md /
            # OBSERVABILITY.md)
            wait_t0 = _time.monotonic()
            while True:
                try:
                    item = q.get(timeout=1.0)
                except queue.Empty:
                    if not t.is_alive():
                        raise ReaderWorkerFailed(
                            "prefetch_to_device worker died without an "
                            "end-of-stream sentinel — epoch would have "
                            "been silently truncated")
                    continue
                if item is _End:
                    return
                if isinstance(item, _WorkerError):
                    raise ReaderWorkerFailed(
                        "prefetch_to_device worker failed mid-stream: %s"
                        % item.exc_repr, cause_repr=item.exc_repr)
                if _obs_tracing.enabled():
                    _obs_tracing.stamp("train/prefetch_wait", wait_t0,
                                       _time.monotonic(), kind="train")
                yield item
                wait_t0 = _time.monotonic()
        finally:
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=5.0)

    return data_reader


def retry_reader(reader, policy=None, retry_on=(Exception,)):
    """Wrap a reader with the fault-tolerance RetryPolicy (the SAME
    policy object family as the RPC re-dial wrappers — utils/retry.py):
    when the underlying reader raises mid-stream, back off with jitter,
    re-open it, skip the samples already delivered, and continue the
    epoch from where it broke.  Exhausting the policy's attempts
    re-raises the reader's exception.

    Correct only for deterministic re-openable sources (files, object
    stores, PipeReader commands) — the skip replays the prefix to find
    the resume point."""
    if policy is None:
        from ..utils.retry import RetryPolicy
        policy = RetryPolicy(max_attempts=3, base_delay=0.05,
                             retry_on=retry_on)
    retry_on = tuple(retry_on)

    def data_reader():
        delivered = 0
        delays = policy.delays()
        while True:
            try:
                for i, item in enumerate(reader()):
                    if i < delivered:
                        continue  # replaying the already-yielded prefix
                    yield item
                    delivered += 1
                return
            except retry_on:
                # next() must not raise StopIteration inside a generator
                # (PEP 479 would mask the reader's exception)
                delay = next(delays, None)
                if delay is None:
                    raise
                policy.sleep(delay)

    return data_reader


class PipeReader:
    """Stream a shell command's stdout and parse it into lines
    (reference decorator.py:438) — read corpora from another program
    (hdfs/ceph/s3 cat, curl, zcat, ...)."""

    def __init__(self, command, bufsize=8192, file_type="plain"):
        import subprocess
        import zlib
        if not isinstance(command, str):
            raise TypeError("left_cmd must be a string")
        if file_type == "gzip":
            self.dec = zlib.decompressobj(32 + zlib.MAX_WBITS)
        elif file_type != "plain":
            raise TypeError("file_type %s is not allowed" % file_type)
        self.file_type = file_type
        self.bufsize = bufsize
        self.process = subprocess.Popen(
            command.split(" "), bufsize=bufsize, stdout=subprocess.PIPE)

    def get_line(self, cut_lines=True, line_break="\n"):
        remained = ""
        while True:
            buff = self.process.stdout.read(self.bufsize)
            if not buff:
                break
            if self.file_type == "gzip":
                decomp = self.dec.decompress(buff).decode(
                    "utf-8", "replace")
            else:
                decomp = buff.decode("utf-8", "replace")
            if cut_lines:
                pieces = (remained + decomp).split(line_break)
                remained = pieces[-1]
                for line in pieces[:-1]:
                    yield line
            else:
                yield decomp
        if cut_lines and remained:
            yield remained


class Fake(object):
    """Cache the first item a reader yields and repeat it data_num times
    (reference decorator.py:509) — pins the input for speed testing."""

    _EMPTY = object()      # source reader yielded nothing
    _UNSET = object()      # first item not cached yet (None is a legal
                           # item — it must not re-trigger consumption)

    def __init__(self):
        self.data = Fake._UNSET

    def __call__(self, reader, data_num):
        def fake_reader():
            if self.data is Fake._UNSET:
                self.data = next(reader(), Fake._EMPTY)
            if self.data is Fake._EMPTY:
                return   # empty source reader -> empty stream
            for _ in range(data_num):
                yield self.data

        return fake_reader
