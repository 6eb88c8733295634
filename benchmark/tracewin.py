"""The profiled sub-window of a --trace 1 run: a few seconds in the middle
of the measured window, started and stopped by a timer thread so the
driver's loop is not interrupted.  Off (`--trace 0`) it does nothing.

The host tracer is OFF (level 0).  At level 1 or 2 the TPU runtime's own
host events made a 154 MB `Executor.run` feed take 650 ms instead of 137 ms
and `stop_trace` 130 s instead of 11 s (my chip runs, PR 23), so a traced
run would have measured the profiler.  With no host events in the trace,
its clock is tied to the host's by a DEVICE-side anchor: a tiny jitted
`bench_anchor` computation dispatched at the start and at the end of the
sub-window; the host notes its clocks when `block_until_ready` returns,
which is within a dispatch latency of the moment the trace shows the
module ending.
"""

import threading
import time

from benchmark import xplane


# Rebound to 2 only by the CPU rehearsal (benchmark/tests/rehearse.py): the
# CPU backend's ops reach a trace through the host tracer alone.
HOST_TRACER_LEVEL = 0


def _anchor_fn():
    import jax

    def bench_anchor(x):
        return x + 1.0
    return jax.jit(bench_anchor)


class Window(object):
    def __init__(self, ctx, start_fraction=1.0 / 3.0):
        self.on = bool(ctx.trace)
        self.dir = ctx.trace_dir
        self.start_after = ctx.seconds * start_fraction
        self.length = ctx.trace_seconds
        self.t_start = self.t_stop = None      # time.monotonic()
        self.stop_seconds = None
        self.anchors = []                      # (wall_s, monotonic_s)
        self._thread = None
        self._abort = threading.Event()
        if self.on:
            import jax.numpy as jnp
            self._fn, self._x = _anchor_fn(), jnp.zeros((8, 128))
            self._fn(self._x).block_until_ready()      # compiled in set-up
            self._thread = threading.Thread(target=self._timer, daemon=True)
            self._thread.start()

    def _anchor(self):
        self._fn(self._x).block_until_ready()
        self.anchors.append((time.time(), time.monotonic()))

    def _timer(self):
        import jax
        if self._abort.wait(self.start_after):
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = HOST_TRACER_LEVEL
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._anchor()
        self.t_start = time.monotonic()
        self._abort.wait(self.length)
        self.t_stop = time.monotonic()
        self._anchor()
        jax.profiler.stop_trace()
        self.stop_seconds = time.monotonic() - self.t_stop

    def close(self):
        """End of the measured window: cut a trace still running, wait for
        the profiler to finish writing."""
        if self._thread is not None:
            self._abort.set()
            self._thread.join()

    def read(self):
        """(Trace, window start, window end) on the trace's clock."""
        if self.t_start is None:
            raise RuntimeError("the measured window ended before the "
                               "profiled sub-window began")
        trace = xplane.read_trace(self.dir)
        trace.set_anchor(self.anchors)
        return (trace, trace.from_monotonic(self.t_start),
                trace.from_monotonic(self.t_stop))
