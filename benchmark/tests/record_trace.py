"""How benchmark/tests/data/tiny_tpu.xplane.pb was recorded (on one v5e chip;
run from the checkout's root: `python3 benchmark/tests/record_trace.py <out>`):
a 256x256 matmul + reduction, four calls with a sleep between, inside the
benchmark's own profiled window (tracewin.Window: host tracer off, the
device-side `bench_anchor` at both ends); the host's stamps of the anchors
and of the four calls are kept beside it in `<out>.json`."""

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.getcwd())


def main(out):
    import jax
    import jax.numpy as jnp
    from benchmark import tracewin, xplane
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()

    class Ctx(object):
        trace, trace_dir, seconds, trace_seconds = True, out + ".dir", 0.0, 0.1
    win = tracewin.Window(Ctx)              # starts at once, lasts 0.1 s
    calls = []
    while win.t_start is None:
        time.sleep(0.001)
    for _ in range(4):
        a = time.monotonic()
        f(x).block_until_ready()
        calls.append((a, time.monotonic()))
        time.sleep(0.002)
    win.close()
    shutil.copy(xplane.find_xplane(Ctx.trace_dir), out)
    shutil.rmtree(Ctx.trace_dir)
    with open(out + ".json", "w") as fh:
        json.dump({"anchors": win.anchors, "calls": calls,
                   "window": [win.t_start, win.t_stop]}, fh)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
