"""Profile one ResNet-50 train step on the real TPU; print top XLA ops.

Usage: profile_step.py [NHWC|NCHW] [batch] [remat]
The optional third arg profiles the rematerialized whole-graph-AD step
(ROOFLINE.md remat lever) so the measured per-step op time / HBM
arithmetic intensity under remat can be compared against the baseline.
Emits a trailing PROFILE_JSON line for the watcher to archive."""
import glob
import gzip
import json
import os
import sys
import time
from collections import defaultdict

import numpy as np


def main(layout="NHWC", batch=256, remat=False):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import init_backend
    init_backend(tool="profile_step")
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import functionalizer
    from paddle_tpu.models import resnet

    fluid.set_amp(True)
    with fluid.unique_name.guard():
        main_prog, startup, feeds, loss, acc, predict = resnet.get_model(
            batch_size=batch, class_dim=1000, depth=50, dataset="imagenet",
            lr=0.1, is_train=True, layout=layout)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        state_names = tuple(functionalizer.persistable_names(main_prog))
        if remat:
            step_fn = functionalizer.build_whole_graph_step_fn(
                main_prog, ("data", "label"), (loss.name,), state_names,
                remat_policy="conv_out")
            if step_fn is None:
                raise RuntimeError("program ineligible for whole-graph "
                                   "AD; remat profile would be a lie")
        else:
            step_fn = functionalizer.build_step_fn(
                main_prog, ("data", "label"), (loss.name,), state_names)
        jitted = jax.jit(step_fn, donate_argnums=(0,))
        state = {n: scope.get(n) for n in state_names
                 if scope.get(n) is not None}
    rng = np.random.RandomState(0)
    shape = (batch, 3, 224, 224) if layout == "NCHW" \
        else (batch, 224, 224, 3)
    img = jax.device_put(rng.rand(*shape).astype(np.float32))
    lab = jax.device_put(rng.randint(0, 1000, (batch, 1)).astype(np.int32))
    for i in range(3):
        fetches, state = jitted(state, {"data": img, "label": lab},
                                np.uint32(i))
    float(np.asarray(fetches[0]))

    trace_dir = "/tmp/tpu_profile_%s_%d" % (layout, batch)
    os.system("rm -rf %s" % trace_dir)
    with jax.profiler.trace(trace_dir):
        for i in range(3):
            fetches, state = jitted(state, {"data": img, "label": lab},
                                    np.uint32(i + 3))
        float(np.asarray(fetches[0]))

    # parse perfetto trace
    paths = glob.glob(trace_dir + "/**/*.trace.json.gz", recursive=True)
    if not paths:
        print("NO TRACE under", trace_dir)
        return
    with gzip.open(paths[0], "rt") as f:
        trace = json.load(f)
    # find XLA Ops thread(s)
    pid_names = {}
    tid_names = {}
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") == "M":
            if ev.get("name") == "process_name":
                pid_names[ev["pid"]] = ev["args"].get("name", "")
            if ev.get("name") == "thread_name":
                tid_names[(ev["pid"], ev["tid"])] = \
                    ev["args"].get("name", "")
    by_op = defaultdict(float)
    total = 0.0
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        tname = tid_names.get((ev.get("pid"), ev.get("tid")), "")
        pname = pid_names.get(ev.get("pid"), "")
        if "XLA Ops" not in tname:
            continue
        dur = ev.get("dur", 0) / 1e3  # ms
        name = ev.get("name", "?")
        by_op[name] += dur
        total += dur
    items = sorted(by_op.items(), key=lambda kv: -kv[1])
    print("total XLA-op time over 3 steps: %.2f ms (%.2f ms/step)"
          % (total, total / 3))
    print("%-64s %10s %6s" % ("op", "ms", "%"))
    for name, ms in items[:40]:
        print("%-64s %10.3f %5.1f%%" % (name[:64], ms, ms / total * 100))
    print("PROFILE_JSON " + json.dumps({
        "layout": layout, "batch": batch, "remat": remat,
        "ms_per_step": round(total / 3, 2),
        "top_ops": [{"op": n[:96], "ms": round(t, 3)}
                    for n, t in items[:12]]}))


if __name__ == "__main__":
    layout = sys.argv[1] if len(sys.argv) > 1 else "NHWC"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    main(layout, batch, remat="remat" in sys.argv[3:])
