"""Perf experiment: ResNet-50 train step, layout x batch sweep on real TPU.

Usage: PYTHONPATH=/root/repo python tools/bench_experiment.py NHWC 256
"""
import sys
import time

import numpy as np


def run(layout, batch, amp=True, iters=20):
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import functionalizer
    from paddle_tpu.models import resnet

    fluid.set_amp(amp)
    with fluid.unique_name.guard():
        main_prog, startup, feeds, loss, acc, predict = resnet.get_model(
            batch_size=batch, class_dim=1000, depth=50, dataset="imagenet",
            lr=0.1, is_train=True, layout=layout)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        state_names = tuple(functionalizer.persistable_names(main_prog))
        step_fn = functionalizer.build_step_fn(
            main_prog, ("data", "label"), (loss.name,), state_names)
        jitted = jax.jit(step_fn, donate_argnums=(0,))
        state = {n: scope.get(n) for n in state_names
                 if scope.get(n) is not None}
    rng = np.random.RandomState(0)
    shape = (batch, 3, 224, 224) if layout == "NCHW" \
        else (batch, 224, 224, 3)
    n_batches = 2
    images = [jax.device_put(rng.rand(*shape).astype(np.float32))
              for _ in range(n_batches)]
    labels = [jax.device_put(rng.randint(0, 1000, (batch, 1))
                             .astype(np.int32)) for _ in range(n_batches)]
    for i in range(2):
        fetches, state = jitted(state, {"data": images[i % n_batches],
                                        "label": labels[i % n_batches]},
                                np.uint32(i))
    assert np.isfinite(float(np.asarray(fetches[0])))
    t0 = time.perf_counter()
    for i in range(iters):
        fetches, state = jitted(state, {"data": images[i % n_batches],
                                        "label": labels[i % n_batches]},
                                np.uint32(i + 2))
    final = float(np.asarray(fetches[0]))
    dt = time.perf_counter() - t0
    ips = batch * iters / dt
    from paddle_tpu.analysis.resources import device_peaks
    flops = ips * 12.3e9
    peak = device_peaks(jax.devices()[0])["peak_flops"]
    print("layout=%s batch=%d amp=%s: %.1f img/s  %.1f TFLOP/s  %.1f%% MFU "
          "(loss %.4f)" % (layout, batch, amp, ips, flops / 1e12,
                           flops / peak * 100.0, final), flush=True)


if __name__ == "__main__":
    layout = sys.argv[1] if len(sys.argv) > 1 else "NHWC"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    amp = (sys.argv[3] != "0") if len(sys.argv) > 3 else True
    run(layout, batch, amp)
