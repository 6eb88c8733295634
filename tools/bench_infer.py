"""Inference benchmark: ResNet-50 NHWC serving throughput, fused vs
unfused.

Measures what the FuseBottleneckPass + Pallas fused_bottleneck kernel buy
on real silicon: the unfused variant is the InferenceTranspiler's BN-fold
output executed by XLA (per-conv epilogue fusion only); the fused variant
additionally collapses every eligible bottleneck onto the VMEM-resident
kernel (ROOFLINE.md "cross-layer fused conv pipelines"). Prints one JSON
line per variant:

  {"metric": "resnet50_infer_images_per_sec_per_chip", "variant": ...,
   "value": N, "unit": "images/sec", "fused_blocks": K}

--smoke asks for the CPU explicitly: tiny batch, `platform: cpu` on every
line. Without it the run needs the chip (exit 3 otherwise).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--smoke", action="store_true",
                    help="force the CPU smoke path")
    ap.add_argument("--bf16", type=int, default=1,
                    help="cast params + input to bf16 (TPU-idiomatic "
                         "serving precision)")
    ap.add_argument("--staged_feed", type=int, default=1,
                    help="stage the input batch on device once and "
                         "reuse it (default): measures the serving "
                         "computation alone. 0 = per-request H2D "
                         "(the realistic serving path)")
    args = ap.parse_args()

    from bench import init_backend
    device = init_backend(smoke=args.smoke, tool="bench_infer")
    on_tpu = device["platform"] == "tpu"
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models.resnet import resnet_imagenet
    batch = args.batch if on_tpu else 4
    iters = args.iters if on_tpu else 2

    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = 17
    with fluid.program_guard(main_prog, startup):
        img = fluid.layers.data(name="data", shape=[224, 224, 3],
                                dtype="float32")
        pred = resnet_imagenet(img, class_dim=1000, depth=50,
                               is_train=False, layout="NHWC")

    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    x = rng.randn(batch, 224, 224, 3).astype(np.float32)

    def cast_params_bf16():
        for var in main_prog.global_block().vars.values():
            if not getattr(var, "persistable", False):
                continue
            val = scope.get(var.name)
            if val is not None and np.asarray(val).dtype == np.float32:
                scope.set(var.name, jnp.asarray(val, jnp.bfloat16))

    def timed(prog, feed_x, tag):
        # warmup/compile; the host transfer fences the device
        out, = exe.run(prog, feed={"data": feed_x},
                       fetch_list=[pred.name])
        assert np.all(np.isfinite(np.asarray(out, np.float32)))
        t0 = time.perf_counter()
        for _ in range(iters):
            out, = exe.run(prog, feed={"data": feed_x},
                           fetch_list=[pred.name])
        np.asarray(out)
        dt = time.perf_counter() - t0
        return batch * iters / dt

    results = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed_x = x
        if args.bf16 and on_tpu:
            cast_params_bf16()
            feed_x = x.astype(jnp.bfloat16)
            # retype the feed var too — prepare_feeds casts feeds to the
            # var's dtype, so a bf16 array fed at a float32 var would be
            # silently cast BACK to fp32
            main_prog.global_block().var("data").dtype = "bfloat16"
        if args.staged_feed:
            # one H2D, reused every request (Executor's prepare_feeds
            # keeps jax.Array feeds as-is); host round-trip fences the
            # transfer out of the timed window
            feed_x = jax.device_put(feed_x)
            np.asarray(feed_x.ravel()[:1])

        infer = main_prog.clone(for_test=True)._prune(["data"],
                                                      [pred.name])
        # unfused: BN folded, blocks left to XLA (fuse pass skipped).
        # The fold mutates the SHARED scope's conv weights, so it runs
        # exactly once; the fused variant clones the folded program.
        from paddle_tpu.fluid.transpiler.inference_transpiler import (
            InferenceTranspiler)
        unfused = infer.clone(for_test=True)
        tr = InferenceTranspiler()
        tr._remove_dropout(unfused)
        tr._fuse_batch_norm(unfused, scope)
        tr._set_is_test(unfused)
        v = timed(unfused, feed_x, "unfused")
        results.append({"metric": "resnet50_infer_images_per_sec_per_chip",
                        "variant": "unfused", "value": round(v, 2),
                        "unit": "images/sec", "batch": batch,
                        "fused_blocks": 0,
                        "staged_feed": bool(args.staged_feed)})

        fused = unfused.clone(for_test=True)
        from paddle_tpu.fluid.ir_passes import apply_passes
        apply_passes(fused, ["fuse_bottleneck_pass"])
        nf = sum(1 for op in fused.global_block().ops
                 if op.type == "fused_bottleneck")
        v = timed(fused, feed_x, "fused")
        results.append({"metric": "resnet50_infer_images_per_sec_per_chip",
                        "variant": "fused", "value": round(v, 2),
                        "unit": "images/sec", "batch": batch,
                        "fused_blocks": nf,
                        "staged_feed": bool(args.staged_feed)})

    for rec in results:
        rec.update(device)
        print(json.dumps(rec))


if __name__ == "__main__":
    main()
