"""Benchmark: ResNet-50 training throughput on one TPU chip.

Matches BASELINE.json's flagship config (benchmark/fluid/resnet.py,
ImageNet-shape inputs, Momentum+L2, batch 256 global). The north star is
v5e-16 >= 8xV100; published 8xV100 fp32 ResNet-50 throughput of that era is
~2.9k images/s total, i.e. ~181 images/s per v5e chip at 16 chips. We report
images/sec on ONE chip and vs_baseline = value / 181.25.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count"}.  A measurement needs the chip:
without one the script exits 3 and prints no metric.
"""

import json
import os
import sys
import time

import numpy as np

BASELINE_PER_CHIP = 181.25  # 8xV100 fp32 (~2900 img/s) / 16 chips


def init_backend(smoke=False, tool="bench"):
    """Shared preamble of the bench tools: bring jax up IN THIS PROCESS
    (a chip belongs to one process — no probing child) and return the
    device record every result line carries: {"platform", "device_kind",
    "device_count"} as jax reports them.

    `smoke=True` is the caller asking for the CPU explicitly (tiny shapes,
    path check): jax is held to it and the record says `platform: cpu`.
    Otherwise the run is a measurement, and a platform other than `tpu`
    is exit 3 — never a CPU number under a device metric's name."""
    if smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    devs = jax.devices()
    if not smoke and devs[0].platform != "tpu":
        print("%s: jax platform is %r, not tpu — no measurement without "
              "the chip" % (tool, devs[0].platform), file=sys.stderr)
        sys.exit(3)
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def main():
    device = init_backend()
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import functionalizer
    from paddle_tpu.models import resnet

    batch = int(os.environ.get("BENCH_BATCH", 256))
    # bf16 AMP (fp32 master weights + MXU-native bf16 matmuls) unless
    # explicitly disabled — the TPU-idiomatic training precision
    if os.environ.get("BENCH_AMP", "1") == "1":
        fluid.set_amp(True)
    # NHWC: channels-last activations (lane-aligned BN); filters stay OIHW
    layout = os.environ.get("BENCH_LAYOUT", "NHWC")

    main_prog, startup, feeds, loss, acc, predict = resnet.get_model(
        batch_size=batch, class_dim=1000, depth=50, dataset="imagenet",
        lr=0.1, is_train=True, layout=layout)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup)
    scope = fluid.global_scope()
    state_names = tuple(functionalizer.persistable_names(main_prog))
    # whole-graph AD: one jax.vjp over the forward region (vs per-op
    # stashed vjps). Required for BENCH_REMAT to mean anything — a
    # jax.checkpoint around a program whose backward is already baked in
    # is a no-op (there is no outer differentiation for the policy to
    # act on); with whole-graph AD the save_only_these_names("conv_out")
    # policy genuinely drops BN/activation tails and recomputes them in
    # the backward (ROOFLINE.md remat lever).
    remat = os.environ.get("BENCH_REMAT", "0") == "1"
    # "conv_out" keeps every conv output (recompute BN/relu tails);
    # "block_out" keeps only residual-block boundaries (recompute block
    # interiors) — the larger projected lever (tools/fused_block_traffic.py)
    remat_policy = os.environ.get("BENCH_REMAT_POLICY", "conv_out")
    whole_graph = os.environ.get("BENCH_WHOLEGRAPH", "1") == "1"
    if whole_graph or remat:
        step_fn = functionalizer.build_whole_graph_step_fn(
            main_prog, ("data", "label"), (loss.name,), state_names,
            remat_policy=remat_policy if remat else None)
        if step_fn is None and remat:
            # never mislabel a baseline run as a remat measurement
            raise RuntimeError(
                "BENCH_REMAT=1 but the program is ineligible for "
                "whole-graph AD (remat would silently not engage)")
        if step_fn is None:
            step_fn = functionalizer.build_step_fn(
                main_prog, ("data", "label"), (loss.name,), state_names)
    else:
        step_fn = functionalizer.build_step_fn(
            main_prog, ("data", "label"), (loss.name,), state_names)
    jitted = jax.jit(step_fn, donate_argnums=(0,))

    state = {n: scope.get(n) for n in state_names
             if scope.get(n) is not None}
    rng = np.random.RandomState(0)
    img_shape = (batch, 3, 224, 224) if layout == "NCHW" \
        else (batch, 224, 224, 3)
    iters = 20
    # BENCH_PREFETCH=<depth>: feed the loop through the device prefetch
    # queue — every batch is freshly generated ON THE HOST and staged by
    # the background thread (reader.prefetch_to_device, PIPELINE.md), so
    # the number includes the real per-step feed path with the pipeline
    # hiding it. Default: pre-staged rotating device batches (the
    # double-buffer reader's steady state; feed cost amortized away).
    prefetch = int(os.environ.get("BENCH_PREFETCH", "0"))
    if prefetch > 0:
        from paddle_tpu import reader as reader_mod

        def host_batches():
            for _ in range(2 + iters):
                yield {"data": rng.randn(*img_shape).astype(np.float32),
                       "label": rng.randint(0, 1000, (batch, 1))
                       .astype(np.int32)}
        feed_it = reader_mod.prefetch_to_device(host_batches, prefetch)()
        next_feed = lambda i: next(feed_it)  # noqa: E731
    else:
        # pre-staged rotating batches
        n_batches = 4
        images = [jax.device_put(rng.randn(*img_shape).astype(np.float32))
                  for _ in range(n_batches)]
        labels = [jax.device_put(rng.randint(0, 1000, (batch, 1))
                                 .astype(np.int32))
                  for _ in range(n_batches)]
        next_feed = lambda i: {"data": images[i % n_batches],  # noqa: E731
                               "label": labels[i % n_batches]}

    # warmup / compile; the host transfer of the loss fences the device
    for i in range(2):
        fetches, state = jitted(state, next_feed(i), np.uint32(i))
    warm_loss = float(np.asarray(fetches[0]))
    assert np.isfinite(warm_loss)

    t0 = time.perf_counter()
    for i in range(iters):
        fetches, state = jitted(state, next_feed(i + 2), np.uint32(i + 2))
    final_loss = float(np.asarray(fetches[0]))  # host transfer = real fence
    dt = time.perf_counter() - t0
    assert np.isfinite(final_loss)

    imgs_per_sec = batch * iters / dt
    # model FLOPs: ResNet-50 train ~= 12.3 GFLOP/image (2.05 GMAC fwd x2
    # x3), against the peak of the device kind jax reports (the peaks
    # table raises on a kind it does not know)
    from paddle_tpu.analysis.resources import device_peaks
    peak = device_peaks(jax.devices()[0])["peak_flops"]
    flops = imgs_per_sec * 12.3e9
    print("MFU note: %.1f TFLOP/s model FLOPs = %.1f%% of bf16 peak"
          % (flops / 1e12, flops / peak * 100.0))
    result = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(imgs_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(imgs_per_sec / BASELINE_PER_CHIP, 3),
        # feed provenance: staged rows amortize the transfer away,
        # prefetch rows include the real host feed path hidden by the
        # pipeline — the two must never be compared unlabeled
        **({"feed": "prefetch(depth=%d)" % prefetch}
           if prefetch > 0 else {}),
        **device,
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
