"""Off-chip TPU-lowering sweep for the benchmark zoo.

Pallas->Mosaic conversion and XLA lowering happen at jax.export time,
so every zoo config's training step can be validated for the TPU
platform from a CPU-only host — no chip time gets burned
discovering a lowering bug mid-sweep. Prints one JSON line per config:

  {"config": ..., "ok": true, "mlir_bytes": N}
  {"config": ..., "ok": false, "error": ..., "note": ...}

Run after kernel/model/functionalizer changes; the per-kernel fast
guards live in the suite (tests/test_fused_bottleneck.py,
test_whole_graph_ad.py) — this sweep is the full-model version.
"""

import argparse
import json
import os
import sys
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# (name, model, kwargs, batch, amp, remat)
CONFIGS = [
    ("mnist_cnn", "mnist", {}, 16, True, None),
    ("resnet50_nhwc", "resnet", {"dataset": "imagenet",
                                 "layout": "NHWC"}, 8, True, None),
    ("resnet50_nhwc_remat", "resnet", {"dataset": "imagenet",
                                       "layout": "NHWC"}, 8, True,
     "conv_out"),
    ("resnet50_nhwc_remat_blk", "resnet", {"dataset": "imagenet",
                                           "layout": "NHWC"}, 8, True,
     "block_out"),
    ("se_resnext_nhwc", "se_resnext", {"layout": "NHWC"}, 4, True, None),
    ("vgg16_cifar10", "vgg", {"dataset": "cifar10"}, 8, True, None),
    ("vgg16_cifar10_remat", "vgg", {"dataset": "cifar10"}, 8, True,
     "conv_out"),
    ("stacked_dynamic_lstm", "stacked_dynamic_lstm", {}, 8, True, None),
    ("transformer", "transformer", {}, 4, True, None),
    ("machine_translation", "machine_translation", {}, 4, True, None),
]


def check(name, model, kwargs, batch, amp, remat):
    import importlib
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import functionalizer
    from paddle_tpu.fluid.executor import prepare_feeds
    from fluid_benchmark import synth_feed

    fluid.set_amp(amp)
    with fluid.unique_name.guard():
        mod = importlib.import_module("paddle_tpu.models.%s" % model)
        main_prog, startup, feeds, loss, acc, _ = mod.get_model(
            batch_size=batch, **kwargs)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        feeds = [main_prog.global_block().var(f)
                 if isinstance(f, str) else f for f in feeds]
        rng = np.random.RandomState(0)
        feed = synth_feed(feeds, batch, rng, program=main_prog)
        dense = prepare_feeds(main_prog, feed, device_put=False)
        sn = tuple(functionalizer.persistable_names(main_prog))
        state = {n: scope.get(n) for n in sn
                 if scope.get(n) is not None}
    feed_key = tuple(sorted(dense.keys()))
    step_fn = functionalizer.build_step_fn(
        main_prog, feed_key, (loss.name,), tuple(state.keys()),
        whole_graph_ad=bool(remat), remat_policy=remat)
    feed_specs = {n: (np.shape(v), np.asarray(v).dtype)
                  for n, v in dense.items()}
    exp = functionalizer.export_step_for_tpu(step_fn, state, feed_specs)
    return len(exp.mlir_module_serialized)


def check_spmd_dp16():
    """BASELINE config 5 (v5e-16 pod): the ResNet-50 NHWC bf16 training
    step sharded dp=16 over an ABSTRACT 16-TPU-device mesh — the
    north-star topology's lowering, validated with zero chips (the
    partitioner consumes the sdy.sharding annotations at target-compile
    time; SCALING_r04.md has the compiled-HLO collective census)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import functionalizer
    from paddle_tpu.models import resnet

    fluid.set_amp(True)
    with fluid.unique_name.guard():
        main_prog, startup, feeds, loss, acc, _ = resnet.get_model(
            batch_size=64, class_dim=1000, depth=50, dataset="imagenet",
            is_train=True, layout="NHWC")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        sn = tuple(functionalizer.persistable_names(main_prog))
        state = {n: scope.get(n) for n in sn if scope.get(n) is not None}
    # trace against the virtual CPU mesh; export against the abstract
    # TPU one (build_step_fn only reads axis names from the mesh)
    n_cpu = len(jax.devices())
    cpu_mesh = Mesh(np.array(jax.devices()).reshape(n_cpu), ("data",))
    step_fn = functionalizer.build_step_fn(
        main_prog, ("data", "label"), (loss.name,), tuple(state.keys()),
        mesh=cpu_mesh)
    amesh = jax.sharding.AbstractMesh((16,), ("data",))
    state_specs = {n: jax.ShapeDtypeStruct(
        np.shape(v), np.asarray(v).dtype,
        sharding=NamedSharding(amesh, P())) for n, v in state.items()}
    feed_specs = {
        "data": jax.ShapeDtypeStruct((64, 224, 224, 3), np.float32,
                                     sharding=NamedSharding(
                                         amesh, P("data"))),
        "label": jax.ShapeDtypeStruct((64, 1), np.int32,
                                      sharding=NamedSharding(
                                          amesh, P("data"))),
    }
    exp = functionalizer.export_step_for_tpu(step_fn, state_specs,
                                             feed_specs)
    assert exp.nr_devices == 16, exp.nr_devices
    return len(exp.mlir_module_serialized)


def check_fused_serving():
    """The fusion-transpiled ResNet-50 NHWC serving graph: all 16
    bottlenecks collapsed onto the Pallas kernel, exported for TPU —
    the module must carry the Mosaic custom calls (the kernel-geometry
    guards live in tests/test_fused_bottleneck.py; this is the
    full-model version)."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import functionalizer
    from paddle_tpu.models.resnet import resnet_imagenet

    # AMP is process-global and the preceding training checks enable
    # it — pin explicitly so --only runs and full sweeps trace the
    # SAME module (serving precision is the artifact's own, fp32 here;
    # bf16 serving casts are bench_infer's explicit job)
    fluid.set_amp(False)
    with fluid.unique_name.guard():
        main_prog, startup = fluid.Program(), fluid.Program()
        main_prog.random_seed = startup.random_seed = 17
        with fluid.program_guard(main_prog, startup):
            img = fluid.layers.data(name="data", shape=[224, 224, 3],
                                    dtype="float32")
            pred = resnet_imagenet(img, class_dim=1000, depth=50,
                                   is_train=False, layout="NHWC")
    scope = fluid.Scope()
    from paddle_tpu.flags import set_flags, get_flags
    old_width = get_flags("fuse_bottleneck_max_width")
    try:
        with fluid.scope_guard(scope):
            fluid.Executor(fluid.CPUPlace()).run(startup)
            infer = main_prog.clone(for_test=True)._prune(["data"],
                                                          [pred.name])
            from paddle_tpu.fluid.transpiler import InferenceTranspiler
            # fusion defaults OFF (measured slower end-to-end,
            # ROOFLINE.md); this check validates the OPT-IN path still
            # lowers every geometry through Mosaic, so fuse-all
            set_flags({"fuse_bottleneck_max_width": 1 << 30})
            InferenceTranspiler().transpile(infer, scope=scope)
            n_fused = sum(1 for op in infer.global_block().ops
                          if op.type == "fused_bottleneck")
            assert n_fused == 16, n_fused
    finally:
        set_flags(old_width)
    sn = tuple(functionalizer.persistable_names(infer))
    state = {n: scope.get(n) for n in sn
             if scope.get(n) is not None}
    step_fn = functionalizer.build_step_fn(
        infer, ("data",), (pred.name,), tuple(state.keys()))
    exp = functionalizer.export_step_for_tpu(
        step_fn, state, {"data": ((8, 224, 224, 3), np.float32)})
    n_calls = exp.mlir_module().count("tpu_custom_call")
    assert n_calls >= 1, "no Mosaic kernel in the serving module"
    return len(exp.mlir_module_serialized)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated config-name substring filter")
    args = ap.parse_args()
    # pin CPU BEFORE any backend query: on a host with a chip the first
    # jax op would otherwise take the TPU this sweep exists to avoid
    # touching
    import jax
    jax.config.update("jax_platforms", "cpu")
    wanted = [w for w in args.only.split(",") if w]
    failures = 0

    def run_one(name, fn):
        nonlocal failures
        try:
            n = fn()
            print(json.dumps({"config": name, "ok": True,
                              "mlir_bytes": n}), flush=True)
        except Exception as e:
            failures += 1
            print(json.dumps({
                "config": name, "ok": False,
                "error": type(e).__name__,
                "note": (str(e).splitlines() or [""])[0][:300]}),
                flush=True)
            traceback.print_exc(file=sys.stderr)

    entries = [(cfg[0], (lambda c=cfg: check(*c)))
               for cfg in CONFIGS]
    entries.append(("resnet50_dp16_pod", check_spmd_dp16))
    entries.append(("resnet50_infer_fused", check_fused_serving))
    for name, thunk in entries:
        if wanted and not any(w in name for w in wanted):
            continue
        run_one(name, thunk)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
