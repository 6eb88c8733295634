"""Fused ResNet bottleneck: Pallas kernel parity + the inference-graph
fusion pass.

The kernel (ops/pallas_kernels.py fused_bottleneck) runs a whole BN-folded
residual block — three convs, both relus, shortcut add — in one
VMEM-resident pallas_call, the "cross-layer fused conv pipeline" lever from
ROOFLINE.md. Reference analogue: the conv+bn+act fusion pass family
(paddle/fluid/framework/ir/conv_bn_fuse_pass.cc) which stops at per-conv
epilogues; fusing across the block is TPU-specific.

Interpret mode makes every test here exact on the CPU mesh.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.ops.pallas_kernels import (fused_bottleneck,
                                           bottleneck_reference)


def _params(rng, C, F, C4, branch):
    t = lambda *s: rng.randn(*s).astype(np.float32) * 0.1
    p = dict(w0=t(C, F), b0=t(F), w1=t(3, 3, F, F), b1=t(F),
             w2=t(F, C4), b2=t(C4))
    p["ws"], p["bs"] = (t(C, C4), t(C4)) if branch else (None, None)
    return p


@pytest.mark.parametrize(
    "N,H,W,C,F,stride,branch",
    [(2, 8, 8, 32, 16, 1, False),      # identity shortcut
     (2, 8, 8, 32, 16, 1, True),       # projection, stride 1
     (2, 8, 8, 32, 16, 2, True),       # projection, stride 2
     (1, 14, 14, 64, 32, 2, True),     # odd output rows (Ho=7)
     (1, 7, 7, 128, 32, 1, False)])    # odd everything
def test_kernel_matches_reference(N, H, W, C, F, stride, branch):
    rng = np.random.RandomState(0)
    C4 = F * 4 if branch else C
    p = _params(rng, C, F, C4, branch)
    x = rng.randn(N, H, W, C).astype(np.float32)
    got = fused_bottleneck(x, p["w0"], p["b0"], p["w1"], p["b1"], p["w2"],
                           p["b2"], p["ws"], p["bs"], stride=stride,
                           interpret=True)
    want = bottleneck_reference(x, p["w0"], p["b0"], p["w1"], p["b1"],
                                p["w2"], p["b2"], p["ws"], p["bs"], stride)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_kernel_bf16():
    import jax.numpy as jnp
    rng = np.random.RandomState(1)
    p = _params(rng, 32, 16, 64, True)
    x = rng.randn(2, 8, 8, 32).astype(np.float32)
    cast = lambda a: None if a is None else jnp.asarray(a, jnp.bfloat16)
    got = fused_bottleneck(cast(x), *(cast(p[k]) for k in
                                      ("w0", "b0", "w1", "b1", "w2", "b2",
                                       "ws", "bs")),
                           stride=1, interpret=True)
    want = bottleneck_reference(x, p["w0"], p["b0"], p["w1"], p["b1"],
                                p["w2"], p["b2"], p["ws"], p["bs"], 1)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=0.12, rtol=0.12)


def test_untileable_falls_back():
    # odd W under stride 2 cannot reshape-decimate: the wrapper must
    # return the plain-XLA composition rather than fail
    rng = np.random.RandomState(2)
    p = _params(rng, 16, 8, 32, True)
    x = rng.randn(1, 9, 9, 16).astype(np.float32)
    got = fused_bottleneck(x, p["w0"], p["b0"], p["w1"], p["b1"], p["w2"],
                           p["b2"], p["ws"], p["bs"], stride=2,
                           interpret=True)
    want = bottleneck_reference(x, p["w0"], p["b0"], p["w1"], p["b1"],
                                p["w2"], p["b2"], p["ws"], p["bs"], 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# graph-level: InferenceTranspiler folds BN then collapses NHWC blocks
# ---------------------------------------------------------------------------

@pytest.fixture
def fusion_enabled():
    """Fusion is opt-in (FLAGS.fuse_bottleneck_max_width defaults to 0:
    the r05 chip runs measured the fused graph slower end-to-end at
    every width gate) — graph tests that exercise the pass itself
    enable it explicitly."""
    from paddle_tpu.flags import set_flags, get_flags
    old = get_flags("fuse_bottleneck_max_width")
    set_flags({"fuse_bottleneck_max_width": 128})
    yield
    set_flags(old)


def _build_resnet_tail(layout):
    """data -> bottleneck(stride 2, projection) -> bottleneck(identity)."""
    from paddle_tpu.models.resnet import bottleneck_block
    main = fluid.Program()
    startup = fluid.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with fluid.program_guard(main, startup):
        shape = [8, 8, 16] if layout == "NHWC" else [16, 8, 8]
        img = fluid.layers.data(name="img", shape=shape, dtype="float32")
        out = bottleneck_block(img, 8, 2, is_train=False, layout=layout)
        out = bottleneck_block(out, 8, 1, is_train=False, layout=layout)
    return main, startup, out


@pytest.mark.parametrize("layout", ["NHWC", "NCHW"])
def test_transpiler_fuses_nhwc_blocks(layout, fusion_enabled):
    main, startup, out = _build_resnet_tail(layout)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(3)
    shape = (4, 8, 8, 16) if layout == "NHWC" else (4, 16, 8, 8)
    x = rng.randn(*shape).astype(np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        want, = exe.run(main, feed={"img": x}, fetch_list=[out.name])
        infer = main.clone(for_test=True)
        from paddle_tpu.fluid.transpiler import InferenceTranspiler
        InferenceTranspiler().transpile(infer, scope=scope)
        types = [op.type for op in infer.global_block().ops]
        if layout == "NHWC":
            # both blocks collapse: no loose conv/add/relu remain
            assert types.count("fused_bottleneck") == 2, types
            assert "conv2d" not in types and "relu" not in types, types
        else:
            # NCHW stays on the XLA path (kernel is lane-aligned NHWC)
            assert "fused_bottleneck" not in types, types
        got, = exe.run(infer, feed={"img": x}, fetch_list=[out.name])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_wide_bottleneck_declines_fusion():
    """Measured-geometry gate: the r05 chip sweep (tune_bottleneck
    stages, ROOFLINE.md round 5) showed the Pallas kernel LOSES
    to XLA for wide bottlenecks (F=256/512), so the pass must fuse only
    blocks with F <= FLAGS.fuse_bottleneck_max_width and leave wide
    ones (numerically intact) to XLA."""
    from paddle_tpu.flags import set_flags, get_flags
    main, startup, out = _build_resnet_tail("NHWC")   # width F = 8
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(11)
    x = rng.randn(4, 8, 8, 16).astype(np.float32)
    old = get_flags("fuse_bottleneck_max_width")
    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
            want, = exe.run(main, feed={"img": x}, fetch_list=[out.name])
            from paddle_tpu.fluid.transpiler import InferenceTranspiler
            # cap below this model's width: nothing may fuse
            set_flags({"fuse_bottleneck_max_width": 4})
            infer = main.clone(for_test=True)
            InferenceTranspiler().transpile(infer, scope=scope)
            types = [op.type for op in infer.global_block().ops]
            assert "fused_bottleneck" not in types, types
            got, = exe.run(infer, feed={"img": x}, fetch_list=[out.name])
            np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
            # cap at the width: both blocks fuse again
            set_flags({"fuse_bottleneck_max_width": 8})
            infer2 = main.clone(for_test=True)
            InferenceTranspiler().transpile(infer2, scope=scope)
            types2 = [op.type for op in infer2.global_block().ops]
            assert types2.count("fused_bottleneck") == 2, types2
    finally:
        set_flags(old)


def test_nhwc_bn_fold_bias_axis():
    # regression: the folded BN bias add must broadcast over the channel
    # axis of the conv's layout — for NHWC that is the trailing dim, and
    # H != C here so a wrong axis is a loud shape error (or silent
    # corruption when H == C)
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[6, 6, 5],
                                dtype="float32")
        conv = fluid.layers.conv2d(input=img, num_filters=7, filter_size=3,
                                   padding=1, act=None, bias_attr=False,
                                   data_format="NHWC")
        out = fluid.layers.batch_norm(input=conv, act=None, is_test=True,
                                      data_layout="NHWC")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(4)
    x = rng.randn(2, 6, 6, 5).astype(np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        want, = exe.run(main, feed={"img": x}, fetch_list=[out.name])
        infer = main.clone(for_test=True)
        from paddle_tpu.fluid.transpiler import InferenceTranspiler
        InferenceTranspiler().transpile(infer, scope=scope)
        got, = exe.run(infer, feed={"img": x}, fetch_list=[out.name])
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_biased_conv_declines_fusion(fusion_enabled):
    """A conv2d carrying an inline Bias input has no slot in the fused
    kernel; the PASS must leave that block unfused (and numerically
    intact) instead of silently dropping the bias. The transpiler's own
    BN fold absorbs inline biases before the pass runs (tested below),
    so this models a LOADED, already-folded program with a stray inline
    bias — the pass is applied directly."""
    main, startup, out = _build_resnet_tail("NHWC")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(6)
    x = rng.randn(4, 8, 8, 16).astype(np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        infer = main.clone(for_test=True)
        from paddle_tpu.fluid.transpiler import InferenceTranspiler
        it = InferenceTranspiler()
        it._remove_dropout(infer)
        it._fuse_batch_norm(infer, scope)   # folded, not yet fused
        blk = infer.global_block()
        conv = next(op for op in blk.ops if op.type == "conv2d")
        w = blk._find_var_recursive(conv.inputs["Filter"][0])
        bias_name = "inline_conv_bias"
        blk.create_var(name=bias_name, shape=(int(w.shape[0]),),
                       dtype="float32", persistable=True)
        scope.set(bias_name,
                  rng.randn(int(w.shape[0])).astype(np.float32))
        conv.inputs["Bias"] = [bias_name]
        want, = exe.run(infer, feed={"img": x}, fetch_list=[out.name])
        from paddle_tpu.fluid.ir_passes import apply_passes
        apply_passes(infer, ["fuse_bottleneck_pass"])
        types = [op.type for op in infer.global_block().ops]
        # the biased block stays on loose ops; the clean block still fuses
        assert types.count("fused_bottleneck") == 1, types
        assert "conv2d" in types, types
        got, = exe.run(infer, feed={"img": x}, fetch_list=[out.name])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_bn_fold_absorbs_inline_conv_bias(fusion_enabled):
    """BN(conv + b) folds to inv_std*conv + (beta + (b - mean)*inv_std):
    the inline bias must be scaled into the folded add and removed from
    the conv, not left to double-apply (or silently drop)."""
    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[6, 6, 5],
                                dtype="float32")
        conv = fluid.layers.conv2d(input=img, num_filters=7, filter_size=3,
                                   padding=1, act=None, bias_attr=False,
                                   data_format="NHWC")
        out = fluid.layers.batch_norm(input=conv, act=None, is_test=True,
                                      data_layout="NHWC")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(9)
    x = rng.randn(2, 6, 6, 5).astype(np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        blk = main.global_block()
        conv_op = next(op for op in blk.ops if op.type == "conv2d")
        blk.create_var(name="cb", shape=(7,), dtype="float32",
                       persistable=True)
        scope.set("cb", rng.randn(7).astype(np.float32))
        conv_op.inputs["Bias"] = ["cb"]
        # non-trivial running stats so a wrong fold is numerically loud
        for v in blk.vars.values():
            n, a = v.name, scope.get(v.name)
            if a is None or np.asarray(a).ndim != 1 or n == "cb" or \
                    "batch_norm" not in n:
                continue
            a = np.asarray(a)
            if n.split(".")[-1].startswith("var"):
                scope.set(n, (0.05 + rng.rand(*a.shape) * 2.0)
                          .astype(a.dtype))
            else:
                scope.set(n, rng.randn(*a.shape).astype(a.dtype) * 0.5)
        want, = exe.run(main, feed={"img": x}, fetch_list=[out.name])
        infer = main.clone(for_test=True)
        from paddle_tpu.fluid.transpiler import InferenceTranspiler
        InferenceTranspiler().transpile(infer, scope=scope)
        iblk = infer.global_block()
        itypes = [op.type for op in iblk.ops]
        assert "batch_norm" not in itypes, itypes
        iconv = next(op for op in iblk.ops if op.type == "conv2d")
        assert not iconv.inputs.get("Bias"), iconv.inputs
        got, = exe.run(infer, feed={"img": x}, fetch_list=[out.name])
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_fused_program_exports_aot(tmp_path, fusion_enabled):
    """The AnalysisPredictor path (BN fold + block fusion) must still
    AOT-export and serve in a fresh predictor: the fused op's kernel has
    to survive jax.export serialization."""
    main, startup, out = _build_resnet_tail("NHWC")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(5)
    x = rng.randn(4, 8, 8, 16).astype(np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        md = str(tmp_path / "model")
        fluid.save_inference_model(md, ["img"], [out], exe,
                                   main_program=main)
        from paddle_tpu.inference import (AnalysisConfig,
                                          create_paddle_predictor,
                                          load_aot_predictor)
        p = create_paddle_predictor(AnalysisConfig(model_dir=md))
        types = [op.type for op in p._program.global_block().ops]
        assert types.count("fused_bottleneck") == 2, types
        ref, = p.run({"img": x})
        ad = str(tmp_path / "aot")
        p.save_aot(ad, batch_sizes=(4,))
        got, = load_aot_predictor(ad).run({"img": x})
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize(
    "C,F,stride,branch,dtype",
    [(32, 16, 1, False, "bfloat16"),
     (32, 16, 2, True, "bfloat16"),
     (64, 32, 2, True, "float32"),
     (128, 32, 1, False, "bfloat16")])
def test_kernel_lowers_for_tpu_offchip(C, F, stride, branch, dtype):
    """Pallas -> Mosaic conversion happens at LOWERING time, so the
    kernel's TPU path is checkable without a chip: cross-platform
    jax.export must produce a tpu_custom_call carrying the serialized
    Mosaic module. Catches Mosaic-side regressions (unsupported ops,
    layout constraints) from the CPU suite."""
    import jax
    import jax.numpy as jnp
    from jax import export as jax_export

    C4 = F * 4 if branch else C
    H = 16
    dt = jnp.dtype(dtype)

    def fn(x, w0, b0, w1, b1, w2, b2, ws, bs):
        return fused_bottleneck(
            x, w0, b0, w1, b1, w2, b2,
            ws if branch else None, bs if branch else None,
            stride=stride, interpret=False)

    shapes = [(4, H, H, C), (C, F), (F,), (3, 3, F, F), (F,), (F, C4),
              (C4,), (C, C4), (C4,)]
    specs = [jax.ShapeDtypeStruct(s, dt) for s in shapes]
    exp = jax_export.export(jax.jit(fn), platforms=["tpu"])(*specs)
    mlir = exp.mlir_module()
    assert "tpu_custom_call" in mlir, \
        "fused kernel fell back instead of lowering to Mosaic"


def test_flash_attention_lowers_for_tpu_offchip():
    import jax
    import jax.numpy as jnp
    from jax import export as jax_export
    from paddle_tpu.ops.pallas_kernels import flash_attention

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    spec = jax.ShapeDtypeStruct((2, 512, 4, 128), jnp.bfloat16)
    exp = jax_export.export(jax.jit(fn), platforms=["tpu"])(
        spec, spec, spec)
    assert "tpu_custom_call" in exp.mlir_module()


def test_transpiled_program_embeds_mosaic_kernel_for_tpu(fusion_enabled):
    """The DEFAULT path (interpret unspecified) must choose per lowering
    platform: a TPU export of the fusion-transpiled serving program from
    this CPU host embeds the real Mosaic kernels, while CPU execution
    keeps the interpret branch (exercised by the parity tests above)."""
    from paddle_tpu.fluid import functionalizer
    main, startup, out = _build_resnet_tail("NHWC")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        infer = main.clone(for_test=True)
        from paddle_tpu.fluid.transpiler import InferenceTranspiler
        InferenceTranspiler().transpile(infer, scope=scope)
        sn = tuple(functionalizer.persistable_names(infer))
        state = {n: scope.get(n) for n in sn
                 if scope.get(n) is not None}
    step_fn = functionalizer.build_step_fn(
        infer, ("img",), (out.name,), tuple(state.keys()))
    exp = functionalizer.export_step_for_tpu(
        step_fn, state, {"img": ((4, 8, 8, 16), np.float32)})
    assert exp.mlir_module().count("tpu_custom_call") >= 2


def test_fused_artifact_cross_compiles_for_tpu(tmp_path, fusion_enabled):
    """save_aot(platforms=("tpu",)) from this CPU build host: the
    artifact must embed the REAL Mosaic kernels (not interpret
    emulation) for the TPU target. cpu+tpu multi-platform with Pallas
    is NOT supported (jax lowers every platform_dependent branch on
    every platform when the index is dynamic; pallas has no
    non-interpret CPU lowering) — the save_aot docstring records that;
    single-target cross-compilation is the supported build-host
    story."""
    from jax import export as jax_export
    import os as _os
    main, startup, out = _build_resnet_tail("NHWC")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        md = str(tmp_path / "m")
        fluid.save_inference_model(md, ["img"], [out], exe,
                                   main_program=main)
        from paddle_tpu.inference import (AnalysisConfig,
                                          create_paddle_predictor)
        p = create_paddle_predictor(AnalysisConfig(model_dir=md))
        types = [op.type for op in p._program.global_block().ops]
        assert types.count("fused_bottleneck") == 2, types
        ad = str(tmp_path / "aot")
        p.save_aot(ad, batch_sizes=(4,), platforms=("tpu",))
    with open(_os.path.join(ad, "aot_b4.bin"), "rb") as f:
        exp = jax_export.deserialize(f.read())
    assert [pl.lower() for pl in exp.platforms] == ["tpu"]
    assert exp.mlir_module().count("tpu_custom_call") >= 2
