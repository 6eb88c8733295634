"""Whole-graph AD mode (functionalizer.build_whole_graph_step_fn).

The per-op interpreter stashes a jax.vjp per forward op, so fwd+bwd are one
dataflow graph and a jax.checkpoint around the step cannot rematerialize
anything. Whole-graph mode serves the program's backward section with ONE
jax.vjp over the forward region — the formulation under which
save_only_these_names("conv_out") (tagged at ops/nn_ops.py:72) is real.

Parity contract: bitwise-equal losses/grads/updated state vs the per-op
path in fp32; bf16-rounding-schedule-level differences under AMP (each
path materializes cotangents at different op boundaries).
"""

import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import functionalizer


def _conv_model(lr=0.1, with_while=False):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[8, 8, 3], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        conv = fluid.layers.conv2d(
            input=img, num_filters=8, filter_size=3, padding=1, act=None,
            data_format="NHWC")
        bn = fluid.layers.batch_norm(input=conv, act="relu",
                                     data_layout="NHWC")
        pool = fluid.layers.pool2d(input=bn, pool_size=2, pool_stride=2,
                                   pool_type="max", data_format="NHWC")
        if with_while:
            i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
            n = fluid.layers.fill_constant(shape=[1], dtype="int64", value=2)
            w = fluid.layers.While(cond=fluid.layers.less_than(i, n))
            with w.block():
                fluid.layers.increment(i, in_place=True)
        fc = fluid.layers.fc(input=pool, size=10)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(fc, label))
        fluid.optimizer.MomentumOptimizer(
            learning_rate=lr, momentum=0.9).minimize(loss)
    return main, startup, loss


def _setup(main, startup):
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        sn = tuple(functionalizer.persistable_names(main))
        state = {n: scope.get(n) for n in sn if scope.get(n) is not None}
    return sn, state


def _batch(rng, bs=4):
    return {"img": rng.randn(bs, 8, 8, 3).astype(np.float32),
            "label": rng.randint(0, 10, (bs, 1)).astype(np.int64)}


def test_whole_graph_matches_per_op_fp32_exactly():
    fluid.set_amp(False)
    main, startup, loss = _conv_model()
    sn, state = _setup(main, startup)
    gname = main.global_block().all_parameters()[0].name + "@GRAD"
    fetches = (loss.name, gname)

    per_op = functionalizer.build_step_fn(main, ("img", "label"), fetches, sn)
    wg = functionalizer.build_whole_graph_step_fn(
        main, ("img", "label"), fetches, sn)
    assert wg is not None

    rng = np.random.RandomState(0)
    batches = [_batch(rng) for _ in range(3)]
    st_a, st_b = dict(state), dict(state)
    for i, b in enumerate(batches):
        fa, st_a = jax.jit(per_op)(st_a, b, np.uint32(i))
        fb, st_b = jax.jit(wg)(st_b, b, np.uint32(i))
        np.testing.assert_array_equal(np.asarray(fa[0]), np.asarray(fb[0]))
        np.testing.assert_array_equal(np.asarray(fa[1]), np.asarray(fb[1]))
    for n in sn:
        if st_a.get(n) is not None:
            np.testing.assert_array_equal(
                np.asarray(st_a[n]), np.asarray(st_b[n]), err_msg=n)


def test_whole_graph_amp_parity_within_bf16_noise():
    fluid.set_amp(True)
    try:
        main, startup, loss = _conv_model()
        sn, state = _setup(main, startup)
        per_op = functionalizer.build_step_fn(
            main, ("img", "label"), (loss.name,), sn)
        wg = functionalizer.build_whole_graph_step_fn(
            main, ("img", "label"), (loss.name,), sn)
        assert wg is not None
        rng = np.random.RandomState(1)
        b = _batch(rng)
        st_a, st_b = dict(state), dict(state)
        la = lb = None
        for i in range(3):
            fa, st_a = jax.jit(per_op)(st_a, b, np.uint32(i))
            fb, st_b = jax.jit(wg)(st_b, b, np.uint32(i))
            la, lb = float(np.asarray(fa[0])), float(np.asarray(fb[0]))
            np.testing.assert_allclose(la, lb, rtol=5e-2)
    finally:
        fluid.set_amp(False)


def test_remat_policy_recomputes_bn_not_conv():
    """save_only_these_names('conv_out') must add recompute (BN sqrt /
    relu+pool maximum ops duplicated into the backward) while convs stay
    saved (count fixed)."""
    fluid.set_amp(False)
    main, startup, loss = _conv_model()
    sn, state = _setup(main, startup)
    wg = functionalizer.build_whole_graph_step_fn(
        main, ("img", "label"), (loss.name,), sn)
    wg_remat = functionalizer.build_whole_graph_step_fn(
        main, ("img", "label"), (loss.name,), sn, remat_policy="conv_out")
    rng = np.random.RandomState(2)
    b = _batch(rng)
    texts = {}
    for name, fn in (("plain", wg), ("remat", wg_remat)):
        texts[name] = jax.jit(fn).lower(
            state, b, np.uint32(0)).as_text()
    assert (texts["plain"].count("stablehlo.convolution")
            == texts["remat"].count("stablehlo.convolution"))
    for recomputed in ("stablehlo.sqrt", "stablehlo.maximum"):
        assert (texts["remat"].count(recomputed)
                > texts["plain"].count(recomputed)), recomputed
    # and the numbers still match (recompute is exact: deterministic RNG)
    f_a, _ = jax.jit(wg)(state, b, np.uint32(0))
    f_b, _ = jax.jit(wg_remat)(state, b, np.uint32(0))
    np.testing.assert_array_equal(np.asarray(f_a[0]), np.asarray(f_b[0]))


def test_control_flow_program_is_ineligible():
    fluid.set_amp(False)
    main, startup, loss = _conv_model(with_while=True)
    sn = tuple(functionalizer.persistable_names(main))
    assert functionalizer.build_whole_graph_step_fn(
        main, ("img", "label"), (loss.name,), sn) is None
    # and build_step_fn silently falls back to the per-op path
    fn = functionalizer.build_step_fn(
        main, ("img", "label"), (loss.name,), sn, whole_graph_ad=True)
    assert fn is not None


def test_executor_flag_path():
    from paddle_tpu.flags import FLAGS
    fluid.set_amp(False)
    main, startup, loss = _conv_model()
    rng = np.random.RandomState(3)
    b = _batch(rng)

    def run(flag):
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            FLAGS.whole_graph_ad = flag
            try:
                out, = exe.run(main, feed=dict(b),
                               fetch_list=[loss.name])
            finally:
                FLAGS.whole_graph_ad = False
        return np.asarray(out)

    np.testing.assert_array_equal(run(False), run(True))


@pytest.mark.parametrize("amp", [False, True])
def test_remat_step_lowers_for_tpu_offchip(amp):
    """The BENCH_REMAT step must LOWER for TPU — checkable without a
    chip via cross-platform jax.export (the full ResNet-50 variant was
    validated the same way; this keeps a fast guard in the suite, in
    BOTH precisions since bench runs bf16 AMP)."""
    fluid.set_amp(amp)
    try:
        main, startup, loss = _conv_model()
        sn, state = _setup(main, startup)
        step_fn = functionalizer.build_whole_graph_step_fn(
            main, ("img", "label"), (loss.name,), sn,
            remat_policy="conv_out")
        assert step_fn is not None
        exp = functionalizer.export_step_for_tpu(
            step_fn, state,
            {"img": ((4, 8, 8, 3), np.float32),
             "label": ((4, 1), np.int64)})
        assert len(exp.mlir_module_serialized) > 0
    finally:
        fluid.set_amp(False)

def test_block_out_remat_recomputes_convs():
    """remat_policy='block_out' saves only the residual-block boundary
    tags (models/resnet.py _tag_block_out) and recomputes block
    INTERIORS — so conv ops must be duplicated into the backward (unlike
    'conv_out', which pins every conv output), while numerics stay
    exact."""
    fluid.set_amp(False)
    from paddle_tpu.models import resnet
    with fluid.unique_name.guard():
        main, startup, feeds, loss, acc, predict = resnet.get_model(
            batch_size=4, class_dim=10, depth=20, dataset="cifar10",
            lr=0.1, is_train=True, layout="NHWC")
    assert any(op.type == "remat_tag"
               for op in main.global_block().ops), "blocks must be tagged"
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        sn = tuple(functionalizer.persistable_names(main))
        state = {n: scope.get(n) for n in sn if scope.get(n) is not None}
    wg = functionalizer.build_whole_graph_step_fn(
        main, ("data", "label"), (loss.name,), sn)
    wg_blk = functionalizer.build_whole_graph_step_fn(
        main, ("data", "label"), (loss.name,), sn,
        remat_policy="block_out")
    assert wg is not None and wg_blk is not None
    rng = np.random.RandomState(3)
    b = {"data": rng.randn(4, 32, 32, 3).astype(np.float32),
         "label": rng.randint(0, 10, (4, 1)).astype(np.int64)}
    n_plain = jax.jit(wg).lower(state, b, np.uint32(0)).as_text().count(
        "stablehlo.convolution")
    n_blk = jax.jit(wg_blk).lower(state, b, np.uint32(0)).as_text().count(
        "stablehlo.convolution")
    assert n_blk > n_plain, (n_plain, n_blk)
    # recompute is exact math, but the different save-set changes XLA's
    # fusion schedule, so parity is float-rounding-tight, not bitwise
    f_a, _ = jax.jit(wg)(state, b, np.uint32(0))
    f_b, _ = jax.jit(wg_blk)(state, b, np.uint32(0))
    np.testing.assert_allclose(np.asarray(f_a[0]), np.asarray(f_b[0]),
                               rtol=1e-5, atol=1e-6)


def test_remat_tag_transparent_to_per_op_and_inference():
    """The remat_tag identity must not change per-op execution, and the
    is_train=False graph must not contain it."""
    from paddle_tpu.models import resnet
    with fluid.unique_name.guard():
        main, startup, feeds, loss, acc, predict = resnet.get_model(
            batch_size=2, class_dim=10, depth=20, dataset="cifar10",
            is_train=False, layout="NHWC")
    assert not any(op.type == "remat_tag"
                   for op in main.global_block().ops)
    with fluid.unique_name.guard():
        main_t, startup_t, _, loss_t, _, _ = resnet.get_model(
            batch_size=2, class_dim=10, depth=20, dataset="cifar10",
            is_train=True, layout="NHWC")
    scope = fluid.Scope()
    rng = np.random.RandomState(4)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup_t)
        (lv,) = exe.run(main_t, feed={
            "data": rng.randn(2, 32, 32, 3).astype(np.float32),
            "label": rng.randint(0, 10, (2, 1)).astype(np.int64)},
            fetch_list=[loss_t])
        assert np.isfinite(float(np.asarray(lv).flatten()[0]))


def test_remat_policy_typos_rejected():
    """A typo'd policy string must raise, not silently compile a
    save-nothing policy recorded under a remat label."""
    from paddle_tpu.fluid.functionalizer import _resolve_remat_policy
    for bad in ("blockout", "conv-out", "conv_out,typo", ""):
        with pytest.raises(ValueError):
            _resolve_remat_policy(bad)
    for good in ("conv_out", "block_out", "conv_out,block_out",
                 "nothing", "dots", None):
        _resolve_remat_policy(good)
