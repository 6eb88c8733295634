"""Program/Block/Operator IR tests (reference unittests/test_program.py,
test_operator_desc.py, test_variable.py)."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.framework import Program


def test_program_blocks():
    p = Program()
    assert p.num_blocks == 1
    with p._block_guard():
        assert p.current_block().idx == 1
        assert p.current_block().parent_idx == 0
    assert p.current_block().idx == 0


def test_variable_shape_dtype():
    p = Program()
    with fluid.program_guard(p):
        x = fluid.layers.data("x", shape=[3, 4], dtype="float32")
        assert x.shape == (-1, 3, 4)
        assert x.np_dtype == np.float32


def test_infer_shape_through_layers():
    main, startup = Program(), Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        assert h.shape == (-1, 16)
        img = fluid.layers.data("img", shape=[3, 32, 32], dtype="float32")
        c = fluid.layers.conv2d(img, num_filters=6, filter_size=5)
        assert c.shape == (-1, 6, 28, 28)
        pl = fluid.layers.pool2d(c, pool_size=2, pool_stride=2)
        assert pl.shape == (-1, 6, 14, 14)


def test_program_serialize_roundtrip():
    main, startup = Program(), Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        y = fluid.layers.fc(input=x, size=2)
        loss = fluid.layers.mean(y)
    s = main.serialize_to_string()
    p2 = Program.parse_from_string(s)
    assert len(p2.global_block().ops) == len(main.global_block().ops)
    assert sorted(p2.global_block().vars) == sorted(main.global_block().vars)
    # parameters keep their class
    assert len(p2.global_block().all_parameters()) == \
        len(main.global_block().all_parameters())


def test_clone_for_test_sets_is_test():
    main, startup = Program(), Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        d = fluid.layers.dropout(x, dropout_prob=0.5)
    t = main.clone(for_test=True)
    dropout_ops = [op for op in t.global_block().ops
                   if op.type == "dropout"]
    assert dropout_ops and all(op.attrs["is_test"] for op in dropout_ops)


def test_prune():
    main, startup = Program(), Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        h = fluid.layers.fc(input=x, size=8)
        y = fluid.layers.fc(input=h, size=2)
        z = fluid.layers.fc(input=h, size=3)  # dead branch for y
    pruned = main._prune(["x"], [y.name])
    types = [op.type for op in pruned.global_block().ops]
    # z's second mul should be gone
    assert len([t for t in types if t == "mul"]) == 2


def test_operator_accessors():
    main = Program()
    with fluid.program_guard(main):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        y = fluid.layers.relu(x)
        op = main.global_block().ops[-1]
        assert op.type == "relu"
        assert op.input("X") == [x.name]
        assert op.output("Out") == [y.name]


def test_place_out_of_range_raises_instead_of_wrapping():
    """TPUPlace(3) on a one-device host used to wrap onto device 0 without
    a word; a Place names a device or fails."""
    import jax
    from paddle_tpu.fluid import core
    n = len(jax.devices())
    assert core.TPUPlace(n - 1).jax_device() == jax.devices()[n - 1]
    for bad in (n, -1):
        with pytest.raises(ValueError, match="device"):
            core.TPUPlace(bad).jax_device()
    with pytest.raises(ValueError, match="cpu"):
        core.CPUPlace(len(jax.devices("cpu"))).jax_device()
