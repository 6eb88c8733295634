"""The record of the kinds of slot state (`paddle_tpu/inference/slot_state.py`)
against every served stack at a tiny size on the CPU: GPT-2-, OLMoE-, LFM2-,
openPangu-, Falcon-H1- and K-EXAONE-shaped artifacts, which between them
hold every kind (K/V rows, latent rows, conv state, scanned state, K/V
rings).

What these tests pin, stack by stack: the record's leaves, in its order, are
what a prefill returns behind its first token and what the step takes and
returns; the closed-form bytes, the session's measured bytes and the
resource analysis' are one number, kind by kind; `free` zeroes every leaf of
its slot and touches no other; what a kind has no rule for is refused by a
typed error that names the meta key, exactly where the record says so and
nowhere else; a session's fetch-span attributes are the literal dicts the
benchmark's readers were written against.
"""

import os
import sys

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu.inference import slot_state  # noqa: E402
from paddle_tpu.inference.decode import (GenerativePredictor,  # noqa: E402
                                         SpeculativeDecodeSession,
                                         build_tiny_decode_model)
from tests.test_decode_sliding import (OLD_STACKS, OLD_TINY,  # noqa: E402
                                       TINY, W, WINDOW_BLOCK)

N = 2           # slots
STACKS = {name: (block, OLD_TINY) for name, block in OLD_STACKS.items()}
STACKS["kexaone"] = (WINDOW_BLOCK, TINY)
# the kinds each stack holds with the layers that hold them, and its leaves
# in the phases' order
KINDS = {"gpt2": {"kv": 3}, "olmoe": {"kv": 3},
         "lfm2": {"kv": 1, "conv": 2}, "pangu": {"latent": 3},
         "falconh1": {"kv": 3, "conv": 3, "ssm": 3},
         "kexaone": {"kv": 1, "ring": 4}}
LEAVES = {"gpt2": ("kc", "vc"), "olmoe": ("kc", "vc"),
          "lfm2": ("kc", "vc", "cs"), "pangu": ("kc",),
          "falconh1": ("kc", "vc", "cs", "ss"),
          "kexaone": ("kc", "vc", "kw", "vw")}
# a session's `_stack_attrs`, spelled out: OLD_TINY is 64 wide with 8 heads
# and 64 positions (lfm2: 2 K/V heads of 8, 3 taps; pangu: rows of 16 + 4,
# 4 experts held; falconh1: 2 K/V heads of 8, 4 taps over 32 + 2 * 2 * 16
# channels, a state of 4 x 8 x 16), kexaone 2 K/V heads of 8, 64 positions
# and a window of 8; all fp32
ATTRS = {
    "gpt2": {}, "olmoe": {},
    "lfm2": {"conv_layers": 2, "attn_layers": 1,
             "conv_state_bytes": 2 * N * 2 * 64 * 4},
    "pangu": {"mla_layers": 3, "latent_cache_bytes": 3 * N * 64 * 20 * 4,
              "moe_experts_held": 4},
    "falconh1": {"conv_layers": 3, "attn_layers": 3,
                 "conv_state_bytes": 3 * N * 3 * 96 * 4, "ssm_layers": 3,
                 "ssm_state_bytes": 3 * N * 4 * 8 * 16 * 4},
    "kexaone": {"full_layers": 1, "window_layers": 4,
                "full_kv_bytes": 2 * 1 * N * 64 * 16 * 4,
                "window_kv_bytes": 2 * 4 * N * W * 16 * 4,
                # (PR 51: a row's lanes by kind and by leaf; one geometry
                # here, so all four are 2 heads of 8)
                "full_k_lanes": 16, "full_v_lanes": 16,
                "window_k_lanes": 16, "window_v_lanes": 16},
}
# what each stack is refused, as the helpers before the record refused it
REFUSED = {"gpt2": (), "olmoe": (),
           "pangu": ("mesh", "speculative", "int8"),
           "lfm2": slot_state.CAPABILITIES,
           "falconh1": slot_state.CAPABILITIES,
           "kexaone": slot_state.CAPABILITIES}


@pytest.fixture(scope="module", params=sorted(STACKS))
def stack(request, tmp_path_factory):
    block, size = STACKS[request.param]
    art = build_tiny_decode_model(
        str(tmp_path_factory.mktemp("stack_" + request.param) / "lm"),
        block=block, **size)
    return request.param, art, GenerativePredictor(art)


def _prompt(pred, n, seed=3):
    return np.random.RandomState(seed).randint(
        1, pred.vocab_size, n).astype(np.int32)


def test_the_record_lists_the_stacks_kinds_and_leaves(stack):
    name, _, pred = stack
    assert {k.name: n for k, n in pred._kinds} == KINDS[name]
    assert pred._table_names == LEAVES[name]
    assert tuple(pred._slot_state(N)[0]) == LEAVES[name]
    assert [k.name for k, _ in pred._kinds] == [
        k.name for k in slot_state.KINDS if k.name in KINDS[name]]
    assert set(LEAVES[name]) <= set(slot_state.LEAVES)


def test_the_phases_take_and_return_the_records_leaves_in_order(stack):
    name, _, pred = stack
    leaves = pred._slot_state(N)[0]
    by_length = {leaf: kind.by_length for kind, _ in pred._kinds
                 for leaf in kind.leaves}
    bucket, n = 16, 11
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = _prompt(pred, n)
    first, *rows = pred.prefill_fn(bucket)(pred._state, padded, np.int32(n))
    assert len(rows) == len(leaves)
    for got, (leaf, (shape, dtype)) in zip(rows, leaves.items()):
        # the slot's block of the table: one slot, a bucket of positions
        # where the rows are addressed by the length, else the whole of it
        assert got.shape == (shape[0], 1, bucket if by_length[leaf]
                             else shape[2]) + shape[3:], leaf
        assert got.dtype == dtype, leaf
    specs = pred._step_specs(N)
    assert [(s.shape, s.dtype) for s in specs[:len(leaves)]] \
        == list(leaves.values())
    assert len(specs) == len(leaves) + 5
    sess = pred.new_session(N)
    assert [(t.shape, t.dtype) for t in sess._tables()] \
        == list(leaves.values())
    for leaf in slot_state.LEAVES:
        assert (getattr(sess, "_" + leaf) is None) == (leaf not in leaves)
    sess.prefill(1, padded[0, :n])
    out, *tables = pred.step_fn(N)(
        pred._state, *sess._tables(), sess.lengths, sess.last_tokens,
        sess.active, np.array([0, 1], np.int32), np.int32(1))
    assert [(t.shape, t.dtype) for t in tables] == list(leaves.values())


def test_closed_form_measured_and_analysed_bytes_agree(stack):
    from paddle_tpu.analysis.resources import _decode_report
    name, art, pred = stack
    leaves, kinds, totals = pred._slot_state(N)
    sess = pred.new_session(N)
    assert {k: sess._kind_bytes(name=k) for k in KINDS[name]} == kinds
    assert sum(kinds.values()) == sum(
        int(np.prod(shape)) * 4 for shape, _ in leaves.values())
    assert sess.cache_bytes() == pred.kv_cache_bytes(N) \
        == totals["kv_cache_bytes"] \
        == sum(v for k, v in kinds.items() if k != "conv")
    assert sess.conv_state_bytes() == pred.conv_state_bytes(N) \
        == totals["conv_state_bytes"] == kinds.get("conv", 0)
    assert sess.ssm_state_bytes() == pred.ssm_state_bytes(N) \
        == kinds.get("ssm", 0)
    assert sess.window_kv_bytes() == pred.window_kv_bytes(N) \
        == kinds.get("ring", 0)
    rep = _decode_report(art, pred.meta, N, None, name)
    assert rep.kv_cache_bytes == totals["kv_cache_bytes"]
    L, D = int(pred.meta["n_layers"]), int(pred.meta["d_model"])
    assert rep.activation_peak_bytes - N * D * 4 * (L + 2) \
        == totals["conv_state_bytes"]
    # the public reads by kind, for the callers that unpack three and one
    from paddle_tpu.inference import decode as dec
    assert dec.slot_state_shapes(pred.meta, N, None) == (
        pred.table_shape(N), pred.conv_state_shape(N),
        pred.ssm_state_shape(N))
    assert dec.window_state_shape(pred.meta, N) \
        == pred.window_table_shape(N)


def test_free_zeroes_every_leaf_of_its_slot_and_no_other(stack):
    name, _, pred = stack
    sess = pred.new_session(N)
    for slot in range(N):
        sess.prefill(slot, _prompt(pred, 9 + slot, seed=slot))
    sess.decode_fused(4)
    before = [np.array(t, copy=True) for t in sess._tables()]
    assert all(t[:, s].any() for t in before for s in range(N))
    sess.free(0)
    assert sess.slot_is_zero(0) and not sess.slot_is_zero(1)
    for leaf, was, now in zip(LEAVES[name], before, sess._tables()):
        now = np.asarray(now)
        assert not now[:, 0].any(), leaf
        assert (now[:, 1] == was[:, 1]).all(), leaf


def _ask(capability, pred, art):
    """What asks a stack for `capability`, through the product's own entry
    points."""
    if capability == "rollback":
        return pred.new_session(N).rollback(0, 0)
    if capability == "speculative":
        return SpeculativeDecodeSession(pred, pred, N, 2)
    if capability == "int8":
        return GenerativePredictor(art, kv_cache_dtype="int8")
    from paddle_tpu.parallel.mesh import MeshGroup
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs two devices")
    return GenerativePredictor(art, device=MeshGroup(devs[:2]))


@pytest.mark.parametrize("capability", slot_state.CAPABILITIES)
def test_a_kind_without_a_rule_refuses_by_name_and_no_other_does(
        stack, capability):
    name, art, pred = stack
    if capability in REFUSED[name]:
        with pytest.raises(NotImplementedError, match="layer_types"):
            _ask(capability, pred, art)
        with pytest.raises(NotImplementedError, match="layer_types"):
            pred._require("this test", capability)
    else:
        _ask(capability, pred, art)
        pred._require("this test", capability)
    # the table the record holds is the one the stacks show
    assert (capability in REFUSED[name]) == any(
        capability not in kind.rules for kind, _ in pred._kinds)


def test_the_fetch_spans_attributes_are_the_literal_dicts(stack):
    name, _, pred = stack
    assert pred.new_session(N)._stack_attrs == ATTRS[name]
