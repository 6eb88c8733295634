"""An attending layer's geometry BY KIND and BY LEAF (PR 51): window layers
with their own K/V head count beside full ones, value heads of another size
than key heads (a K row and a V row of two widths in every table), a learned
sink a head in the window layers' softmax, a rotated part of the head with a
theta a kind, a value scale.  Tiny widths on the CPU; the reference is the
benchmark's plain one (benchmark/reference/mimo_v2_flash.py), the planted
faults the cell's (benchmark/tests/test_mimov2flash_cell.py: what the chip's
calibration plants at the published widths).
"""

import hashlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.reference import mimo_v2_flash as reference  # noqa: E402
from benchmark.tests.test_mimov2flash_cell import PLANTED  # noqa: E402
from paddle_tpu.inference import decode as dec  # noqa: E402
from paddle_tpu.inference import slot_state  # noqa: E402
from paddle_tpu.ops import pallas_kernels as pk  # noqa: E402

KINDS = ["attention", "window_attention", "window_attention",
         "window_attention", "window_attention", "attention",
         "window_attention"]
# two K/V head counts (1 and 2 under 4 query heads), keys of 12 beside values
# of 8, 4 rotated lanes of 12, two thetas, a sink, a value scale, 4 of 16
# experts held: MiMo-V2-Flash's layers 0-6 in small
META = dict(
    vocab_size=97, d_model=48, n_heads=4, n_layers=7, max_seq_len=64,
    eos_id=0, prefill_buckets=[16, 32, 64], norm="rmsnorm", norm_eps=1e-5,
    position="rope", rope_theta=5e6, window_rope_theta=1e4,
    rope_layers="all", rotary_dim=4, n_kv_heads=1, window_kv_heads=2,
    head_dim=12, v_head_dim=8, value_scale=0.707, window_sink=True,
    layer_types=list(KINDS), sliding_window=8, n_dense_layers=1,
    dense_width=96, ffn="moe_swiglu", n_experts=16, experts_per_token=4,
    expert_width=32, norm_topk_prob=True, router="sigmoid_bias",
    experts_held=[4, 4], head="untied", weight_dtype="bfloat16")
SEED = 2 ** 31 + 5
PROMPTS, STEPS = [5, 13, 30], 12


@pytest.fixture(scope="module")
def state():
    return {n: np.asarray(reference.draw_tensor(n, s, SEED))
            for n, s in reference.tensor_shapes(META).items()}


@pytest.fixture(scope="module")
def artifact(state, tmp_path_factory):
    return dec.save_decode_model(
        str(tmp_path_factory.mktemp("kinds") / "lm"), state, META)


def run_program(artifact):
    """Prefill and `STEPS` decode steps through both kinds of table (rings
    of 8 rows: the longest stream wraps its rings five times); returns
    ([sequence as the cache holds it], [steps][slots, vocab] logits)."""
    pred = dec.GenerativePredictor(artifact)
    sess = pred.new_session(len(PROMPTS))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, META["vocab_size"], n, dtype=np.int32)
               for n in PROMPTS]
    seqs = [list(p) + [sess.prefill(i, p)] for i, p in enumerate(prompts)]
    got = []
    for _ in range(STEPS):
        toks, logits = sess.decode_logits()
        got.append(logits)
        for i, s in enumerate(seqs):
            s.append(int(toks[i]))
    return seqs, got


def worst_difference(state, seqs, got):
    """Largest |program's logit - reference's| over the decode steps, and
    whether every prefill's token is the reference's."""
    st = {n: jnp.asarray(v) for n, v in state.items()}
    worst, firsts = 0.0, True
    for i, s in enumerate(seqs):
        want = np.asarray(reference.forward(
            st, jnp.asarray(s[:-1], jnp.int32), META)[0])
        n = PROMPTS[i]
        firsts = firsts and int(np.argmax(want[n - 1])) == s[n]
        worst = max(worst, max(float(np.max(np.abs(got[t][i] - want[n + t])))
                               for t in range(STEPS)))
    return worst, firsts


def test_the_stack_is_described_by_kind_and_by_leaf(state):
    blk = dec.block_of(META)
    assert slot_state.attention_geometry(META, blk) == (1, 12, 8)
    assert slot_state.attention_geometry(META, blk, window=True) == (2, 12, 8)
    assert reference.tensor_shapes(META) == dec.decode_state_shapes(META)
    shapes = dec.decode_state_shapes(META)
    assert (shapes["l0_wq"], shapes["l0_wk"], shapes["l0_wv"],
            shapes["l0_wo"]) == ((48, 48), (48, 12), (48, 8), (32, 48))
    assert (shapes["l1_wk"], shapes["l1_wv"], shapes["l1_sink"]) == (
        (48, 24), (48, 16), (4,))
    assert "l0_sink" not in shapes and "l5_sink" not in shapes
    # one record a kind, a shape a leaf where K and V differ
    assert slot_state.kind_shapes(META, blk, 3, None) == {
        "kv": ((2, 3, 64, 12), (2, 3, 64, 8)),
        "ring": ((5, 3, 8, 24), (5, 3, 8, 16))}
    leaves = slot_state.slot_leaves(META, blk, 3, None)
    assert {k: v[0] for k, v in leaves.items()} == {
        "kc": (2, 3, 64, 12), "vc": (2, 3, 64, 8),
        "kw": (5, 3, 8, 24), "vw": (5, 3, 8, 16)}
    kinds, totals = slot_state.state_bytes(META, blk, 3, None)
    assert kinds == {"kv": 2 * 3 * 64 * 20 * 4, "ring": 5 * 3 * 8 * 40 * 4}
    assert totals["kv_cache_bytes"] == sum(kinds.values())
    # a stack that names none of the keys is described as it always was
    plain = {k: v for k, v in META.items() if k not in dec._LATER_KEYS
             and k != "v_head_dim"}
    assert slot_state.kind_shapes(plain, dec.block_of(plain), 3, None) == {
        "kv": (2, 3, 64, 12), "ring": (5, 3, 8, 12)}


@pytest.mark.parametrize("edit,key", [
    (dict(window_kv_heads=3), "window_kv_heads"),
    (dict(rotary_dim=5), "rotary_dim"), (dict(rotary_dim=14), "rotary_dim"),
    (dict(v_head_dim=-1), "v_head_dim"),
    (dict(window_rope_theta=-1.0), "window_rope_theta"),
    (dict(layer_types=["attention"] * 7, sliding_window=0), "window_kv_heads"),
    (dict(position="learned"), "rotary_dim")])
def test_a_geometry_the_stack_cannot_have_is_a_typed_error(edit, key):
    with pytest.raises(ValueError, match=key):
        dec.block_of(dict(META, **edit))


def test_a_mesh_and_an_int8_cache_are_refused_for_rows_of_two_widths(
        artifact):
    """Owed (ROADMAP Queue 2 part C rows 3 and 8): both are refused by name,
    a ring for its own reasons first, rows of two widths where the stack
    holds full tables alone."""
    with pytest.raises(NotImplementedError, match="a ring of K/V rows"):
        dec.GenerativePredictor(artifact, kv_cache_dtype="int8")
    pred = dec.GenerativePredictor(artifact)
    full_alone = type("P", (), {
        "_kinds": [k for k in pred._kinds if k[0].name == "kv"],
        "_block_meta": pred._block_meta})()
    for what, capability in (("a mesh placement", "mesh"),
                             ("an int8 KV cache", "int8")):
        with pytest.raises(NotImplementedError,
                           match="rows of two widths.*v_head_dim=8"):
            dec.GenerativePredictor._require(full_alone, what, capability)
    dec.GenerativePredictor._require(full_alone, "a rollback", "rollback")
    # a value head said to be a key head's size is one width, said or not
    assert dec.block_of(dict(META, v_head_dim=12))["v_head_dim"] == 0


def test_program_matches_the_reference_through_both_tables(state, artifact):
    """Prefill, then decode through the full layers' tables and the window
    layers' rings, wrapped: logits against the reference's full forward."""
    seqs, got = run_program(artifact)
    worst, firsts = worst_difference(state, seqs, got)
    assert firsts and worst < 2e-5, worst
    # the session's accounting reads the rows' own widths
    pred = dec.GenerativePredictor(artifact)
    sess = pred.new_session(3)
    assert sess.cache_bytes() == pred.kv_cache_bytes(3) \
        == (2 * 3 * 64 * 20 + 5 * 3 * 8 * 40) * 4
    assert sess.window_kv_bytes() == pred.window_kv_bytes(3) \
        == 5 * 3 * 8 * 40 * 4
    sess.prefill(0, np.arange(1, 11, dtype=np.int32))
    assert sess.kv_live_bytes() == {"full": 2 * 10 * 20 * 4,
                                    "window": 5 * 8 * 40 * 4}
    assert sess._stack_attrs == {
        "full_layers": 2, "window_layers": 5,
        "full_kv_bytes": 2 * 3 * 64 * 20 * 4,
        "window_kv_bytes": 5 * 3 * 8 * 40 * 4, "full_k_lanes": 12,
        "full_v_lanes": 8, "window_k_lanes": 24, "window_v_lanes": 16,
        "moe_experts_held": 4}
    # a ring's block weighs twice a full table's: K and V tiles at their
    # own widths (20 lanes a full row pair, 40 a ring's)
    sess._kv_block, sess._ring_block = 16, 8
    one = sess._kv_stream(np.array([1, 0, 0]), 1)
    assert one == {"kv_blocks_live": 3 * (2 * 1 + 5 * 2 * 1),
                   "kv_blocks_total": 3 * (2 * 4 + 5 * 2 * 1)}


@pytest.mark.parametrize("fault", sorted(PLANTED))
def test_a_planted_fault_moves_the_logits(state, artifact, monkeypatch,
                                          fault):
    """Each fault of the issue's list, planted in the program: far from the
    reference, where the clean program is within 2e-5."""
    from paddle_tpu.flags import FLAGS, set_flags
    PLANTED[fault](dec, monkeypatch)
    was = FLAGS.compile_cache
    set_flags({"compile_cache": False})     # a stored phase knows no plant
    try:
        seqs, got = run_program(artifact)
    finally:
        set_flags({"compile_cache": was})
    worst, _ = worst_difference(state, seqs, got)
    assert worst > 0.02, worst


@pytest.mark.parametrize("count", [4, 8, 16], ids=[
    "four_members", "two_members", "one_member"])
def test_the_shares_add_up_to_the_uncut_layer(state, count):
    """The guide's section 4: the parts of a routed layer that the members'
    shares of `count` experts give (four members of 4, two of 8, one of
    all 16) add up to the layer with all 16 held (there is no shared expert
    to count once)."""
    whole = dict(META, experts_held=[0, 16])
    i = 2                                       # a routed window layer
    w = reference.layer_weights(whole, SEED, i)
    x = jax.random.normal(jax.random.PRNGKey(3), (20, META["d_model"]))
    with jax.default_matmul_precision("highest"):
        g = reference._rms(x, w["ln2_g"], META["norm_eps"])
        uncut, gap = reference.ffn_parts(g, w, whole)
        parts = []
        for first in range(0, 16, count):
            mine = dict(w, **{n: w[n][first:first + count]
                              for n in ("w_gate", "w_up", "w_down")})
            part, gap_i = reference.ffn_parts(
                g, mine, dict(META, experts_held=[first, count]))
            parts.append(part)
            np.testing.assert_array_equal(gap_i, gap)
    np.testing.assert_allclose(sum(parts), uncut, rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(parts[-1]))) > 1e-3
    # ... and the program's member holds the run the meta names
    assert reference.tensor_shapes(META)["l2_w_gate"] == (4, 48, 32)
    held = np.asarray(reference.draw_tensor(
        "l2_w_gate", (4, 48, 32), SEED, jnp.float32))
    np.testing.assert_array_equal(
        held, np.asarray(state["l2_w_gate"].astype(np.float32)))


# --- the kernel ------------------------------------------------------------

def kernel_case(rng, dk, dv, N=3, H=8, Hc=2, S=64, L=2):
    q = jnp.asarray(rng.normal(size=(N, H, dk)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(L, N, S, Hc * dk)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, N, S, Hc * dv)), jnp.float32)
    return q, k, v, jnp.asarray([0, 17, 64], jnp.int32), jnp.asarray(
        rng.normal(size=(H,)), jnp.float32)


@pytest.mark.parametrize("dk,dv", [(24, 16), (16, 16), (8, 24)])
@pytest.mark.parametrize("sink", [False, True])
def test_decode_attention_with_rows_of_two_widths_and_a_sink(dk, dv, sink):
    q, k, v, lengths, sinks = kernel_case(np.random.default_rng(1), dk, dv)
    sinks = sinks if sink else None
    got = pk.decode_attention(q, k, v, lengths, block_kv=16, interpret=True,
                              layer=1, sinks=sinks)
    want = pk.decode_attention_reference(q, k[1], v[1], lengths, sinks=sinks)
    assert got.shape == want.shape == (3, 8, dv)
    np.testing.assert_allclose(got[1:], want[1:], rtol=1e-5, atol=1e-6)
    if sink:
        # a slot with no live row attends to its sink alone: nothing
        assert float(jnp.max(jnp.abs(got[0]))) == 0.0
        # by hand for one head: the sink is one more term of the sum
        n, h = 1, 5
        a = (k[1, n, :17].reshape(17, 2, dk)[:, h // 4] @ q[n, h]) \
            / np.sqrt(dk)
        p = jnp.exp(a) / (jnp.exp(sinks[h]) + jnp.sum(jnp.exp(a)))
        np.testing.assert_allclose(
            got[n, h], p @ v[1, n, :17].reshape(17, 2, dv)[:, h // 4],
            rtol=1e-4, atol=1e-6)
        # the head-slice entry slices the whole vector of sinks
        half = pk.decode_attention_head_slice(
            q[:, 4:], k[..., dk:], v[..., dv:], lengths, 4, 4, block_kv=16,
            interpret=True, layer=1, sinks=sinks)
        np.testing.assert_allclose(half[1:], want[1:, 4:], rtol=1e-5,
                                   atol=1e-6)
    # no block edge divides the rows: the plain-XLA fall-back, the same
    odd = pk.decode_attention(q, k[:, :, :60], v[:, :, :60],
                              jnp.minimum(lengths, 60), block_kv=16,
                              interpret=True, layer=1, sinks=sinks)
    assert odd.shape == (3, 8, dv)


# sha256 of the jaxpr of the call below as the parent commit (5ca1fba, whose
# kernel knew one row width and no sink) traced it, by jax version: the
# Mosaic lowering is a function of the jaxpr and the call's parameters
PARENT_JAXPR = {
    "0.9.0":
    "80a8630dce22019143191d7cccb0c7d82d09d609e6417a29e0b5188a3c7f8a9e"}


def test_one_geometry_and_no_sink_is_the_call_it_always_was():
    """With value heads as wide as key heads and no sink the generalised
    kernel is ONE call of the operands it always had, the very jaxpr the
    parent's kernel traced to (so its result is that one's bit for bit, and
    what Mosaic is given is what it was given), and the reference's
    result."""
    q, k, v, lengths, _ = kernel_case(np.random.default_rng(2), 16, 16)

    def call(*a):
        return pk.decode_attention(*a, block_kv=16, interpret=True, layer=0)
    jaxpr = str(jax.make_jaxpr(call)(q, k, v, lengths))
    assert jaxpr.count("pallas_call[") == 1
    if jax.__version__ in PARENT_JAXPR:
        assert hashlib.sha256(jaxpr.encode()).hexdigest() \
            == PARENT_JAXPR[jax.__version__]
    want = pk.decode_attention_reference(q, k[0], v[0], lengths)
    np.testing.assert_allclose(call(q, k, v, lengths)[1:], want[1:],
                               rtol=1e-5, atol=1e-6)
    # ... and a sink, or a V row of another width, is another call
    other = str(jax.make_jaxpr(lambda *a: pk.decode_attention(
        *a, block_kv=16, interpret=True, layer=0,
        sinks=jnp.zeros((8,))))(q, k, v, lengths))
    assert other != jaxpr and other.count("pallas_call[") == 1
