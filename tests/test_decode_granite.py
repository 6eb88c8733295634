"""A decode artifact in which a Mamba-2 state-space mixer is a layer's ONLY
operator (`layer_types` "ssm": Granite-4.0-H's nine layers of ten), an
attention layer among them sees NO position signal (`position: "none"`) and
scales its scores by a number of its own (`attention_multiplier`), a routed
FFN with a shared MLP stands behind every layer, and every sublayer's result
is multiplied as it joins the residual stream (`residual_multiplier`),
through the serving path's phases, against the plain reference
`benchmark/reference/granite_4_0_h_small.py` (recurrence position by
position, softmax over the top-k logits), at a tiny size on the CPU.

What these tests pin: an ssm layer keeps a conv window and a scanned state
and NO K/V rows (the `kv` table has the attention layer's one layer); the
one-prompt prefill, the group prefill, the step and its fused window compute
the reference's logits; a stream beside others is the stream alone, bit for
bit; `free` zeroes all three kinds; the three keys are refused by typed
errors where they cannot apply and move no older artifact's fingerprint; a
member's share of the routed experts (18 of 72, top-10) and its three
brothers' add up to the uncut layer; `moe_ffn` counts the pairs that stayed.

TOL as in test_decode_ssm.py: both sides compute in float32 here, in
another order of operations.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import granite_4_0_h_small as reference  # noqa: E402
from paddle_tpu.inference import decode as dec  # noqa: E402
from paddle_tpu.inference import slot_state  # noqa: E402
from paddle_tpu.inference.decode import (GenerativePredictor,  # noqa: E402
                                         SpeculativeDecodeSession)
from paddle_tpu.obs import tracing as obs_tracing  # noqa: E402

TOL = 1e-4
SEED = 2 ** 31 + 56
CHUNK = 4
# two ssm layers around one NoPE attention layer; top-3 of 6 experts, the
# member holds 1..3, a shared MLP of two expert widths; the three multipliers
META = dict(
    vocab_size=61, d_model=32, n_heads=4, n_layers=3, max_seq_len=64,
    eos_id=0, prefill_buckets=[8, 16], norm="rmsnorm", norm_eps=1e-5,
    position="none", n_kv_heads=2, head_dim=8,
    layer_types=["ssm", "attention", "ssm"], ssm_heads=4, ssm_head_dim=8,
    ssm_state=16, ssm_groups=1, ssm_conv_kernel=4, ssm_chunk=CHUNK,
    ffn="moe_swiglu", n_experts=6, experts_per_token=3, expert_width=16,
    norm_topk_prob=True, n_shared_experts=2, experts_held=[1, 3],
    head="tied", weight_dtype="bfloat16", embedding_multiplier=12.0,
    lm_head_multiplier=0.0625, attention_multiplier=0.0078125,
    residual_multiplier=0.22)


@pytest.fixture(scope="module")
def state():
    return {n: np.asarray(reference.draw_tensor(n, s, SEED, None, META))
            for n, s in reference.tensor_shapes(META).items()}


@pytest.fixture(scope="module")
def artifact(tmp_path_factory, state):
    return dec.save_decode_model(
        str(tmp_path_factory.mktemp("granite") / "lm"), state, META)


@pytest.fixture(scope="module")
def pred(artifact):
    return GenerativePredictor(artifact)


def _prompt(n, seed=1):
    return [int(t) for t in np.random.RandomState(seed).randint(
        1, META["vocab_size"], n)]


_REF = {}


def _ref(state, seq):
    """The reference's (logits, states after the last position) for `seq`,
    one jitted program a length."""
    fn = _REF.get(len(seq))
    if fn is None:
        model = {k: META[k] for k in sorted(META)}
        fn = _REF[len(seq)] = jax.jit(lambda st, t: reference.forward(
            st, t, model, states=True))
    logits, _, kept = fn(state, jnp.asarray(seq, jnp.int32))
    return np.asarray(logits), {n: np.asarray(t) for n, t in kept.items()}


def _tables(sess, slot):
    return {leaf: np.array(getattr(sess, "_" + leaf), copy=True)[:, slot]
            for leaf in ("kc", "vc", "cs", "ss")}


def _holds_the_references_state(sess, slot, state, seq):
    """The slot's three kinds of state are what the reference's full
    forward over `seq` leaves."""
    _, want = _ref(state, seq)
    got = _tables(sess, slot)
    np.testing.assert_allclose(got["cs"], want["conv"], atol=TOL)
    np.testing.assert_allclose(got["ss"], want["ssm"], atol=TOL)
    # (the keys are large: their weights undo the scale of 1/128)
    np.testing.assert_allclose(got["kc"][:, :len(seq)], want["k"],
                               rtol=1e-5, atol=TOL)
    np.testing.assert_allclose(got["vc"][:, :len(seq)], want["v"], atol=TOL)
    assert not got["kc"][:, len(seq):].any()
    assert np.abs(want["ssm"]).max() > 1e-3


def test_an_ssm_layer_keeps_a_window_and_a_state_and_no_kv_rows(pred):
    blk = pred.block
    assert blk["position"] == "none" and blk["layer_types"] == (
        "ssm", "attention", "ssm")
    assert slot_state.HOLDS["ssm"] == ("conv", "ssm")
    assert pred.layer_kinds == [("ssm", "moe_swiglu"),
                                ("attention", "moe_swiglu"),
                                ("ssm", "moe_swiglu")]
    assert [(k.name, n) for k, n in pred._kinds] == [
        ("kv", 1), ("conv", 2), ("ssm", 2)]
    assert pred.table_shape(3) == (1, 3, 64, 2 * 8)
    assert pred.conv_state_shape(3) == (2, 3, 3, 32 + 2 * 16)
    assert pred.ssm_state_shape(3) == (2, 3, 4, 8, 16)
    shapes = dec.decode_state_shapes(META)
    assert shapes == reference.tensor_shapes(META)
    assert "l0_wq" not in shapes and "l1_ssm_in" not in shapes
    assert "pos" not in shapes and "lm_head" not in shapes
    assert shapes["l0_ssm_in"] == (32, 32 + 32 + 2 * 16 + 4)
    assert shapes["l2_w_gate"] == (3, 32, 16)
    assert pred._attention_scale == 0.0078125
    # a recurrent layer behind routed FFNs: the step hands out its picks
    assert pred._step_picks and pred.routed_layers == 3


@pytest.mark.parametrize("n", [1, CHUNK - 1, CHUNK + 1, 8, 13])
def test_prefill_then_decode_against_the_reference(pred, state, n):
    """A chunked scan at the prompt's bucket, then steps of the recurrence
    on the slot's state, against the reference's full forward: the slot's
    three kinds of state and the logits; a neighbour slot stays zero."""
    prompt = _prompt(n, seed=n)
    sess = pred.new_session(2)
    seq = prompt + [sess.prefill(1, prompt)]
    _holds_the_references_state(sess, 1, state, seq[:-1])
    for _ in range(4):
        toks, logits = sess.decode_logits()
        want, _ = _ref(state, seq)
        assert int(np.argmax(want[-2])) == seq[-1]
        np.testing.assert_allclose(logits[1], want[-1], atol=TOL)
        seq.append(int(toks[1]))
    _holds_the_references_state(sess, 1, state, seq[:-1])
    assert sess.last_picks.shape == (3, 2, 3)
    assert sess.slot_is_zero(0) and not sess.slot_is_zero(1)


def test_a_group_prefill_is_its_prompts_one_by_one(pred, state):
    """Three prompts of one bucket in ONE call (PR 55's group): each row's
    token, routing facts and three kinds of state are the prompt's own."""
    prompts = [_prompt(n, seed=20 + n) for n in (9, 13, 16)]
    assert pred.prefill_width(16) >= 3
    one = pred.new_session(3)
    want = [one.prefill(i, p) for i, p in enumerate(prompts)]
    group = pred.new_session(3)
    group.launch_prefill([0, 1, 2], prompts)
    assert group.fetch_prefill() == want
    assert group.last_routing.shape == (3, 3, 2)
    assert group.last_pairs_held.shape == (3, 3)
    for i, p in enumerate(prompts):
        _holds_the_references_state(group, i, state, p)
        for a, b in zip(_tables(one, i).values(),
                        _tables(group, i).values()):
            np.testing.assert_allclose(a, b, atol=TOL)


def _reference_picks(state, seq):
    """The experts the reference's routed layers use at each position of
    `seq`, [layers, positions, k] ascending, and its least gap between the
    k-th and the next logit."""
    model = {k: META[k] for k in sorted(META)}
    x = reference.embed(jnp.asarray(state["embed"], jnp.float32),
                        jnp.asarray(seq, jnp.int32), model)
    used, gaps = [], []
    for i in range(META["n_layers"]):
        w = {n: jnp.asarray(state["l%d_%s" % (i, n)])
             for n in reference.layer_names(model, i)}
        x, gap, mine, _ = reference.layer_hinted(x, w, model)
        used.append(np.asarray(mine))
        gaps.append(np.asarray(gap))
    return np.stack(used), float(np.min(gaps))


def test_a_prefill_hands_out_its_picks_at_every_position(pred, state):
    """Routed FFNs behind state-space layers: the experts each routed layer
    chose at every position of the bucket ride the fetch that brings the
    token, a prompt's own in a group; they are the reference's (no near-tie
    in these prompts) and the step's at the same positions."""
    assert pred._prefill_picks
    prompts = [_prompt(n, seed=40 + n) for n in (9, 13, 16)]
    one, ours = pred.new_session(3), []
    for i, p in enumerate(prompts):
        one.prefill(i, p)
        assert one.last_prefill_picks.shape == (3, 16, 3)
        ours.append(np.sort(one.last_prefill_picks, axis=-1))
        want, gap = _reference_picks(state, p)
        assert gap > 1e-4
        np.testing.assert_array_equal(ours[i][:, :len(p)], want)
    group = pred.new_session(3)
    group.launch_prefill([0, 1, 2], prompts)
    group.fetch_prefill()
    assert group.last_prefill_picks.shape == (3, 3, 16, 3)
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            np.sort(group.last_prefill_picks[i], axis=-1)[:, :len(p)],
            ours[i][:, :len(p)])
    # the step, teacher-forced with the prompt's own tokens
    step, p = pred.new_session(1), prompts[1]
    step.prefill(0, p[:1])
    for t in range(1, len(p)):
        step.last_tokens[0] = p[t]
        step.decode_logits()
        np.testing.assert_array_equal(
            np.sort(step.last_picks[:, 0], axis=-1), ours[1][:, t])
    # a stack that routes behind no state-space layer hands out none
    assert not GenerativePredictor._prefill_picks.fget(
        type("P", (), {"ssm_layers": 0, "routed_layers": 3}))


def test_the_fused_window_is_its_one_trip_dispatches(pred):
    a, b = pred.new_session(2), pred.new_session(2)
    for s in (a, b):
        s.prefill(0, _prompt(4, 18))
        s.prefill(1, _prompt(10, 19))
    toks, counts, trips = a.decode_fused(dec.STEP_WINDOW)
    assert list(counts) == [trips] * 2       # neither stream ended
    held = a.last_pairs_held.copy()
    singles, pairs = [], 0
    for _ in range(trips):
        singles.append(b.decode())
        pairs = pairs + b.last_pairs_held
    np.testing.assert_array_equal(toks[:, :trips], np.stack(singles, axis=1))
    np.testing.assert_array_equal(held, pairs)
    for x, y in zip(a._tables(), b._tables()):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_batched_decode_is_the_single_request_session_bit_for_bit(pred):
    """A stream beside two others is the stream alone; an inactive slot's
    state of all three kinds stays as it was through a window; `free`
    zeroes the `ssm`, `conv` and `kv` lines and the slot's next stream is
    the one a fresh session gives."""
    p0, p1, p2 = _prompt(5, 1), _prompt(9, 2), _prompt(3, 3)
    alone = pred.new_session(1)
    want = [alone.prefill(0, p1)]
    for _ in range(3):
        toks, counts, trips = alone.decode_fused(3)
        want += [int(t) for t in toks[0, :counts[0]]]
    sess = pred.new_session(3)
    sess.prefill(0, p0)
    got = [sess.prefill(1, p1)]
    sess.prefill(2, p2)
    sess.active[2] = False                   # holds state, does not run
    held = [np.array(t, copy=True)[:, 2] for t in sess._tables()]
    for i in range(3):
        toks, counts, trips = sess.decode_fused(3)
        assert counts[2] == 0
        got += [int(t) for t in toks[1, :counts[1]]]
        if i == 0:
            sess.free(0)                     # a neighbour leaves
            assert sess.slot_is_zero(0)
    assert got == want
    for before, t in zip(held, sess._tables()):
        np.testing.assert_array_equal(before, np.asarray(t)[:, 2])
        assert before.any()
    sess.free(2)
    sess.free(1)
    assert all(sess.slot_is_zero(i) for i in range(3))
    for leaf in ("kc", "vc", "cs", "ss"):
        assert not np.asarray(getattr(sess, "_" + leaf)).any(), leaf
    again = [sess.prefill(1, p1)]
    for _ in range(3):
        toks, counts, trips = sess.decode_fused(3)
        again += [int(t) for t in toks[1, :counts[1]]]
    assert again == want


REFUSALS = {
    "rollback": lambda pred, art: pred.new_session(2).rollback(0, 0),
    "verify_fn": lambda pred, art: pred.verify_fn(2, 2),
    "fused_spec_fn": lambda pred, art: pred.fused_spec_fn(pred, 2, 2),
    "speculative_session": lambda pred, art: SpeculativeDecodeSession(
        pred, pred, 2, 2),
    "int8_kv": lambda pred, art: GenerativePredictor(
        art, kv_cache_dtype="int8"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_a_recurrent_state_cannot_do_is_refused_by_name(pred, artifact,
                                                             what):
    with pytest.raises(NotImplementedError, match="layer_types"):
        REFUSALS[what](pred, artifact)


def test_a_mesh_refuses_by_name(artifact):
    from paddle_tpu.parallel.mesh import MeshGroup
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs two devices")
    with pytest.raises(NotImplementedError, match="layer_types"):
        GenerativePredictor(artifact, device=MeshGroup(devs[:2]))


MLA = dict(layer_types=["mla"] * 3, position="rope", q_lora_rank=24,
           kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
           v_head_dim=8, head_dim=0, n_kv_heads=0, attention_multiplier=0.0)
SPARSE = dict(layer_types=["sparse_attention"] * 3, position="rope",
              sparse_block=8, sparse_topk=4, sparse_init_blocks=1,
              sparse_window=8, sparse_kernel_size=4, sparse_kernel_stride=2)


@pytest.mark.parametrize("edit,match", [
    (dict(position="nope"), "position"),
    (dict(MLA, position="none"), "position"),
    (dict(rope_layers="linear"), "position"),
    (dict(rotary_dim=4), "rotary_dim"),
    (dict(attention_multiplier=-0.5), "attention_multiplier"),
    (dict(MLA, attention_multiplier=0.01), "attention_multiplier"),
    (dict(SPARSE, attention_multiplier=0.01), "attention_multiplier"),
    (dict(ffn="relu_mlp", n_shared_experts=0, experts_held=[],
          norm_topk_prob=False), "residual_multiplier"),
    (dict(layer_types=["ssm"] * 3), "attention layer"),
    (dict(layer_types=["ssm", "attention", "conv"], conv_kernel=3),
     "one width"),
    (dict(ssm_conv_kernel=1), "ssm_conv_kernel"),
    (dict(ssm_groups=3), "ssm_groups"),
    (dict(ssm_head_dim=0), "ssm_head_dim"),
])
def test_a_key_where_it_cannot_apply_is_a_typed_error(edit, match):
    with pytest.raises(ValueError, match=match):
        dec.block_of(dict(META, **edit))


def test_ssm_keys_without_a_mixer_are_refused():
    plain = dict(vocab_size=61, d_model=32, n_heads=4, n_layers=3,
                 max_seq_len=64)
    with pytest.raises(ValueError, match="attention\\+ssm\\|ssm"):
        dec.block_of(dict(plain, ssm_out_multiplier=0.5))


def test_the_new_keys_are_named_only_where_the_meta_moves_them(pred,
                                                               tmp_path):
    """A stack that names none of the three keys is fingerprinted without
    them (`_LATER_KEYS`); this one names all three.  Every stack's phases
    are at ONE rev."""
    assert {"attention_multiplier", "residual_multiplier"} \
        <= set(dec._LATER_KEYS)
    named = dict(pred._fingerprint(("step", 2), ())["block"])
    assert named["attention_multiplier"] == 0.0078125
    assert named["residual_multiplier"] == 0.22
    assert named["position"] == "none"
    assert pred._fingerprint(("step", 2), ())["rev"] == 14
    old = GenerativePredictor(dec.build_tiny_decode_model(
        str(tmp_path / "old"), n_layers=1))
    fp = old._fingerprint(("step", 2), ())
    assert fp["rev"] == 14
    assert not {"attention_multiplier", "residual_multiplier"} \
        & {k for k, _ in fp["block"]}


def test_a_multiplier_dropped_or_a_position_signal_moves_the_logits(
        state, tmp_path):
    """Each of the three keys is read: the same weights under another
    value of it give other logits."""
    prompt = _prompt(11, 5)

    def logits(**edit):
        art = dec.save_decode_model(
            str(tmp_path / ("lm_" + "_".join(sorted(edit)))), state,
            dict(META, **edit))
        sess = GenerativePredictor(art).new_session(1)
        sess.prefill(0, prompt)
        return sess.decode_logits()[1][0]
    clean = logits()
    for edit in (dict(attention_multiplier=0.0),
                 dict(residual_multiplier=1.0),
                 dict(position="rope")):
        assert np.abs(logits(**edit) - clean).max() > 100 * TOL, edit


def test_the_four_members_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST (the `model-configs` guide, section 4), at the
    model's own routing: the routed parts of the four members that hold 18
    of the 72 experts each (`experts_held` [0, 18], [18, 18], [36, 18],
    [54, 18]; top-10), with the shared MLP counted ONCE, add up to the
    uncut reference's layer; the program's member computes its own part,
    and counts the pairs that stayed with it."""
    small = dict(META, n_experts=72, experts_per_token=10,
                 experts_held=[0, 72])
    w = reference.layer_weights(small, SEED, 0)
    g = jax.random.normal(jax.random.PRNGKey(3), (12, META["d_model"]))
    with jax.default_matmul_precision("highest"):
        uncut, gap, used, _ = reference.routed_ffn(g, w, small)
        shared = reference.shared_mlp(g, w)
        total, stayed = np.zeros_like(np.asarray(uncut)), 0
        for first in range(0, 72, 18):
            mine = dict(small, experts_held=[first, 18])
            part = dict(w, **{n: w[n][first:first + 18]
                              for n in ("w_gate", "w_up", "w_down")})
            # a member draws the very run of the layer drawn whole
            np.testing.assert_array_equal(
                np.asarray(part["w_up"]), np.asarray(reference.draw_tensor(
                    "l0_w_up", (18, 32, 16), SEED, jnp.float32, mine)))
            routed, gap_i, used_i, _ = reference.routed_ffn(g, part, mine)
            np.testing.assert_array_equal(gap_i, gap)
            np.testing.assert_array_equal(used_i, used)
            got, facts = dec.moe_ffn(
                g, part["router"], part["w_gate"], part["w_up"],
                part["w_down"], 10, True, held=(first, 18))
            np.testing.assert_allclose(np.asarray(got), np.asarray(routed),
                                       rtol=0, atol=TOL)
            here = (np.asarray(used) >= first) & (np.asarray(used)
                                                  < first + 18)
            assert int(facts[2]) == int(here.sum())
            assert int(facts[0]) == len(set(np.asarray(used)[here]))
            total += np.asarray(routed)
            stayed += int(facts[2])
    assert stayed == 12 * 10
    np.testing.assert_allclose(total, np.asarray(uncut), rtol=0, atol=TOL)
    assert float(jnp.max(jnp.abs(shared))) > 1e-3


SHARES = [(72, 10, 18), (128, 8, 8), (256, 8, 8)]        # (E, k, count)
HELD_D, HELD_F, HELD_FIRST = 16, 8, 3


def _held_weights(rng, E, count, T):
    D, F = HELD_D, HELD_F
    return [jnp.asarray(rng.randn(*s), jnp.float32) for s in (
        (T, D), (D, E), (count, D, F), (count, D, F), (count, F, D))]


def _walk(jaxpr):
    """Every equation of `jaxpr` and of the jaxprs its equations hold, a
    `cond`'s branches left to the caller."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "cond":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub)


@pytest.mark.parametrize("prompts", [1, 2], ids=["one_prompt", "group"])
@pytest.mark.parametrize("T", [96, 512])
@pytest.mark.parametrize("E,k,count", SHARES)
def test_the_held_branch_works_over_the_rows_that_stay(E, k, count, T,
                                                       prompts):
    """`moe_ffn` with `held`, at a quarter, a sixteenth and a thirty-second
    of the experts, one prompt's pairs or a group's merged ones: ONE `cond`;
    its fast branch's three grouped matmuls take `held_cap` rows (twice the
    member's share in row tiles, not every pair's row: ROADMAP S6(e)), pads
    nothing, and holds one [pairs, D] gather, the one that reads the [cap, D]
    result; the other branch is the full-size form.  Granite's decode trip
    (960 pairs, a quarter held: the cap's row tile is the 64 that all 960
    get) is the one case where fewer rows would buy nothing: no `cond`, the
    grouped matmuls over every pair's row as the parent ran them."""
    h, router, *w = _held_weights(np.random.RandomState(4), E, count,
                                  T * prompts)

    def part(h):
        return dec.moe_ffn(h, router, *w, k, True,
                           held=(HELD_FIRST, count))[0]
    if prompts == 1:
        jaxpr = jax.make_jaxpr(part)(h)
    else:
        with dec._prompts_share_experts():
            jaxpr = jax.make_jaxpr(jax.vmap(part))(
                h.reshape(prompts, T, HELD_D))
    pairs, D = prompts * T * k, HELD_D
    cap = dec.held_cap(pairs, count, E)
    conds = [e for e in _walk(jaxpr.jaxpr) if e.primitive.name == "cond"]
    if (E, T, prompts) == (72, 96, 1):
        assert cap == pairs == 960 and not conds
        assert [e.invars[0].aval.shape[0] for e in _walk(jaxpr.jaxpr)
                if e.primitive.name == "ragged_dot_general"] == [pairs] * 3
        return
    assert 64 <= cap < pairs and cap % 64 == 0 and cap % 512
    assert len(conds) == 1
    assert not [e for e in _walk(jaxpr.jaxpr)
                if e.primitive.name == "ragged_dot_general"]

    def rows(branch):
        return [e.invars[0].aval.shape[0] for e in _walk(branch.jaxpr)
                if e.primitive.name == "ragged_dot_general"]
    full, fast = sorted(conds[0].params["branches"],
                        key=lambda b: -rows(b)[0])
    assert rows(full) == [pairs] * 3 and rows(fast) == [cap] * 3
    eqns = list(_walk(fast.jaxpr))
    assert not [e for e in eqns if e.primitive.name == "pad"]
    wide = [e for e in eqns if e.primitive.name == "gather"
            and e.outvars[0].aval.shape == (pairs, D)]
    assert [e.invars[0].aval.shape for e in wide] == [(cap, D)]
    # nothing is gathered FROM, or multiplied at, [pairs, .] rows
    assert not [e for e in eqns
                if e.primitive.name in ("gather", "ragged_dot_general",
                                        "dot_general")
                and e.invars[0].aval.shape[:1] == (pairs,)
                and len(e.invars[0].aval.shape) == 2]


def test_the_cap_is_twice_the_members_share_in_odd_tiles():
    """`held_cap`: m = pairs * count / E rows are expected; the cap is
    max(2 m, m + 6 sqrt(m)) rounded up to an ODD number of row tiles of 64,
    128 or 256 (the TPU's compiler tiles the rows by the largest power of
    two that divides them, `_row_tile`; the tile nearest sqrt(240 x rows an
    expert) costs the kernel's visits least), or all the pairs where that
    tile's visits cost no less than at the tile of all the pairs.  The cells'
    own shapes: Granite's decode trip (96 slots x top-10, 18 of 72) all 960
    as the parent (9 x 64 rows would be tiled by 64 like the 960), its 256
    and 512 buckets 1,408 / 2,688 of 2,560 / 5,120, its groups 5,376 of
    10,240; K-EXAONE's trip 192 of 768 (as the parent), its 4,096 bucket
    4,352 of 32,768 (the parent: 8,192); MiMo's trip 64 (the parent: 96),
    pangu's 64."""
    assert [dec._row_tile(r) for r in (960, 576, 2560, 1408, 5376, 4096)] \
        == [64, 64, 512, 128, 256, 512]
    assert dec.held_cap(960, 18, 72) == 960         # tiles of 64 either way
    assert dec.held_cap(1920, 18, 72) == 960                # 15 x 64
    assert dec.held_cap(2560, 18, 72) == 1408               # 11 x 128
    assert dec.held_cap(5120, 18, 72) == 2688               # 21 x 128
    assert dec.held_cap(10240, 18, 72) == 5376              # 21 x 256
    assert dec.held_cap(768, 8, 128) == 192                 # 3 x 64
    assert dec.held_cap(4096 * 8, 8, 128) == 4352           # 17 x 256
    assert dec.held_cap(768, 8, 256) == 64
    assert dec.held_cap(512, 8, 256) == 64
    assert dec.held_cap(40, 1, 4) == 40             # never over the pairs
    assert dec.held_cap(960, 72, 72) == 960         # a member with them all
    for pairs in range(64, 40000, 97):
        for count, of in ((18, 72), (8, 128), (8, 256), (1, 2)):
            cap = dec.held_cap(pairs, count, of)
            m = -(-pairs * count // of)
            assert cap == pairs or (
                2 * m <= cap < pairs and cap >= m + 6 * m ** 0.5
                and cap % 64 == 0 and cap % 512 != 0)


def _parent_part(h, router, w_gate, w_up, w_down, k, first, count):
    """The parent's held branch at full size (PR 56's `moe_ffn` where no
    `cond` was built): every pair's row sorted by its place here, through
    the grouped matmuls, the rows behind the groups zeroed, back to token
    order, a fixed-order sum over k.  -> ([T, D], the pairs that stayed)."""
    T = h.shape[0]
    logits = jnp.dot(h, router, precision=jax.lax.Precision.HIGHEST)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    here = (idx >= first) & (idx < first + count)
    flat = jnp.where(here, idx - first, count).reshape(T * k)
    w = jnp.where(here, w, 0.0)
    sizes = jnp.sum(flat[:, None] == jnp.arange(count)[None], axis=0,
                    dtype=jnp.int32)
    order = jnp.argsort(flat)
    rows = h[order // k]

    def grouped(x, m):
        return jax.lax.ragged_dot(x, m, group_sizes=sizes)
    out = grouped(jax.nn.silu(grouped(rows, w_gate)) * grouped(rows, w_up),
                  w_down)
    out = jnp.where((jnp.arange(T * k) < jnp.sum(sizes))[:, None], out, 0.0)
    out = out[jnp.argsort(order)].reshape(T, k, -1)
    return jnp.sum(out * w[:, :, None], axis=1), jnp.sum(sizes)


def _dense_part(h, router, w_gate, w_up, w_down, k, first, count):
    """The same part as a plain masked dense sum: every held expert's FFN
    of every token, a token's picks taken in pick order."""
    logits = jnp.dot(h, router, precision=jax.lax.Precision.HIGHEST)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(
        jnp.einsum("td,edf->etf", h, w_gate))
        * jnp.einsum("td,edf->etf", h, w_up), w_down)
    here = (idx >= first) & (idx < first + count)
    rows = y[jnp.clip(idx - first, 0, count - 1),
             jnp.arange(h.shape[0])[:, None]]
    return jnp.sum(jnp.where(here, w, 0.0)[..., None]
                   * jnp.where(here[..., None], rows, 0.0), axis=1)


def _planted(E, k, count, T, stay, rng):
    """(h, router) whose routing keeps exactly `stay` of the T * k pairs on
    the member that holds HELD_FIRST .. HELD_FIRST + count - 1: feature 0
    votes for k experts inside, feature 1 for k outside, each in a fixed
    order of preference; a token is all inside, all outside, or the one
    mixture that keeps `stay % k`."""
    inside = np.arange(HELD_FIRST, HELD_FIRST + k)
    outside = np.arange(HELD_FIRST + count, HELD_FIRST + count + k)
    router = rng.randn(HELD_D, E) * 1e-3
    router[:2] = 0.0
    router[0, inside] = router[1, outside] = 3.0 + np.arange(k, 0, -1)
    h = rng.randn(T, HELD_D)
    h[:, :2] = (0.0, 1.0)
    h[:stay // k, :2] = (1.0, 0.0)
    if stay % k:
        for c in np.linspace(0.31, 3.1, 400):       # no ties: c is no ratio
            top = np.argsort(-(np.array([1.0, c]) @ router[:2]))[:k]
            if np.isin(top, inside).sum() == stay % k:
                h[stay // k] = 0.0      # (no third feature tips a vote)
                h[stay // k, :2] = (1.0, c)
                break
        else:
            raise AssertionError("no mixture keeps %d" % (stay % k))
    return jnp.asarray(h, jnp.float32), jnp.asarray(router, jnp.float32)


@pytest.mark.parametrize("routing", ["random", "all_stay", "none_stays",
                                     "at_the_cap", "one_over_the_cap"])
@pytest.mark.parametrize("E,k,count", SHARES)
def test_the_held_part_is_the_parents_under_every_routing(E, k, count,
                                                          routing):
    """Dropless and exact whatever the router does (CPU, float32): the
    member's part is the parent's full-size form bit for bit, and the plain
    dense sum to rounding; the fourth fact is 1 exactly where more pairs
    stayed than `held_cap` rows (the full-size branch ran)."""
    T = 192         # (at 96 Granite's share builds no `cond`: 960 pairs)
    rng = np.random.RandomState(E + len(routing))
    cap = dec.held_cap(T * k, count, E)
    assert cap < T * k
    h, router, *w = _held_weights(rng, E, count, T)
    stay = {"all_stay": T * k, "none_stays": 0, "at_the_cap": cap,
            "one_over_the_cap": cap + 1}.get(routing)
    if stay is not None:
        h, router = _planted(E, k, count, T, stay, rng)
    got, facts = jax.jit(lambda h, r, *w: dec.moe_ffn(
        h, r, *w, k, True, held=(HELD_FIRST, count)))(h, router, *w)
    want, stayed = jax.jit(lambda *a: _parent_part(
        *a, k, HELD_FIRST, count))(h, router, *w)
    if stay is None:
        assert 0 < int(stayed) <= cap
    else:
        assert int(stayed) == stay
    assert int(facts[2]) == int(stayed)
    assert int(facts[3]) == int(int(stayed) > cap)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_dense_part(h, router, *w, k,
                                                HELD_FIRST, count)),
        rtol=0, atol=1e-4)
    if routing == "none_stays":
        assert not np.asarray(got).any()
    else:
        assert np.abs(np.asarray(got)).max() > 1e-3


def test_spans_carry_the_stack_and_the_pairs_that_stayed(pred):
    """A step's `decode/fetch` span of this stack: `ssm_layers` 2,
    `attn_layers` 1, the bytes of both fixed-size kinds, and
    `moe_pairs_held` beside `moe_experts_touched`."""
    sess = pred.new_session(2)
    was = obs_tracing.enabled()
    obs_tracing.set_enabled(True)
    try:
        obs_tracing.clear()
        sess.prefill(0, _prompt(6, 2))
        sess.prefill(1, _prompt(12, 3))
        sess.decode_fused(3)
        spans = obs_tracing.recent_spans()
    finally:
        obs_tracing.set_enabled(was)
    fetch = [s["attrs"] for s in spans if s["name"] == "decode/fetch"]
    step = [a for a in fetch if a["phase"] == "step"][-1]
    assert (step["ssm_layers"], step["attn_layers"],
            step["conv_layers"]) == (2, 1, 2)
    assert step["ssm_state_bytes"] == 2 * 2 * 4 * 8 * 16 * 4
    assert step["conv_state_bytes"] == 2 * 2 * 3 * 64 * 4
    assert step["moe_experts_held"] == 3
    assert step["moe_pairs_held"] == int(sess.last_pairs_held.sum())
    # 2 slots x 3 trips x 3 layers x 3 picks, those of experts 1..3
    assert 0 < step["moe_pairs_held"] <= 2 * 3 * 3 * 3
    assert step["moe_experts_touched"] <= step["moe_pairs_held"]
    pre = [a for a in fetch if a["phase"] == "prefill"][-1]
    assert pre["moe_pairs_held"] > 0
    # 6 and 24-48 pairs a call: under `held_cap`'s smallest tile, no `cond`
    assert step["moe_cap_overflows"] == pre["moe_cap_overflows"] == 0


@pytest.mark.parametrize("router", ["seeded", "planted"])
def test_a_crowded_member_counts_its_full_size_calls(tmp_path, state, router,
                                                     monkeypatch):
    """`moe_cap_overflows` on a step's and a group prefill's `decode/fetch`
    span.  The tiny stack's calls are under `held_cap`'s smallest tile, so
    the rule is rebound to three quarters of the pairs and `moe_ffn` builds
    its `cond` (40 slots x top-3 = 120 pairs a trip over 90 rows; 8 prompts
    x 16 positions x 3 = 384 over 288; the member holds experts 0..2 of 6):
    0 with the seeded router, which keeps about half the pairs here; with
    one planted so that EVERY pair stays (a zero gain in front of the
    router: all logits tie, the top-3 are experts 0, 1, 2) every routed
    layer of every trip, and of the prefill call, runs full size."""
    from paddle_tpu.flags import FLAGS, set_flags
    monkeypatch.setattr(dec, "held_cap",
                        lambda pairs, count, of: pairs * 3 // 4)
    meta = dict(META, experts_held=[0, 3])
    mine = dict(state)
    if router == "planted":
        for i in range(META["n_layers"]):
            mine["l%d_ln2_g" % i] = np.zeros_like(state["l%d_ln2_g" % i])
    # (the executable store keys a phase by the artifact and the meta, not
    # by the code: a rebound rule must neither leave nor load a phase there)
    was_store, was = FLAGS.compile_cache, obs_tracing.enabled()
    set_flags({"compile_cache": False})
    obs_tracing.set_enabled(True)
    try:
        pred = GenerativePredictor(dec.save_decode_model(
            str(tmp_path / "lm"), mine, meta))
        sess = pred.new_session(40)
        obs_tracing.clear()
        sess.launch_prefill([0, 1], [_prompt(12, 2), _prompt(15, 3)])
        sess.fetch_prefill()
        sess.decode_fused(3)
        spans = obs_tracing.recent_spans()
    finally:
        obs_tracing.set_enabled(was)
        set_flags({"compile_cache": was_store})
    fetch = [s["attrs"] for s in spans if s["name"] == "decode/fetch"]
    step = [a for a in fetch if a["phase"] == "step"][-1]
    pre = [a for a in fetch if a["phase"] == "prefill"][-1]
    assert step["trips"] == 3
    if router == "planted":
        assert step["moe_cap_overflows"] == 3 * 3      # layers x trips
        assert pre["moe_cap_overflows"] == 3           # the call's, a layer
        # every live position's three picks, in three layers (the group's
        # dead rows count a position each)
        assert pre["moe_pairs_held"] >= (12 + 15) * 3 * 3
        assert step["moe_pairs_held"] == 2 * 3 * 3 * 3
    else:
        assert step["moe_cap_overflows"] == pre["moe_cap_overflows"] == 0
        assert 0 < step["moe_pairs_held"] < 2 * 3 * 3 * 3


def test_device_scopes_name_the_new_layers_work(pred):
    """The named scopes the benchmark's readers find the step's operations
    by: `ssm_update` and `ssm_proj` (two ssm layers), `gqa_attention` (the
    one attention layer), `moe_ffn` and `shared_expert` (every layer)."""
    spec = {n: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
            for n, v in pred._state_host.items()}
    text = jax.jit(pred._step_math()).lower(
        spec, *pred._step_specs(2)).as_text(debug_info=True)
    for scope in ("ssm_update", "ssm_proj", "gqa_attention", "moe_ffn",
                  "shared_expert"):
        assert "/%s/" % scope in text or "%s/" % scope in text, scope
