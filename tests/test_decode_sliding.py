"""A decode artifact whose attending layers are of TWO kinds (K-EXAONE's:
three that see the last `sliding_window` positions to one that sees all),
rotary on the window layers only, a bias-selected sigmoid router scaled by
2.5 beside a shared expert, through the serving path, against the plain
reference `benchmark/reference/k_exaone_236b_a23b.py` (whole [T, T] masks,
no ring, no cache), at a tiny size on the CPU.

A slot of such a session holds TWO kinds of K/V state: rows of the full
layers' tables (one a position, addressed by its length) and RINGS of the
window layers' last W rows (position p at p % W).  What these tests pin:
a prefill lands the prompt's last min(n, W) rows at their own p % W; a step
lands its row at length % W and attends under min(length + 1, W); the fused
window's in-graph lengths drive the wrap; `free` zeroes both kinds and
nothing leaks into a neighbour; the decode kernel does not care in which
order a ring's rows lie; a stack's prefill attends by blocks of queries and
equals the whole-score oracle under the window's mask; what a ring cannot
take part in is refused by a typed error that names the meta key; every
stack written before the keys opens and decodes as it did.

TOL as in test_decode_hybrid.py: both sides compute in float32 here, in
another order of operations; measured differences are about 1e-6.
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

from benchmark.reference import k_exaone_236b_a23b as reference  # noqa: E402
from paddle_tpu.inference import decode as dec  # noqa: E402
from paddle_tpu.inference.decode import (GenerativePredictor,  # noqa: E402
                                         SpeculativeDecodeSession,
                                         build_tiny_decode_model)
from paddle_tpu.obs import tracing as obs_tracing  # noqa: E402
from paddle_tpu.ops import pallas_kernels as pk  # noqa: E402
from paddle_tpu.serving import (InferenceServer,  # noqa: E402
                                ServingClient)
from tests.test_decode_ssm import (OLD_STACKS, OLD_TINY,  # noqa: E402
                                   _jaxpr)

TOL = 1e-4
W = 8                               # the window; STEP_WINDOW is 8 too
KINDS = ["window_attention", "window_attention", "window_attention",
         "attention", "window_attention"]
WINDOW_BLOCK = {
    "norm": "rmsnorm", "norm_eps": 1e-5, "position": "rope",
    "rope_theta": 1e6, "rope_layers": "window", "qk_norm": "head",
    "n_kv_heads": 2, "head_dim": 8, "layer_types": KINDS,
    "sliding_window": W, "n_dense_layers": 1, "dense_width": 48,
    "ffn": "moe_swiglu", "n_experts": 16, "experts_per_token": 4,
    "expert_width": 16, "norm_topk_prob": True, "router": "sigmoid_bias",
    "routed_scaling": 2.5, "n_shared_experts": 1}
# 4 heads of 8 under d_model 24: head_dim is not d_model // n_heads (6)
TINY = dict(vocab_size=53, d_model=24, n_heads=4, n_layers=5,
            max_seq_len=64, eos_id=0, seed=5, prefill_buckets=[8, 16, 32])


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("kexaone") / "lm")
    return build_tiny_decode_model(d, block=WINDOW_BLOCK, **TINY)


@pytest.fixture(scope="module")
def opened(artifact):
    pred = GenerativePredictor(artifact)
    return pred, {n: jnp.asarray(v) for n, v in pred._state_host.items()}


def _prompt(n, seed=1):
    return [int(t) for t in np.random.RandomState(seed).randint(
        1, TINY["vocab_size"], n)]


_REF = {}


def _ref_logits(state, seq, meta):
    """The reference's logits for `seq`, through ONE jitted program: the
    sequence padded to max_seq_len (causal: a pad moves nothing before
    it)."""
    fn = _REF.get("fn")
    if fn is None:
        model = {k: meta[k] for k in sorted(meta)}
        fn = _REF["fn"] = jax.jit(
            lambda st, t: reference.forward(st, t, model)[0])
    tokens = np.zeros(TINY["max_seq_len"], np.int32)
    tokens[:len(seq)] = seq
    return np.asarray(fn(state, jnp.asarray(tokens)))[:len(seq)]


def _copy(sess):
    return [np.array(t, copy=True) for t in sess._tables()]


def test_the_stack_holds_two_kinds_of_kv_state(opened):
    pred, _ = opened
    assert [op for op, _ in pred.layer_kinds] == KINDS
    assert [f for _, f in pred.layer_kinds] == ["dense_swiglu"] \
        + ["moe_swiglu"] * 4
    assert (pred.window_layers, pred.conv_layers, pred.ssm_layers) \
        == (4, 0, 0)
    assert pred._table_names == ("kc", "vc", "kw", "vw")
    assert pred._n_tables == 4 and len(pred._step_specs(3)) == 9
    assert pred.table_shape(3) == (1, 3, 64, 2 * 8)
    assert pred.window_table_shape(3) == dec.window_state_shape(
        pred.meta, 3) == (4, 3, W, 2 * 8)
    assert dec.slot_state_shapes(pred.meta, 3, None) \
        == ((1, 3, 64, 16), None, None)
    assert [pred._table_layer(i, "ring") for i in range(5)] \
        == [0, 1, 2, 3, 3]
    assert [pred._table_layer(i) for i in range(5)] == [0, 0, 0, 0, 1]
    assert pred.window_kv_bytes(3) == 2 * 4 * 3 * W * 16 * 4
    assert pred.kv_cache_bytes(3) == 2 * 1 * 3 * 64 * 16 * 4 \
        + pred.window_kv_bytes(3)
    sess = pred.new_session(3)
    assert sess.cache_bytes() == pred.kv_cache_bytes(3)
    assert sess.window_kv_bytes() == pred.window_kv_bytes(3)
    assert sess.kv_live_bytes() == {"full": 0, "window": 0}


def test_the_resource_report_prices_the_rings(artifact, opened):
    from paddle_tpu.analysis.resources import analyze_artifact
    pred, _ = opened
    rep = analyze_artifact(artifact, decode_slots=3)
    assert rep.kv_cache_bytes == pred.kv_cache_bytes(3)


@pytest.mark.parametrize("n", [3, W - 1, W, W + 3, 2 * W, 27])
def test_prefill_then_decode_through_both_kinds_of_table(opened, n):
    """Prefill, then teacher-forced decode steps through the full table and
    the rings, against the reference's full forward, by LOGITS: prompts
    under the window, at its edge, past it and past several wraps; the
    steps cross the edge and wrap the ring (W = 8, up to 20 steps)."""
    pred, state = opened
    prompt = _prompt(n, seed=n)
    sess = pred.new_session(2)
    seq = prompt + [sess.prefill(1, prompt)]
    got = []
    for _ in range(20):
        toks, logits = sess.decode_logits()
        got.append(logits[1])
        seq.append(int(toks[1]))
    want = _ref_logits(state, seq, pred.meta)
    assert seq[n] == int(np.argmax(want[n - 1]))
    for t, row in enumerate(got):
        np.testing.assert_allclose(row, want[n + t], rtol=0, atol=TOL)
    # the ring holds the last W positions' rows, each at its p % W
    length = int(sess.lengths[1])
    assert length == n + 20
    held = sess.kv_live_bytes()
    assert held == {"full": 2 * length * 1 * 16 * 4,
                    "window": 2 * W * 4 * 16 * 4}


@pytest.mark.parametrize("n", [1, 5, W, W + 1, 13, 2 * W, 23])
def test_a_prefill_lands_the_prompts_last_rows_at_their_own_places(opened,
                                                                   n):
    """Ring row r after a prompt of n tokens is the row of the last position
    p < n with p % W == r, zeros where there is none, whatever the bucket's
    pads hold; the full table holds rows 0 .. n - 1 and zeros past them."""
    pred, state = opened
    bucket = pred.prompt_bucket(n)
    padded = np.full((1, bucket), 7, np.int32)          # pads that are tokens
    padded[0, :n] = _prompt(n, seed=n)
    out = pred._prefill_math(state, jnp.asarray(padded), jnp.int32(n))
    _, kc, vc, kw, vw = (np.asarray(t) for t in out)
    assert kc.shape == (1, 1, bucket, 16) and kw.shape == (4, 1, W, 16)
    assert not kc[:, :, n:].any() and kc[:, :, :n].any()
    # the window layers' own rows, from the layer-by-layer seam
    ks = []

    def spy(q, k, v, scale, window=0):
        if window:
            ks.append(np.asarray(k))
        return real(q, k, v, scale, window)
    real, dec._blocked_attention = dec._blocked_attention, spy
    try:
        pred._prefill_layers(state, jnp.asarray(padded), jnp.int32(n))
    finally:
        dec._blocked_attention = real
    assert len(ks) == 4
    for layer, k in enumerate(ks):
        for r in range(W):
            ps = [p for p in range(n) if p % W == r]
            want = k[0, ps[-1]].reshape(-1) if ps else np.zeros(16)
            np.testing.assert_array_equal(kw[layer, 0, r], want)


def test_blocked_attention_is_the_oracle_under_the_windows_mask(monkeypatch):
    """`_blocked_attention` in several blocks of queries (a last block that
    is padded among them) against `_causal_attention`'s whole scores with
    the window's mask added; grouped-query heads read their K/V head."""
    rng = np.random.RandomState(3)
    B, H, Hc, Dh = 22, 4, 2, 8
    q = jnp.asarray(rng.randn(1, B, H, Dh), jnp.float32)
    k = jnp.asarray(rng.randn(1, B, Hc, Dh), jnp.float32)
    v = jnp.asarray(rng.randn(1, B, Hc, Dh), jnp.float32)
    scale = 1.0 / np.sqrt(Dh)
    kr, vr = (jnp.repeat(t, H // Hc, axis=2) for t in (k, v))
    full = np.asarray(dec._causal_attention(q, kr, vr, scale))

    def oracle(window):
        s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.asarray(kr)) \
            * scale
        t = np.arange(B)
        mask = (t[None] <= t[:, None]) & (t[None] > t[:, None] - window)
        s = np.where(mask[None, None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        return np.einsum("bhqk,bkhd->bqhd", p, np.asarray(vr))

    for block in (512, 8, 5):
        monkeypatch.setattr(dec, "PREFILL_QUERY_BLOCK", block)
        np.testing.assert_allclose(
            np.asarray(dec._blocked_attention(q, k, v, scale)), full,
            rtol=0, atol=1e-5)
        for window in (1, 3, W, B, B + 4):
            np.testing.assert_allclose(
                np.asarray(dec._blocked_attention(q, k, v, scale,
                                                  window=window)),
                oracle(window), rtol=0, atol=1e-5)


def test_a_prefill_in_blocks_is_the_prefill_in_one(opened, monkeypatch):
    pred, state = opened
    padded = np.zeros((1, 32), np.int32)
    padded[0, :29] = _prompt(29, 4)
    one = pred._prefill_math(state, jnp.asarray(padded), jnp.int32(29))
    monkeypatch.setattr(dec, "PREFILL_QUERY_BLOCK", 8)
    many = pred._prefill_math(state, jnp.asarray(padded), jnp.int32(29))
    assert int(np.asarray(one[0])[0]) == int(np.asarray(many[0])[0])
    for a, b in zip(one[1:], many[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-5)


def test_the_decode_kernel_does_not_care_how_a_rings_rows_lie():
    """Rows carry their own rotation and a softmax does not care in which
    order its keys lie: the kernel over a ring whose rows are PERMUTED gives
    what it gives over the ring, so it runs over a ring unchanged."""
    rng = np.random.RandomState(0)
    N, H, Hc, Dh, ring = 3, 4, 2, 8, 16
    q = jnp.asarray(rng.randn(N, H, Dh), jnp.float32)
    k = rng.randn(2, N, ring, Hc * Dh).astype(np.float32)
    v = rng.randn(2, N, ring, Hc * Dh).astype(np.float32)
    full = jnp.full((N,), ring, jnp.int32)
    want = np.asarray(pk.decode_attention(q, jnp.asarray(k), jnp.asarray(v),
                                          full, layer=1))
    for shift in (1, 5, 11):
        perm = np.roll(np.arange(ring), shift)
        got = pk.decode_attention(q, jnp.asarray(k[:, :, perm]),
                                  jnp.asarray(v[:, :, perm]), full, layer=1)
        np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-5)
    rev = np.arange(ring)[::-1]
    got = pk.decode_attention(q, jnp.asarray(k[:, :, rev]),
                              jnp.asarray(v[:, :, rev]), full, layer=1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-5)


def test_rows_past_the_clamp_are_never_attended(opened):
    """A ring that has not wrapped is read under min(length + 1, W) rows:
    whatever lies in the rows past them (a last owner's, had `free` not
    zeroed them) moves no logit."""
    pred, _ = opened
    a, b = pred.new_session(1), pred.new_session(1)
    prompt = _prompt(3, 2)
    for s in (a, b):
        s.prefill(0, prompt)
    # stale rows in the ring's rows 5 .. W - 1 (positions 3, 4 come next)
    b._kw = b._kw.at[:, :, 5:].set(9.0)
    b._vw = b._vw.at[:, :, 5:].set(-9.0)
    for _ in range(2):
        (_, la), (_, lb) = a.decode_logits(), b.decode_logits()
        np.testing.assert_array_equal(la, lb)


def test_a_window_across_the_wrap_is_its_one_trip_dispatches(opened):
    """The fused window's in-graph lengths drive the wrap: a window of 8
    trips that crosses W mid-way (lengths 4 and W - 1 at its launch; W = 8)
    is token for token, and table for table, eight one-trip dispatches."""
    pred, _ = opened
    a, b = pred.new_session(2), pred.new_session(2)
    for s in (a, b):
        s.prefill(0, _prompt(4, 8))
        s.prefill(1, _prompt(W - 1, 9))
    toks, counts, trips = a.decode_fused(dec.STEP_WINDOW)
    assert trips == dec.STEP_WINDOW == 8 and counts.tolist() == [8, 8]
    singles = np.stack([b.decode() for _ in range(trips)], axis=1)
    np.testing.assert_array_equal(toks[:, :trips], singles)
    for x, y in zip(a._tables(), b._tables()):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert a.lengths.tolist() == [12, W + 7]


@pytest.mark.parametrize("j", [1, 3, 7])
def test_a_slot_that_stops_mid_window_keeps_both_kinds_of_state(opened, j):
    """A slot whose budget ends at trip j of a window sits the rest out:
    its rows and its RINGS after the window are those of its own stop, its
    neighbour's unmoved by it."""
    pred, _ = opened
    win, one = pred.new_session(2), pred.new_session(2)
    for s in (win, one):
        s.prefill(0, _prompt(6, 8))
        s.prefill(1, _prompt(10, 9))
    toks, counts, trips = win.decode_fused(8, budget=[j, 8])
    assert (trips, counts.tolist()) == (8, [j, 8])
    singles = []
    for t in range(8):
        if t == j:
            one.active[0] = False
        singles.append(one.decode())
    one.active[0] = True
    singles = np.stack(singles, axis=1)
    np.testing.assert_array_equal(toks[0, :j], singles[0, :j])
    np.testing.assert_array_equal(toks[1], singles[1])
    for x, y in zip(win._tables(), one._tables()):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert win.lengths.tolist() == one.lengths.tolist() == [6 + j, 18]
    np.testing.assert_array_equal(win.decode_fused(3)[0],
                                  one.decode_fused(3)[0])


def test_slots_are_independent_and_free_zeroes_both_kinds(opened):
    """A stream beside two others is, bit for bit, the stream alone (past
    several wraps); an inactive slot's rows and rings stay as they were
    through a window; `free` zeroes both kinds and the slot's next stream
    is the one a fresh session gives."""
    pred, _ = opened
    p0, p1, p2 = _prompt(5, 1), _prompt(11, 2), _prompt(3, 3)
    alone = pred.new_session(1)
    want = [alone.prefill(0, p1)]
    for _ in range(3):
        toks, counts, trips = alone.decode_fused(8)
        want += [int(t) for t in toks[0, :counts[0]]]
    sess = pred.new_session(3)
    sess.prefill(0, p0)
    got = [sess.prefill(1, p1)]
    sess.prefill(2, p2)
    sess.active[2] = False                   # holds state, does not run
    held = [t[:, 2] for t in _copy(sess)]
    for i in range(3):
        toks, counts, trips = sess.decode_fused(8)
        assert counts[2] == 0
        got += [int(t) for t in toks[1, :counts[1]]]
        if i == 0:
            sess.free(0)                     # a neighbour leaves
            assert sess.slot_is_zero(0)
            assert not sess.slot_is_zero(1)
    assert got == want
    for before, t in zip(held, sess._tables()):
        np.testing.assert_array_equal(before, np.asarray(t)[:, 2])
        assert before.any()
    # slot_is_zero reads the rings too
    sess._kc = sess._kc.at[:, 2].set(0.0)
    sess._vc = sess._vc.at[:, 2].set(0.0)
    assert not sess.slot_is_zero(2)
    sess.free(2)
    sess.free(1)
    assert all(sess.slot_is_zero(i) for i in range(3))
    assert not any(np.asarray(t).any() for t in sess._tables())
    again = [sess.prefill(1, p1)]
    for _ in range(3):
        toks, counts, trips = sess.decode_fused(8)
        again += [int(t) for t in toks[1, :counts[1]]]
    assert again == want


def test_a_full_slot_lands_nothing_in_its_ring(opened):
    """A slot at max_seq_len writes no full row (it has none left) and no
    ring row either: the ring keeps the rows its last W positions left."""
    pred, _ = opened
    sess = pred.new_session(1)
    sess.prefill(0, _prompt(30, 6))
    sess.lengths[0] = pred.max_seq_len
    before = _copy(sess)
    sess.decode_logits()
    for x, y in zip(before, sess._tables()):
        np.testing.assert_array_equal(x, np.asarray(y))


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
def test_what_a_ring_cannot_do_is_refused_by_name(opened, artifact, what):
    pred, _ = opened
    with pytest.raises(NotImplementedError,
                       match="ring of K/V rows.*layer_types"):
        REFUSALS[what](pred, artifact)


@pytest.mark.parametrize("tp", [False, True])
def test_a_mesh_refuses_by_name(artifact, tp):
    from paddle_tpu.flags import FLAGS, set_flags
    from paddle_tpu.parallel.mesh import MeshGroup
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs two devices")
    was = FLAGS.mesh_tp
    set_flags({"mesh_tp": tp})
    try:
        with pytest.raises(NotImplementedError, match="layer_types"):
            GenerativePredictor(artifact, device=MeshGroup(devs[:2]))
    finally:
        set_flags({"mesh_tp": was})


@pytest.mark.parametrize("key,value,match", [
    ("sliding_window", 0, "sliding_window"),
    ("sliding_window", -3, "sliding_window"),
    ("layer_types", ["attention"] * 5, "sliding_window"),
    ("layer_types", ["window_attention"] * 5, "attention layer among"),
    ("layer_types", ["window_attention", "conv", "conv", "conv", "conv"],
     "attention layer among"),
    ("layer_types", ["sliding_attention"] + KINDS[1:], "layer_types"),
    ("rope_layers", "full", "rope_layers"),
    ("position", "learned", "rope_layers"),
    ("routed_scaling", 2.5, None),
])
def test_a_stack_this_module_has_no_math_for_is_a_typed_error(key, value,
                                                              match):
    meta = dict(WINDOW_BLOCK, vocab_size=53, d_model=24, n_heads=4,
                n_layers=5, max_seq_len=64, **{key: value})
    if "conv" in meta["layer_types"]:
        meta["conv_kernel"] = 3
    if match is None:
        assert dec.block_of(meta)[key] == value
        return
    with pytest.raises(ValueError, match=match):
        dec.block_of(meta)


def test_window_keys_without_the_layers_are_refused():
    meta = dict(vocab_size=53, d_model=24, n_heads=4, n_layers=2,
                max_seq_len=32)
    with pytest.raises(ValueError, match="sliding_window"):
        dec.block_of(dict(meta, sliding_window=8))
    with pytest.raises(ValueError, match="rope_layers"):
        dec.block_of(dict(meta, position="rope", rope_layers="window"))
    mla = dict(meta, norm="rmsnorm", position="rope",
               layer_types=["mla", "mla"], q_lora_rank=24, kv_lora_rank=16,
               qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
    dec.block_of(mla)
    with pytest.raises(ValueError, match="sliding_window"):
        dec.block_of(dict(mla, sliding_window=8))
    with pytest.raises(ValueError, match="window_attention"):
        dec.block_of(dict(mla, sliding_window=8,
                          layer_types=["mla", "window_attention"]))
    # routed_scaling still goes with a sigmoid router alone
    with pytest.raises(ValueError, match="routed_scaling"):
        dec.block_of(dict(meta, ffn="moe_swiglu", n_experts=8,
                          experts_per_token=2, expert_width=16,
                          routed_scaling=2.5))


def test_the_full_layers_see_no_position_and_the_window_layers_do(opened):
    """Under rope_layers=window a full layer's q and k are unrotated: the
    same stack with rope_layers=all (every attending layer rotated) is
    another function, and so is the stack with the window left out."""
    pred, state = opened
    seq = _prompt(20, 5)
    want = _ref_logits(state, seq, pred.meta)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :20] = seq
    x, _, _ = pred._prefill_layers(state, jnp.asarray(padded), jnp.int32(20))
    got = np.asarray(pred._head(state, x, dec._OFF_MESH))[0, :20]
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    for other in ({"rope_layers": "all"},):
        twin = GenerativePredictor(None, _clone_of=pred)
        twin._block_meta = dict(pred._block_meta, **other)
        x, _, _ = twin._prefill_layers(state, jnp.asarray(padded),
                                       jnp.int32(20))
        moved = np.asarray(twin._head(state, x, dec._OFF_MESH))[0, :20]
        assert np.abs(moved - want).max() > 100 * TOL


def test_the_router_selects_by_bias_weighs_without_it_and_scales(opened):
    """`moe_ffn` under router=sigmoid_bias with routed_scaling 2.5 against
    the reference's `ffn_parts`, whole and as a member's share."""
    pred, state = opened
    rng = np.random.RandomState(1)
    g = jnp.asarray(rng.randn(9, 24), jnp.float32)
    w = {n: state["l2_" + n] for n in reference.ROUTED_WEIGHTS}
    model = dict(pred.meta)
    routed, _, _ = reference.ffn_parts(g, w, model)
    for scaling in (2.5, 1.0):
        got, _ = dec.moe_ffn(g, w["router"], w["w_gate"], w["w_up"],
                             w["w_down"], 4, True,
                             expert_bias=w["expert_bias"], scaling=scaling)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(routed) * scaling / 2.5,
                                   rtol=0, atol=TOL)
    # the bias SELECTS: without it other experts are kept somewhere
    plain, _ = dec.moe_ffn(g, w["router"], w["w_gate"], w["w_up"],
                           w["w_down"], 4, True, sigmoid=True, scaling=2.5)
    assert np.abs(np.asarray(plain) - np.asarray(routed)).max() > 100 * TOL


@pytest.mark.parametrize("count", [4, 8, 16], ids=[
    "four_members", "two_members", "one_member"])
def test_the_members_shares_add_up_to_the_uncut_layer(opened, count):
    """THE SHARE TEST (the `model-configs` guide, section 4): the routed
    parts of the members that hold `count` of the 16 experts each (four of
    4; two of 8, a half, where `moe_ffn`'s held branch takes every pair's
    row; one of all 16), with what every member computes alike (the shared
    expert) counted ONCE, add up to the uncut layer of the reference, with
    this router (bias-selected, scaled 2.5); and the program's member
    computes its own part."""
    pred, state = opened
    rng = np.random.RandomState(2)
    g = jnp.asarray(rng.randn(11, 24), jnp.float32)
    w = {n: state["l3_" + n] for n in reference.ROUTED_WEIGHTS}
    model = dict(pred.meta)
    whole_routed, whole_shared, _ = reference.ffn_parts(g, w, model)
    total = np.zeros_like(np.asarray(whole_routed))
    for first in range(0, 16, count):
        held = dict(model, experts_held=[first, count])
        part = {n: (v[first:first + count]
                    if n in ("w_gate", "w_up", "w_down") else v)
                for n, v in w.items()}
        routed, shared, _ = reference.ffn_parts(g, part, held)
        np.testing.assert_allclose(np.asarray(shared),
                                   np.asarray(whole_shared), rtol=0,
                                   atol=1e-6)
        got, _ = dec.moe_ffn(g, part["router"], part["w_gate"], part["w_up"],
                             part["w_down"], 4, True,
                             expert_bias=part["expert_bias"], scaling=2.5,
                             held=(first, count))
        np.testing.assert_allclose(np.asarray(got), np.asarray(routed),
                                   rtol=0, atol=TOL)
        total += np.asarray(routed)
    np.testing.assert_allclose(total + np.asarray(whole_shared),
                               np.asarray(whole_routed + whole_shared),
                               rtol=0, atol=TOL)


def test_a_members_stack_runs_against_the_reference(tmp_path):
    """The configuration's shape of cut: experts_held, bf16 at rest, the
    leading dense layer, through prefill and steps past the wrap."""
    block = dict(WINDOW_BLOCK, experts_held=[4, 4], weight_dtype="bfloat16")
    pred = GenerativePredictor(build_tiny_decode_model(
        str(tmp_path / "member"), block=block, **TINY))
    state = {n: jnp.asarray(v) for n, v in pred._state_host.items()}
    assert pred._state_host["l1_w_gate"].shape[0] == 4
    assert pred._state_host["l1_wq"].dtype.name == "bfloat16"
    assert pred._state_host["l1_router"].dtype.name == "float32"
    prompt = _prompt(13, 3)
    sess = pred.new_session(1)
    seq = prompt + [sess.prefill(0, prompt)]
    got = []
    for _ in range(12):
        toks, logits = sess.decode_logits()
        got.append(logits[0])
        seq.append(int(toks[0]))
    tokens = np.zeros(64, np.int32)
    tokens[:len(seq)] = seq
    want = np.asarray(reference.forward(state, jnp.asarray(tokens),
                                        dict(pred.meta))[0])
    for t, row in enumerate(got):
        np.testing.assert_allclose(row, want[13 + t], rtol=0, atol=TOL)


# the stacks written before this PR's keys: tests/test_decode_ssm.py's, and
# the stack that file's own PR added
OLD_STACKS = dict(OLD_STACKS, falconh1={
    "norm": "rmsnorm", "position": "rope", "n_kv_heads": 2, "head_dim": 8,
    "layer_types": ["attention+ssm"] * 3, "ffn": "swiglu",
    "dense_width": 48, "ssm_heads": 4, "ssm_head_dim": 8, "ssm_state": 16,
    "ssm_groups": 2, "ssm_conv_kernel": 4, "ssm_chunk": 4})
NEW_KEYS = {"sliding_window": 0, "rope_layers": "all"}
# the K/V tables, then what the stack keeps beside them
OLD_TABLES = {"gpt2": ("kc", "vc"), "olmoe": ("kc", "vc"),
              "lfm2": ("kc", "vc", "cs"), "pangu": ("kc",),
              "falconh1": ("kc", "vc", "cs", "ss")}


@pytest.fixture(scope="module", params=sorted(OLD_STACKS))
def old_stack(request, tmp_path_factory):
    d = tmp_path_factory.mktemp("old_" + request.param)
    old = GenerativePredictor(build_tiny_decode_model(
        str(d / "old"), block=OLD_STACKS[request.param], **OLD_TINY))
    # the same weights under a meta that SPELLS every new key at its default
    spelled = GenerativePredictor(dec.save_decode_model(
        str(d / "new"), old._state_host, dict(old.meta, **NEW_KEYS)))
    return request.param, old, spelled


def test_an_artifact_written_before_the_keys_opens_unchanged(old_stack):
    """An artifact that names none of this PR's keys is the block it was:
    every new key defaulted, its step and its prefill the programs of the
    same artifact with the keys SPELLED at their defaults, its slot state
    the tables it had (no ring), its `cache_bytes` what the closed form
    says, and its stream the same tokens."""
    name, old, spelled = old_stack
    assert all(old.block[k] == dict(dec.BLOCK_DEFAULTS)[k]
               for k in NEW_KEYS)
    assert old.window_layers == 0 and old.window_table_shape(2) is None
    assert old.window_kv_bytes(2) == 0
    assert old._table_names == spelled._table_names == OLD_TABLES[name]
    sess = old.new_session(2)
    assert sess._kw is None and sess._vw is None
    assert sess.window_kv_bytes() == 0
    assert len(sess._tables()) == old._n_tables == len(OLD_TABLES[name])
    assert sess.cache_bytes() == old.kv_cache_bytes(2) == (
        (1 if old.latent else 2) * 4 * int(np.prod(old.table_shape(2)))
        + old.ssm_state_bytes(2))
    assert "full_layers" not in sess._stack_attrs
    assert _jaxpr(old, old._step_math(), old._step_specs(2)) \
        == _jaxpr(spelled, spelled._step_math(), spelled._step_specs(2))
    prompt = [int(t) for t in np.random.RandomState(2).randint(1, 97, 11)]
    assert dec.greedy_decode(old, prompt, 12)[0] \
        == dec.greedy_decode(spelled, prompt, 12)[0]


def test_a_stack_without_window_layers_keeps_its_prefill(old_stack, opened):
    """Its prefill attends through `_causal_attention`'s whole scores as it
    did: nothing of it runs in blocks of queries (no loop but a scanning
    stack's own scan), no new scope names anything in it; the window stack's
    runs in blocks under its two scopes."""
    name, old, _ = old_stack
    specs = (jax.ShapeDtypeStruct((1, 16), np.int32),
             jax.ShapeDtypeStruct((), np.int32))
    called = []
    real = dec._blocked_attention
    dec._blocked_attention = lambda *a, **k: called.append(1) or real(*a,
                                                                      **k)
    try:
        text = _jaxpr(old, old._prefill_math, specs)
        assert not called
        if name != "falconh1":
            assert "while" not in text
        pred, _ = opened
        _jaxpr(pred, pred._prefill_math, specs)
        assert len(called) == 5
    finally:
        dec._blocked_attention = real
    spec = {n: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
            for n, v in old._state_host.items()}
    for math, args in ((old._prefill_math, specs),
                       (old._step_math(), old._step_specs(2))):
        lowered = jax.jit(math).lower(spec, *args).as_text(debug_info=True)
        assert "window_attention" not in lowered
        assert "full_attention" not in lowered


def test_spans_say_what_both_kinds_of_table_reserve_and_hold(opened):
    pred, _ = opened
    sess = pred.new_session(2)
    was = obs_tracing.enabled()
    obs_tracing.set_enabled(True)
    try:
        obs_tracing.clear()
        sess.prefill(0, _prompt(6))
        sess.prefill(1, _prompt(13))
        sess.decode_fused(3)
        spans = obs_tracing.recent_spans()
    finally:
        obs_tracing.set_enabled(was)
    fetches = [s["attrs"] for s in spans if s["name"] == "decode/fetch"]
    assert [a["phase"] for a in fetches] == ["prefill", "prefill", "step"]
    row = 16 * 4
    for a in fetches:
        assert (a["full_layers"], a["window_layers"]) == (1, 4)
        assert a["full_kv_bytes"] == 2 * 1 * 2 * 64 * row
        assert a["window_kv_bytes"] == sess.window_kv_bytes() \
            == 2 * 4 * 2 * W * row
    # what the active slots held as each call began
    assert [(a["full_kv_live_bytes"], a["window_kv_live_bytes"])
            for a in fetches] == [
        (0, 0), (2 * 6 * row, 2 * 6 * 4 * row),
        (2 * 19 * row, 2 * (6 + W) * 4 * row)]
    # the K/V stream's counter counts the rings' calls too: a ring is one
    # block (W = 8 rows), a full row 64 / 8 = 8 blocks at that edge
    step = fetches[2]
    assert step["trips"] == 3
    if sess._kv_block and sess._ring_block:
        per_full = 64 // sess._kv_block
        assert step["kv_blocks_total"] == 3 * 2 * (1 * per_full
                                                   + 4 * (W // sess._ring_block))


def test_device_scopes_name_the_two_kinds_of_attention(opened):
    """`window_attention` and `full_attention` in the step and in a prefill,
    beside `moe_ffn`, `shared_expert` and `dense_ffn`: the scopes the
    benchmark's readers find the operations by."""
    pred, _ = opened
    spec = {n: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
            for n, v in pred._state_host.items()}
    step = jax.jit(pred._step_math()).lower(
        spec, *pred._step_specs(2)).as_text(debug_info=True)
    prefill = jax.jit(pred._prefill_math).lower(
        spec, jax.ShapeDtypeStruct((1, 16), np.int32),
        jax.ShapeDtypeStruct((), np.int32)).as_text(debug_info=True)
    for text in (step, prefill):
        for scope in ("window_attention", "full_attention", "moe_ffn",
                      "shared_expert", "dense_ffn"):
            assert scope in text, scope


def test_served_through_the_wire_with_the_default_placement(artifact,
                                                            opened):
    """registry.load_model -> DecodeBatcher -> the wire, no flag: three
    streams over two slots, joining and leaving, each past the window's
    wrap, each the stream of a session of its own; the stats count the
    rings with the cache."""
    import threading
    pred, _ = opened
    server = InferenceServer().start()
    boot = ServingClient(server.endpoint)
    prompts = [_prompt(5), _prompt(13, seed=9), _prompt(2, seed=4)]
    outs, errs = [None] * 3, []
    try:
        boot.load_model("kexaone", artifact, decode_slots=2)

        def worker(i):
            cli = ServingClient(server.endpoint)
            try:
                outs[i] = [t for c in cli.infer_stream(
                    "kexaone", prompts[i], max_new_tokens=18 + i,
                    deadline_ms=60000.0) for t in c]
            except Exception as e:                       # noqa: BLE001
                errs.append(e)
            finally:
                cli.close()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errs, errs
        for i, (p, out) in enumerate(zip(prompts, outs)):
            want, _ = dec.greedy_decode(pred, p, 18 + i)
            assert [int(t) for t in out] == want
        stats = boot.stats()["stats"]["models"]["kexaone"]
        assert stats["kv_cache_bytes"] == pred.kv_cache_bytes(2)
    finally:
        boot.close()
        server.shutdown(drain=True)
