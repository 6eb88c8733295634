"""A decode artifact whose every layer runs TWO mixers on the same normed
input (grouped-query attention and a Mamba-2 state-space mixer: Falcon-H1's),
a head size that is not d_model / n_heads, a dense gated FFN in every layer
and fixed muP multipliers, through the serving path, against the plain
reference `benchmark/reference/falcon_h1_34b.py` (whose recurrence is
SEQUENTIAL, position by position), at a tiny size on the CPU.

A slot of such a session holds THREE kinds of state: rows of the K/V tables
(addressed by its length), a row of the conv-state table (the mixer's conv's
last K-1 inputs) and a row of the scanned-state table ([heads, head size,
state]: a decayed running sum over all its positions, rewritten whole by
every token).  What these tests pin: all three are written by a prefill at
the TRUE prompt end (the scan in chunks equals the sequential recurrence at
lengths that are no multiple of the chunk), advanced by a step and by every
trip of a window only where the slot runs, zeroed by `free`, and never leak
into a neighbour; what a scanned state cannot take part in is refused by a
typed error that names the meta key; every stack written before the keys
opens and decodes as it did, its first token bit-equal with the head taken
at one position.

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

from benchmark.reference import falcon_h1_34b as reference  # noqa: E402
from paddle_tpu.inference import decode as dec  # noqa: E402
from paddle_tpu.inference.decode import (GenerativePredictor,  # noqa: E402
                                         SpeculativeDecodeSession,
                                         build_tiny_decode_model)
from paddle_tpu.obs import tracing as obs_tracing  # noqa: E402
from paddle_tpu.serving import (InferenceServer,  # noqa: E402
                                ServingClient)

TOL = 1e-4
CHUNK, BUCKET = 4, 16
SSM_BLOCK = {
    "norm": "rmsnorm", "norm_eps": 1e-5, "position": "rope",
    "rope_theta": 1e11, "n_kv_heads": 2, "head_dim": 8,
    "layer_types": ["attention+ssm"] * 2, "ffn": "swiglu",
    "dense_width": 48, "ssm_heads": 4, "ssm_head_dim": 8, "ssm_state": 16,
    "ssm_groups": 2, "ssm_conv_kernel": 4, "ssm_chunk": CHUNK,
    "embedding_multiplier": 2.0, "lm_head_multiplier": 0.5,
    "attention_in_multiplier": 1.5, "key_multiplier": 0.7,
    "attention_out_multiplier": 0.8, "ssm_in_multiplier": 0.5,
    "ssm_out_multiplier": 0.9, "ssm_multipliers": [0.9, 0.8, 0.7, 1.1, 1.2],
    "mlp_multipliers": [0.6, 1.3]}
# 4 heads of 8 under d_model 24: head_dim is not d_model // n_heads (6)
TINY = dict(vocab_size=53, d_model=24, n_heads=4, n_layers=2,
            max_seq_len=32, eos_id=0, seed=5, prefill_buckets=[8, BUCKET])


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("falconh1") / "lm")
    return build_tiny_decode_model(d, block=SSM_BLOCK, **TINY)


@pytest.fixture(scope="module")
def opened(artifact):
    pred = GenerativePredictor(artifact)
    return pred, {n: jnp.asarray(v) for n, v in pred._state_host.items()}


def _prompt(n, seed=1):
    return [int(t) for t in np.random.RandomState(seed).randint(
        1, TINY["vocab_size"], n)]


def _ref(state, seq, meta):
    """The reference on exactly `seq`: (logits [T, V], conv windows
    [L, K-1, C], scanned states [L, Hs, P, N]) after its last position."""
    logits, _, windows, scanned = reference.forward(
        state, jnp.asarray(seq, jnp.int32), meta, states=True)
    return np.asarray(logits), np.asarray(windows), np.asarray(scanned)


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


def _tables(sess, slot):
    return (np.array(sess._cs, copy=True)[:, slot],
            np.array(sess._ss, copy=True)[:, slot])


def test_the_stack_holds_three_kinds_of_slot_state(opened):
    pred, _ = opened
    assert pred.layer_kinds == [("attention+ssm", "dense_swiglu")] * 2
    assert (pred.conv_layers, pred.ssm_layers, pred._n_tables) == (2, 2, 4)
    assert pred._dims() == (2, 4, 8, 24)
    assert pred.table_shape(3) == (2, 3, 32, 2 * 8)
    assert pred.conv_state_shape(3) == (2, 3, 3, 32 + 2 * 2 * 16)
    assert pred.ssm_state_shape(3) == (2, 3, 4, 8, 16)
    assert pred.ssm_state_bytes(3) == 2 * 3 * 4 * 8 * 16 * 4
    assert pred.kv_cache_bytes(3) == 2 * 2 * 3 * 32 * 16 * 4 \
        + pred.ssm_state_bytes(3)
    sess = pred.new_session(3)
    assert sess.cache_bytes() == pred.kv_cache_bytes(3)
    assert sess.ssm_state_bytes() == pred.ssm_state_bytes(3)
    assert sess.conv_state_bytes() == pred.conv_state_bytes(3) \
        == 2 * 3 * 3 * 96 * 4
    assert len(pred._step_specs(3)) == 9


def test_the_resource_report_prices_the_scanned_state(artifact, opened):
    from paddle_tpu.analysis.resources import analyze_artifact
    pred, _ = opened
    rep = analyze_artifact(artifact, decode_slots=3)
    assert rep.kv_cache_bytes == pred.kv_cache_bytes(3)


@pytest.mark.parametrize("n", [1, CHUNK - 1, CHUNK, CHUNK + 1, 7, BUCKET])
def test_prefill_then_decode_through_all_three_tables(opened, n):
    """The program (a chunked scan at the prompt's bucket, then steps of the
    recurrence on the slot's state) against the reference's full forward
    with its sequential recurrence: the slot's conv window and scanned state
    after every position, and the logits, to 1e-4; a neighbour slot stays
    zero."""
    pred, state = opened
    prompt = _prompt(n, seed=n)
    sess = pred.new_session(2)
    seq = prompt + [sess.prefill(1, prompt)]

    def tables_are_the_references():
        # what the slot holds: the state after seq[:-1]
        _, windows, scanned = _ref(state, seq[:-1], pred.meta)
        got_w, got_s = _tables(sess, 1)
        np.testing.assert_allclose(got_w, windows, atol=TOL)
        np.testing.assert_allclose(got_s, scanned, atol=TOL)
        assert np.abs(scanned).max() > 1e-3         # a state worth holding

    tables_are_the_references()                     # after the prefill
    for _ in range(5):
        toks, logits = sess.decode_logits()
        want = _ref_logits(state, seq, pred.meta)
        assert int(np.argmax(want[-2])) == seq[-1]
        np.testing.assert_allclose(logits[1], want[-1], atol=TOL)
        seq.append(int(toks[1]))
    tables_are_the_references()                     # after five steps
    assert sess.slot_is_zero(0) and not sess.slot_is_zero(1)


@pytest.mark.parametrize("n", [1, CHUNK - 1, CHUNK, CHUNK + 1, 11, BUCKET])
def test_the_chunked_scan_is_the_sequential_recurrence(n):
    """`ssd_chunked_scan` against the recurrence position by position, at
    lengths that are and are not multiples of the chunk, from a state that
    is not zero; positions with dt = 0 (a bucket's pads) move nothing."""
    rng = np.random.RandomState(n)
    Hs, P, G, N = 4, 8, 2, 16
    xs, Bm, Cm = (rng.randn(*s).astype(np.float32) for s in (
        (n, Hs, P), (n, G, N), (n, G, N)))
    dt = np.abs(rng.randn(n, Hs)).astype(np.float32)
    A = -np.exp(rng.randn(Hs)).astype(np.float32)
    S = rng.randn(Hs, P, N).astype(np.float32)
    want_y, want_S = [], S.copy()
    for t in range(n):
        Bh, Ch = (np.repeat(m[t], Hs // G, axis=0) for m in (Bm, Cm))
        want_S = np.exp(dt[t] * A)[:, None, None] * want_S \
            + (dt[t][:, None] * xs[t])[:, :, None] * Bh[:, None, :]
        want_y.append((want_S * Ch[:, None, :]).sum(-1))
    y, after = dec.ssd_chunked_scan(*(jnp.asarray(a) for a in (
        xs, Bm, Cm, dt, A)), CHUNK, state=jnp.asarray(S))
    np.testing.assert_allclose(np.asarray(y), np.stack(want_y), atol=TOL)
    np.testing.assert_allclose(np.asarray(after), want_S, atol=TOL)
    # the same run padded to the bucket with dt = 0: the same state, to
    # the bit (decay 1, input 0)
    pad = [(0, BUCKET - n)]
    y2, after2 = dec.ssd_chunked_scan(*(jnp.asarray(np.pad(
        a, pad + [(0, 0)] * (a.ndim - 1), constant_values=c))
        for a, c in ((xs, 7.0), (Bm, 7.0), (Cm, 7.0), (dt, 0.0))),
        jnp.asarray(A), CHUNK, state=jnp.asarray(S))
    np.testing.assert_allclose(np.asarray(after2), want_S, atol=TOL)
    np.testing.assert_allclose(np.asarray(y2)[:n], np.stack(want_y),
                               atol=TOL)


def test_pad_positions_leave_state_and_window_untouched(opened):
    """One prompt through both buckets (8 and 16): the pads of the longer
    bucket move neither the conv window nor the scanned state, and whatever
    tokens stand in the pads change nothing."""
    pred, state = opened
    prompt = _prompt(6, seed=3)
    fn8, fn16 = pred.prefill_fn(8), pred.prefill_fn(16)

    def run(fn, bucket, fill):
        padded = np.full((1, bucket), fill, np.int32)
        padded[0, :6] = prompt
        out = fn(pred._state, padded, np.int32(6))
        return [np.asarray(o) for o in out]
    a, b, c = run(fn8, 8, 0), run(fn16, 16, 0), run(fn16, 16, 9)
    assert a[0] == b[0] == c[0]
    _, windows, scanned = _ref(state, prompt, pred.meta)
    for got in (a, b, c):
        np.testing.assert_allclose(got[3][:, 0], windows, atol=TOL)
        np.testing.assert_allclose(got[4][:, 0], scanned, atol=TOL)
        assert not got[1][:, :, 6:].any() and not got[2][:, :, 6:].any()
    for x, y in zip(b, c):
        np.testing.assert_array_equal(x, y)


def test_slots_are_independent_and_free_zeroes_all_three_tables(opened):
    """A stream beside two others is, bit for bit, the stream alone; an
    inactive slot's state of all three kinds stays as it was through a
    window; `free` zeroes all three and the slot's next stream is the one a
    fresh session gives."""
    pred, _ = opened
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
    assert not any(np.asarray(t).any() for t in sess._tables())
    again = [sess.prefill(1, p1)]
    for _ in range(3):
        toks, counts, trips = sess.decode_fused(3)
        again += [int(t) for t in toks[1, :counts[1]]]
    assert again == want


def test_a_window_is_its_one_trip_dispatches(opened):
    pred, _ = opened
    a, b = pred.new_session(2), pred.new_session(2)
    for s in (a, b):
        s.prefill(0, _prompt(4, 8))
        s.prefill(1, _prompt(10, 9))
    toks, counts, trips = a.decode_fused(dec.STEP_WINDOW)
    singles = np.stack([b.decode() for _ in range(trips)], axis=1)
    np.testing.assert_array_equal(toks[:, :trips], singles)
    for x, y in zip(a._tables(), b._tables()):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_a_slot_that_stops_mid_window_keeps_all_three_kinds_of_state(opened):
    """Its K/V rows, conv window and scanned state after the window are
    those of its own stop, bit for bit; the neighbour's stream and the
    K/V blocks counted are those of the trips each slot ran
    (`tests/test_decode_window.py`)."""
    from tests.test_decode_window import a_slot_that_stops_sits_out_the_window
    a_slot_that_stops_sits_out_the_window(
        opened[0], [_prompt(6, 8), _prompt(10, 9)], 3)


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
def test_what_a_scanned_state_cannot_do_is_refused_by_name(opened, artifact,
                                                           what):
    pred, _ = opened
    with pytest.raises(NotImplementedError, match="layer_types"):
        REFUSALS[what](pred, artifact)


def test_a_mesh_refuses_by_name(artifact):
    from paddle_tpu.parallel.mesh import MeshGroup
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs two devices")
    with pytest.raises(NotImplementedError, match="layer_types"):
        GenerativePredictor(artifact, device=MeshGroup(devs[:2]))


@pytest.mark.parametrize("key,value,match", [
    ("layer_types", ["attention+ssm", "conv"], "one width"),
    ("layer_types", ["attention+ssm", "mamba"], "layer_types"),
    ("ssm_heads", 0, "ssm_heads"),
    ("ssm_state", 0, "ssm_state"),
    ("ssm_groups", 3, "ssm_groups"),
    ("ssm_conv_kernel", 1, "ssm_conv_kernel"),
    ("ssm_chunk", 0, "ssm_chunk"),
    ("ssm_multipliers", [1.0, 2.0], "ssm_multipliers"),
    ("mlp_multipliers", [1.0], "mlp_multipliers"),
    ("head_dim", -1, "head_dim"),
    ("head_dim", 7, "even"),
    ("ffn", "geglu", "ffn"),
    ("dense_width", 0, "dense_width"),
    ("n_dense_layers", 1, "n_dense_layers"),
])
def test_a_stack_this_module_has_no_math_for_is_a_typed_error(key, value,
                                                              match):
    meta = dict(SSM_BLOCK, vocab_size=53, d_model=24, n_heads=4, n_layers=2,
                max_seq_len=32, **{key: value})
    if key == "layer_types" and "conv" in value:
        meta["conv_kernel"] = 3
    with pytest.raises(ValueError, match=match):
        dec.block_of(meta)


def test_ssm_keys_without_the_layer_are_refused():
    meta = dict(vocab_size=53, d_model=24, n_heads=4, n_layers=2,
                max_seq_len=32)
    with pytest.raises(ValueError, match="attention\\+ssm"):
        dec.block_of(dict(meta, ssm_in_multiplier=0.25))
    with pytest.raises(ValueError, match="mlp_multipliers"):
        dec.block_of(dict(meta, mlp_multipliers=[0.5, 0.5]))


def test_bf16_at_rest_keeps_the_taps_and_the_vectors_float32(tmp_path):
    art = build_tiny_decode_model(
        str(tmp_path / "bf16"), block=dict(SSM_BLOCK,
                                           weight_dtype="bfloat16"), **TINY)
    pred = GenerativePredictor(art)
    kept = {n: np.asarray(v).dtype.name for n, v in pred._state_host.items()}
    for n in ("ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_A_log",
              "ssm_D", "ssm_norm_g", "ln1_g"):
        assert kept["l0_" + n] == "float32", n
    for n in ("l0_ssm_in", "l0_ssm_out", "l0_wq", "l0_ffn_up", "embed",
              "lm_head"):
        assert kept[n] == "bfloat16", n
    # ... and computes what float32 storage of the same numbers computes
    wide = {n: np.asarray(v, np.float32) for n, v in
            pred._state_host.items()}
    art32 = dec.save_decode_model(str(tmp_path / "f32"), wide,
                                  dict(pred.meta, weight_dtype="float32"))
    prompt = _prompt(7, 4)
    assert dec.greedy_decode(pred, prompt, 8)[0] \
        == dec.greedy_decode(GenerativePredictor(art32), prompt, 8)[0]


OLD_STACKS = {
    "gpt2": None,
    "olmoe": {"norm": "rmsnorm", "position": "rope", "qk_norm": True,
              "ffn": "moe_swiglu", "n_experts": 8, "experts_per_token": 2,
              "expert_width": 32},
    "lfm2": {"norm": "rmsnorm", "position": "rope", "rope_theta": 1e6,
             "qk_norm": "head", "n_kv_heads": 2,
             "layer_types": ["conv", "attention", "conv"], "conv_kernel": 3,
             "n_dense_layers": 1, "dense_width": 96, "ffn": "moe_swiglu",
             "n_experts": 8, "experts_per_token": 2, "expert_width": 32,
             "norm_topk_prob": True, "router": "sigmoid_bias",
             "head": "tied"},
    "pangu": {"norm": "rmsnorm", "position": "rope",
              "layer_types": ["mla"] * 3, "q_lora_rank": 24,
              "kv_lora_rank": 16, "qk_nope_head_dim": 8,
              "qk_rope_head_dim": 4, "v_head_dim": 8, "sandwich_norm": True,
              "n_dense_layers": 1, "dense_width": 96, "ffn": "moe_swiglu",
              "n_experts": 8, "experts_per_token": 2, "expert_width": 32,
              "norm_topk_prob": True, "router": "sigmoid",
              "routed_scaling": 2.5, "n_shared_experts": 1,
              "experts_held": [2, 4], "weight_dtype": "bfloat16"},
}
OLD_TINY = dict(vocab_size=97, d_model=64, n_heads=8, n_layers=3,
                max_seq_len=64, eos_id=0, seed=11, prefill_buckets=[16, 32])
NEW_KEYS = {"head_dim": 0, "ssm_heads": 0, "ssm_head_dim": 0,
            "ssm_state": 0, "ssm_groups": 1, "ssm_conv_kernel": 0,
            "ssm_chunk": 128, "embedding_multiplier": 1.0,
            "lm_head_multiplier": 1.0, "attention_in_multiplier": 1.0,
            "key_multiplier": 1.0, "attention_out_multiplier": 1.0,
            "ssm_in_multiplier": 1.0, "ssm_out_multiplier": 1.0,
            "ssm_multipliers": [], "mlp_multipliers": []}


def _jaxpr(pred, math, specs):
    spec = {n: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
            for n, v in pred._state_host.items()}
    return str(jax.make_jaxpr(math)(spec, *specs))


@pytest.fixture(scope="module", params=sorted(OLD_STACKS))
def old_stack(request, tmp_path_factory):
    d = tmp_path_factory.mktemp("old_" + request.param)
    block = OLD_STACKS[request.param]
    old = GenerativePredictor(build_tiny_decode_model(
        str(d / "old"), block=block, **OLD_TINY))
    # the same weights under a meta that SPELLS every new key at its default
    spelled = GenerativePredictor(dec.save_decode_model(
        str(d / "new"), old._state_host, dict(old.meta, **NEW_KEYS)))
    return old, spelled


def test_an_artifact_written_before_the_keys_opens_unchanged(old_stack):
    """An artifact that names none of this PR's keys is the block it was:
    every new key defaulted, its step and its prefill the programs of the
    same artifact with the keys SPELLED at their defaults, its slot state
    the tables it had, and its stream the same tokens."""
    old, spelled = old_stack
    assert all(old.block[k] == dict(dec.BLOCK_DEFAULTS)[k]
               for k in NEW_KEYS)
    assert old.ssm_layers == 0 and old.ssm_state_shape(2) is None
    assert old.new_session(2)._ss is None
    assert old._n_tables == spelled._n_tables <= 3
    assert _jaxpr(old, old._step_math(), old._step_specs(2)) \
        == _jaxpr(spelled, spelled._step_math(), spelled._step_specs(2))
    prompt = [int(t) for t in np.random.RandomState(2).randint(1, 97, 11)]
    assert dec.greedy_decode(old, prompt, 12)[0] \
        == dec.greedy_decode(spelled, prompt, 12)[0]


@pytest.mark.parametrize("n", [1, 9, 16])
def test_the_first_token_is_the_head_over_all_positions_at_one(old_stack,
                                                                n):
    """A prefill takes x[:, true_len - 1] BEFORE the head: its first token
    is, bit for bit, the argmax of the row `true_len - 1` of the head over
    the whole bucket (what every prefill computed before), and the row's
    logits are that row's."""
    old, _ = old_stack
    prompt = [int(t) for t in np.random.RandomState(n).randint(1, 97, n)]
    padded = np.zeros((1, 16), np.int32)
    padded[0, :n] = prompt
    state = {k: jnp.asarray(v) for k, v in old._state_host.items()}
    x, _, _ = old._prefill_layers(state, jnp.asarray(padded), jnp.int32(n))
    whole = np.asarray(old._head(state, x, dec._OFF_MESH))[0]     # [B, V]
    row = np.asarray(old._head(
        state, x[:, n - 1:n], dec._OFF_MESH))[0, 0]
    np.testing.assert_allclose(row, whole[n - 1], rtol=0, atol=1e-5)
    first = old._prefill_core(state, jnp.asarray(padded), jnp.int32(n))[0]
    first = int(np.asarray(first).reshape(-1)[0])
    assert first == int(np.argmax(whole[n - 1]))
    sess = old.new_session(1)
    assert sess.prefill(0, prompt) == first


def test_spans_say_what_the_stack_holds_and_what_a_prefill_scanned(opened):
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
    launches = [s["attrs"] for s in spans if s["name"] == "decode/launch"]
    assert [a["phase"] for a in fetches] == ["prefill", "prefill", "step"]
    # the step says what ran its recurrence; a prefill scans, and says
    # nothing of it
    assert [a.get("ssm_update") for a in fetches] == [None, None, "pallas"]
    for a in fetches:
        assert (a["ssm_layers"], a["conv_layers"], a["attn_layers"]) \
            == (2, 2, 2)
        assert a["ssm_state_bytes"] == sess.ssm_state_bytes() \
            == 2 * 2 * 4 * 8 * 16 * 4
        assert a["conv_state_bytes"] == sess.conv_state_bytes()
    for spans_of in (fetches, launches):
        assert [(a.get("bucket"), a.get("ssm_chunks")) for a in spans_of] \
            == [(8, 2), (16, 4), (None, None)]
    # the K/V stream's counter counts K/V rows alone
    assert "kv_blocks_live" not in fetches[0]


def _pallas_calls(jaxpr):
    """[(named scope, params)] of every `pallas_call` of a jaxpr, the
    bodies of its loops and branches included, in program order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((str(eqn.source_info.name_stack), eqn.params))
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found.extend(_pallas_calls(inner))
    return found


def test_device_scopes_name_the_mixers_work(opened):
    """`ssm_proj`, `ssm_update` in the step and `ssm_scan` in a prefill:
    the scopes the benchmark's readers find the operations by."""
    pred, _ = opened
    spec = {n: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
            for n, v in pred._state_host.items()}
    step = jax.jit(pred._step_math()).lower(
        spec, *pred._step_specs(2)).as_text(debug_info=True)
    assert "ssm_update" in step and "ssm_proj" in step
    assert "ssm_scan" not in step
    # ... and the step's recurrence IS the kernel: one `pallas_call` a
    # layer under the `ssm_update` scope, named, with metadata of its own
    # (the attention kernel's has none: the benchmark tells them apart by
    # that) and the scanned-state table aliased to its result
    calls = _pallas_calls(jax.make_jaxpr(pred._step_math())(
        spec, *pred._step_specs(2)).jaxpr)
    ours = [(scope, p) for scope, p in calls if p["name"] == "ssm_update"]
    assert len(ours) == pred.ssm_layers == 2
    for scope, p in ours:
        assert scope.split("/")[0] == "ssm_update"
        assert dict(p["metadata"]) == {"kernel": "ssm_update"}
        assert tuple(p["input_output_aliases"]) == ((3, 1),)
    assert [p["metadata"] for scope, p in calls
            if p["name"] != "ssm_update"] == [None, None]
    prefill = jax.jit(pred._prefill_math).lower(
        spec, jax.ShapeDtypeStruct((1, 8), np.int32),
        jax.ShapeDtypeStruct((), np.int32)).as_text(debug_info=True)
    assert "ssm_scan" in prefill and "ssm_proj" in prefill
    assert "ssm_update" not in prefill


def test_served_through_the_wire_with_the_default_placement(artifact,
                                                            opened):
    """registry.load_model -> DecodeBatcher -> the wire, no flag: three
    streams over two slots, joining and leaving, each the stream of a
    session of its own; the stats count the scanned state with the cache
    and the conv windows apart."""
    import threading
    pred, _ = opened
    server = InferenceServer().start()
    boot = ServingClient(server.endpoint)
    prompts = [_prompt(5), _prompt(13, seed=9), _prompt(2, seed=4)]
    outs, errs = [None] * 3, []
    try:
        boot.load_model("falconh1", artifact, decode_slots=2)

        def worker(i):
            cli = ServingClient(server.endpoint)
            try:
                outs[i] = [t for c in cli.infer_stream(
                    "falconh1", prompts[i], max_new_tokens=10 + i,
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
            want, _ = dec.greedy_decode(pred, p, 10 + i)
            assert [int(t) for t in out] == want
        stats = boot.stats()["stats"]["models"]["falconh1"]
        assert stats["kv_cache_bytes"] == pred.kv_cache_bytes(2)
        assert stats["conv_state_bytes"] == pred.conv_state_bytes(2)
    finally:
        boot.close()
        server.shutdown(drain=True)
