"""A decode artifact whose STACK has layers of two kinds (gated short
convolutions beside grouped-query attention: LFM2's), a leading dense SwiGLU
layer before sigmoid, bias-corrected routed experts, per-head qk-norm and a
tied head, through the serving path, against the plain reference
`benchmark/reference/lfm2_24b_a2b.py`, at a tiny size on the CPU.

A slot of such a session holds TWO kinds of state: rows of the K/V tables
(the attention layers', addressed by its length) and a row of the conv-state
table (each conv layer's last K-1 inputs, a fixed size).  What these tests
pin: both are written by prefill at the TRUE prompt end, advanced by a step
and by every trip of a window only where the slot runs, zeroed by `free`,
and never leak into a neighbour; what cannot hold for a recurrent layer is
refused by a typed error that names the meta key.

TOL_LOGITS as in test_olmoe_decode.py: both sides compute in float32 here,
in another order of operations; measured differences are a few 1e-6.
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

from benchmark.reference import lfm2_24b_a2b as reference  # noqa: E402
from paddle_tpu.inference import decode as dec  # noqa: E402
from paddle_tpu.inference.decode import (GenerativePredictor,  # noqa: E402
                                         SpeculativeDecodeSession,
                                         build_tiny_decode_model)
from paddle_tpu.obs import tracing as obs_tracing  # noqa: E402
from paddle_tpu.ops import pallas_kernels as pk  # noqa: E402
from paddle_tpu.serving import (InferenceServer,  # noqa: E402
                                ServingClient)

TOL_LOGITS = 1e-4
K = 3
LFM2_BLOCK = {"norm": "rmsnorm", "norm_eps": 1e-5, "position": "rope",
              "rope_theta": 1e6, "qk_norm": "head", "n_kv_heads": 2,
              "layer_types": ["conv", "attention", "conv", "conv", "conv"],
              "conv_kernel": K, "n_dense_layers": 1, "dense_width": 96,
              "ffn": "moe_swiglu", "n_experts": 8, "experts_per_token": 2,
              "expert_width": 32, "norm_topk_prob": True,
              "router": "sigmoid_bias", "head": "tied"}
TINY = dict(vocab_size=97, d_model=64, n_heads=8, n_layers=5,
            max_seq_len=64, eos_id=0, seed=11, prefill_buckets=[16, 32, 64])
BUCKET = 16


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("lfm2") / "lm")
    return build_tiny_decode_model(d, block=LFM2_BLOCK, **TINY)


@pytest.fixture(scope="module")
def opened(artifact):
    pred = GenerativePredictor(artifact)
    return pred, {n: jnp.asarray(v) for n, v in pred._state_host.items()}


_REF = {}


def _ref(state, seq, meta):
    """(logits, router gaps) of the reference for `seq`, through ONE jitted
    program: the sequence padded to max_seq_len (causal)."""
    fn = _REF.get("fn")
    if fn is None:
        model = {k: meta[k] for k in sorted(meta)}
        fn = _REF["fn"] = jax.jit(
            lambda st, t: reference.forward(st, t, model))
    tokens = np.zeros(TINY["max_seq_len"], np.int32)
    tokens[:len(seq)] = seq
    logits, gaps = fn(state, jnp.asarray(tokens))
    return np.asarray(logits)[:len(seq)], np.asarray(gaps)[:len(seq)]


def _prompt(n, seed=3):
    return np.random.default_rng([seed, n]).integers(
        1, TINY["vocab_size"], n, dtype=np.int32)


def test_the_meta_describes_the_stack(opened):
    pred, _ = opened
    assert pred.layer_kinds == [
        ("conv", "dense_swiglu"), ("attention", "moe_swiglu"),
        ("conv", "moe_swiglu"), ("conv", "moe_swiglu"),
        ("conv", "moe_swiglu")]
    assert (pred.conv_layers, pred.routed_layers, pred._n_tables) == (4, 4,
                                                                      3)
    # the K/V tables hold the ATTENTION layer only, by its K/V heads; the
    # conv state is K-1 inputs a conv layer
    assert pred.table_shape(3) == (1, 3, 64, 2 * 8)
    assert pred.conv_state_shape(3) == (4, 3, K - 1, 64)
    assert pred.kv_cache_bytes(3) == 2 * 3 * 64 * 2 * 8 * 4
    assert pred.conv_state_bytes(3) == 4 * 3 * 2 * 64 * 4
    sess = pred.new_session(3)
    assert sess.cache_bytes() == pred.kv_cache_bytes(3)
    assert sess.conv_state_bytes() == pred.conv_state_bytes(3)
    # a tied head: one table in memory
    assert "lm_head" not in pred._state_host
    assert pred.param_bytes() == sum(
        4 * int(np.prod(s))
        for s in dec.decode_state_shapes(pred.meta).values())


def test_the_static_report_prices_the_same_slot_state(opened, artifact):
    from paddle_tpu.analysis.resources import _decode_report
    pred, _ = opened
    rep = _decode_report(artifact, pred.meta, 3, None, "lfm2")
    assert rep.kv_cache_bytes == pred.kv_cache_bytes(3)
    assert rep.param_bytes == pred.param_bytes()
    plain = _decode_report(artifact, dict(pred.meta, layer_types=[
        "attention"] * 5, n_dense_layers=0), 3, None, "lfm2")
    assert plain.kv_cache_bytes == 5 * rep.kv_cache_bytes
    assert rep.activation_peak_bytes - plain.activation_peak_bytes \
        == pred.conv_state_bytes(3)


@pytest.mark.parametrize("path", ["session", "window"])
@pytest.mark.parametrize("n", [1, 2, K, BUCKET - 1, 20])
def test_prefill_and_32_steps_match_the_reference_by_logits(opened, path, n):
    """Prompts of length 1, 2, K and bucket - 1 (the conv state at the TRUE
    end, zeros where the prompt is shorter than K - 1) and one in the next
    bucket; 32 decode steps through both kinds of state, by logits through
    `DecodeSession.decode_logits` and token for token through the fused
    window."""
    pred, state = opened
    prompt = _prompt(n)
    sess = pred.new_session(3)
    seq = list(prompt) + [sess.prefill(1, prompt)]
    got = []
    if path == "session":
        for _ in range(32):
            toks, logits = sess.decode_logits()
            got.append(logits[1])
            seq.append(int(toks[1]))
    else:
        while len(seq) < n + 33:
            toks, counts, trips = sess.decode_fused(dec.STEP_WINDOW)
            assert counts[1] == trips and counts[0] == counts[2] == 0
            seq += [int(t) for t in toks[1, :counts[1]]]
        seq = seq[:n + 33]
    want, _ = _ref(state, seq, pred.meta)
    # the prefill's token and every decoded one: the reference's top-1
    for t in range(33):
        row = want[n - 1 + t]
        assert row.max() - row[seq[n + t]] <= 2 * TOL_LOGITS, t
    for t, logits in enumerate(got):
        assert np.max(np.abs(logits - want[n + t])) <= TOL_LOGITS, t


def test_prefill_writes_the_conv_state_at_the_true_prompt_end(opened):
    """A prompt shorter than its bucket: the slot's conv state is the last
    K-1 gated inputs BEFORE the true end (zeros before the start), so the
    same prompt through another bucket leaves the same state (to the
    rounding of a matmul of another shape)."""
    pred, _ = opened
    prompt = _prompt(K + 2)
    a, b = pred.new_session(2), pred.new_session(2)
    a.prefill(0, prompt)
    padded = dict(pred.meta, prefill_buckets=[32, 64])
    wide = object.__new__(GenerativePredictor)
    wide.__dict__.update(pred.__dict__)
    wide.meta, wide._fns = padded, {}
    b.predictor = wide
    b.prefill(0, prompt)
    np.testing.assert_allclose(np.asarray(a._cs), np.asarray(b._cs),
                               rtol=0, atol=1e-5)
    assert np.asarray(a._cs[:, 0]).any()
    # shorter than K - 1: the state's first row is the zero before the start
    c = pred.new_session(1)
    c.prefill(0, prompt[:1])
    cs = np.asarray(c._cs)
    assert not cs[:, 0, 0].any() and cs[:, 0, 1].any()


def test_a_slot_that_stops_mid_window_keeps_both_kinds_of_state(opened):
    """Its K/V rows and its conv state after the window are those of its
    own stop, bit for bit (no row written, no window rolled in the trips it
    sat out); the neighbour's stream, the routing facts and the K/V blocks
    counted are those of the trips each slot ran
    (`tests/test_decode_window.py`)."""
    from tests.test_decode_window import a_slot_that_stops_sits_out_the_window
    a_slot_that_stops_sits_out_the_window(
        opened[0], [_prompt(9), _prompt(13, seed=5)], 3)


def test_a_freed_slot_is_zero_in_both_kinds_of_state(opened):
    pred, _ = opened
    sess = pred.new_session(3)
    quiet = pred.new_session(3)          # the neighbour, alone
    p1, p2 = _prompt(9), _prompt(13, seed=5)
    sess.prefill(0, p1)
    sess.prefill(2, p2)
    quiet.prefill(2, p2)
    for _ in range(3):
        sess.decode_fused(4)
        quiet.decode_fused(4)
    assert not sess.slot_is_zero(0) and sess.slot_is_zero(1)
    assert np.asarray(sess._cs[:, 0]).any() and np.asarray(
        sess._kc[:, 0]).any()
    sess.free(0)
    assert sess.slot_is_zero(0)
    assert not np.asarray(sess._cs[:, 0]).any()
    assert not np.asarray(sess._kc[:, 0]).any()
    assert not np.asarray(sess._vc[:, 0]).any()
    # an inactive slot stays zero through steps, and the neighbour's logits
    # do not move by a bit for the company it had
    _, l_sess = sess.decode_logits()
    _, l_quiet = quiet.decode_logits()
    assert sess.slot_is_zero(0) and sess.slot_is_zero(1)
    assert (l_sess[2] == l_quiet[2]).all()
    # a new occupant of the freed slot is the stream of a fresh session
    fresh = pred.new_session(1)
    assert sess.prefill(0, p1) == fresh.prefill(0, p1)
    a, _ = sess.decode_logits()
    b, _ = fresh.decode_logits()
    assert a[0] == b[0]


def test_selection_by_biased_score_is_counted_on_both_sides(opened):
    """The expert bias changes WHICH experts a token gets, not their
    weights: on a counted, non-zero share of the (token, layer) pairs the
    biased top-k differs from the unbiased one, the program's routed FFN
    agrees with the reference's on every pair, and dropping the bias
    changes the program's result on exactly those tokens."""
    pred, state = opened
    k = LFM2_BLOCK["experts_per_token"]
    rng = np.random.default_rng(8)
    h = jnp.asarray(rng.standard_normal((48, 64)), jnp.float32)
    differ = total = 0
    for i in range(1, 5):                       # the routed layers
        p = "l%d_" % i
        w = {n: state[p + n] for n in reference.FFN["routed"]}
        s = jax.nn.sigmoid(jnp.dot(h, w["router"], precision="highest"))
        biased = np.sort(np.asarray(jax.lax.top_k(
            s + w["expert_bias"], k)[1]), axis=1)
        plain = np.sort(np.asarray(jax.lax.top_k(s, k)[1]), axis=1)
        moved = (biased != plain).any(axis=1)
        differ += int(moved.sum())
        total += len(moved)
        with jax.default_matmul_precision("highest"):
            want, _, _, _ = reference._routed_ffn(h, w, pred.meta)
            got, _ = dec.moe_ffn(h, w["router"], w["w_gate"], w["w_up"],
                                 w["w_down"], k, True,
                                 expert_bias=w["expert_bias"])
            unbiased, _ = dec.moe_ffn(
                h, w["router"], w["w_gate"], w["w_up"], w["w_down"], k,
                True, expert_bias=jnp.zeros_like(w["expert_bias"]))
        assert np.max(np.abs(np.asarray(got - want))) <= 1e-5
        changed = np.abs(np.asarray(got - unbiased)).max(axis=1) > 1e-6
        assert (changed == moved).all()
    assert 0 < differ < total and differ / total > 0.05, (differ, total)


def test_the_logits_step_hands_out_each_routed_layers_picks(opened):
    """`decode_logits` of a stack in which a conv layer follows a routed FFN
    keeps the step's chosen experts (`last_picks` [routed layers, slots,
    k]), and they are the reference's at that position; the window's step
    hands out nothing of the kind."""
    pred, state = opened
    assert pred._step_picks
    k = LFM2_BLOCK["experts_per_token"]
    with jax.default_matmul_precision("highest"):
        sess = pred.new_session(2)
        assert sess.last_picks is None
        prompt = _prompt(9)
        seq = list(prompt) + [sess.prefill(1, prompt)]
        for _ in range(3):
            toks, _ = sess.decode_logits()
            assert sess.last_picks.shape == (pred.routed_layers, 2, k)
            seq.append(int(toks[1]))
            x = reference.embed(state["embed"], jnp.asarray(seq[:-1]))
            want = []
            for i in range(pred.meta["n_layers"]):
                x, _, used, _ = reference.layer_hinted(
                    x, {n: state["l%d_%s" % (i, n)]
                        for n in reference.layer_names(pred.meta, i)},
                    pred.meta)
                if used is not None:
                    want.append(np.asarray(used[-1]))
            assert (np.sort(sess.last_picks[:, 1], axis=-1)
                    == np.stack(want)).all()
    assert len(pred._step_specs(2)) == 8        # the window: no picks


@pytest.mark.parametrize("lengths", [[1, 7, 64, 33], [0, 64, 2, 17]])
def test_grouped_query_kernel_matches_the_reference(lengths):
    """The decode kernel at 32 query heads over 8 K/V heads of 64 (LFM2's),
    interpret mode, single layer and stacked: query head a reads K/V head
    a // 4, each K/V tile streamed once for its group."""
    rng = np.random.default_rng(5)
    N, S, H, Hkv, D = 4, 64, 32, 8, 64
    q = jnp.asarray(rng.standard_normal((N, H, D)), jnp.float32)
    apart = [jnp.asarray(rng.standard_normal((2, N, S, Hkv, D)), jnp.float32)
             for _ in "kv"]
    # as a slot table holds them: K/V head c is lanes c * D .. of the row
    kc, vc = (t.reshape(2, N, S, Hkv * D) for t in apart)
    lens = jnp.asarray(lengths, jnp.int32)
    want = pk.decode_attention_reference(q, kc[1], vc[1], lens, scale=0.125)
    # the oracle's own grouping, spelled out for two heads
    for a in (5, 30):
        one = pk.decode_attention_reference(
            q[:, a:a + 1], *(t[1][:, :, a // 4] for t in apart), lens,
            scale=0.125)
        np.testing.assert_allclose(np.asarray(want[:, a]),
                                   np.asarray(one[:, 0]), atol=1e-6)
    live = np.asarray(lengths) > 0          # length 0: well-defined garbage
    for got in (pk.decode_attention(q, kc[1], vc[1], lens, scale=0.125,
                                    block_kv=16, interpret=True),
                pk.decode_attention(q, kc, vc, lens, scale=0.125,
                                    block_kv=16, interpret=True, layer=1)):
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live], atol=2e-5)
    with pytest.raises(ValueError, match="query heads over"):
        pk.decode_attention(q[:, :30], kc[1], vc[1], lens, interpret=True,
                            block_kv=16)
    with pytest.raises(ValueError, match="int8"):
        pk.decode_attention(q, kc[1].astype(jnp.int8),
                            vc[1].astype(jnp.int8), lens, interpret=True,
                            block_kv=16, kv_scales=np.ones((2, 8)))


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
def test_what_cannot_hold_for_a_recurrent_layer_is_refused_by_name(
        opened, artifact, what):
    pred, _ = opened
    with pytest.raises(NotImplementedError, match="layer_types"):
        REFUSALS[what](pred, artifact)


def test_int8_kv_is_refused_for_grouped_query_by_name(tmp_path):
    block = dict(LFM2_BLOCK, layer_types=[], n_dense_layers=0)
    art = build_tiny_decode_model(str(tmp_path / "gqa"), block=block,
                                  **dict(TINY, n_layers=2))
    GenerativePredictor(art).new_session(2).rollback(0, 0)   # no conv: fine
    with pytest.raises(NotImplementedError, match="n_kv_heads"):
        GenerativePredictor(art, kv_cache_dtype="int8")


def test_tp_lane_and_mesh_refuse_by_name(artifact):
    from paddle_tpu.flags import set_flags
    from paddle_tpu.parallel.mesh import MeshGroup
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs two devices")
    group = MeshGroup(devs[:2])
    with pytest.raises(NotImplementedError, match="layer_types"):
        GenerativePredictor(artifact, device=group)
    set_flags({"mesh_tp": True})
    try:
        with pytest.raises(NotImplementedError):
            GenerativePredictor(artifact, device=group)
    finally:
        set_flags({"mesh_tp": False})


@pytest.mark.parametrize("key,value,match", [
    ("layer_types", ["conv"] * 5, "attention layer among"),
    ("layer_types", ["conv", "attention"], "each of the 5"),
    ("layer_types", ["conv", "window"] + ["attention"] * 3, "layer_types"),
    ("conv_kernel", 1, "conv_kernel"),
    ("n_kv_heads", 3, "n_kv_heads"),
    ("n_dense_layers", 6, "n_dense_layers"),
    ("router", "noisy", "router"),
    ("head", "shared", "head"),
    ("qk_norm", "row", "qk_norm"),
])
def test_a_stack_this_module_has_no_math_for_is_a_typed_error(key, value,
                                                              match):
    meta = dict(LFM2_BLOCK, vocab_size=97, d_model=64, n_heads=8,
                n_layers=5, max_seq_len=64, **{key: value})
    with pytest.raises(ValueError, match=match):
        dec.block_of(meta)


def _step_text(pred, n_slots=2):
    spec = {n: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
            for n, v in pred._state_host.items()}
    return str(jax.make_jaxpr(pred._step_math())(
        spec, *pred._step_specs(n_slots)))


@pytest.mark.parametrize("block", [None, {
    "norm": "rmsnorm", "position": "rope", "qk_norm": True,
    "ffn": "moe_swiglu", "n_experts": 8, "experts_per_token": 2,
    "expert_width": 32}], ids=["gpt2", "olmoe"])
def test_an_old_metas_step_is_unchanged_by_the_new_keys(tmp_path, block):
    """An artifact that names none of the new keys is the block it was: the
    step's jaxpr is the one of the same artifact with every new key SPELLED
    at its default, and its slot state is the two K/V tables."""
    kw = dict(TINY, n_heads=4, n_layers=2)
    old = GenerativePredictor(build_tiny_decode_model(
        str(tmp_path / "old"), block=block, **kw))
    new_keys = {"layer_types": [], "conv_kernel": 0, "n_kv_heads": 0,
                "n_dense_layers": 0, "dense_width": 0, "router": "softmax",
                "head": "untied"}
    assert all(old.block[k] == dict(dec.BLOCK_DEFAULTS)[k]
               for k in new_keys)
    spelled = GenerativePredictor(build_tiny_decode_model(
        str(tmp_path / "new"), block=dict(block or {}, **new_keys), **kw))
    text = _step_text(old)
    assert text == _step_text(spelled)
    assert old._n_tables == 2 and old.conv_state_shape(2) is None
    assert not old._step_picks
    assert old.new_session(2)._cs is None
    assert len(old._step_specs(2)) == 7
    # against the PARENT's tree the same texts are compared by
    # tools/decode_hlo_dump.py (two trees, `cmp`): CHANGES.md, PR 31


def test_fetch_spans_say_what_the_stack_holds(opened):
    pred, _ = opened
    sess = pred.new_session(2)
    was = obs_tracing.enabled()
    obs_tracing.set_enabled(True)
    try:
        obs_tracing.clear()
        sess.prefill(0, _prompt(6))
        sess.decode_fused(3)
        fetches = [s for s in obs_tracing.recent_spans()
                   if s["name"] == "decode/fetch"]
    finally:
        obs_tracing.set_enabled(was)
    assert [s["attrs"]["phase"] for s in fetches] == ["prefill", "step"]
    for s in fetches:
        a = s["attrs"]
        assert (a["conv_layers"], a["attn_layers"]) == (4, 1)
        assert a["conv_state_bytes"] == sess.conv_state_bytes() \
            == 4 * 2 * 2 * 64 * 4
        assert a["moe_experts_touched"] > 0
    # the routing facts are the four ROUTED layers'
    assert sess.last_routing.shape == (4, 2)


def test_served_through_the_wire_with_the_default_placement(artifact,
                                                            opened):
    """registry.load_model -> DecodeBatcher -> the wire, no flag: three
    streams over two slots, joining and leaving, each the stream of a
    session of its own; the stats report K/V bytes and conv-state bytes
    apart."""
    import threading
    pred, _ = opened
    server = InferenceServer().start()
    boot = ServingClient(server.endpoint)
    prompts = [_prompt(5), _prompt(17, seed=9), _prompt(2, seed=4)]
    outs, errs = [None] * 3, []
    try:
        boot.load_model("lfm2", artifact, decode_slots=2)

        def worker(i):
            cli = ServingClient(server.endpoint)
            try:
                outs[i] = [t for c in cli.infer_stream(
                    "lfm2", prompts[i], max_new_tokens=12 + i,
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
            want, _ = dec.greedy_decode(pred, p, 12 + i)
            assert [int(t) for t in out] == want
        stats = boot.stats()["stats"]["models"]["lfm2"]
        assert stats["kv_cache_bytes"] == pred.kv_cache_bytes(2)
        assert stats["conv_state_bytes"] == pred.conv_state_bytes(2)
    finally:
        boot.close()
        server.shutdown(drain=True)
