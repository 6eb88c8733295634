"""A decode artifact whose meta describes another block than GPT-2's
(RMSNorm, rotary positions, qk-norm, a dropless top-k routed-expert FFN:
OLMoE's) through the serving path, against the plain reference
`benchmark/reference/olmoe_1b_7b.py`, at a tiny size on the CPU.

TOL_LOGITS: both sides compute in float32 here (the CPU backend does not
round matmul operands to bf16), in another order of operations: sorted
groups against all-experts-then-mask, a cache and a kernel against one
causal pass.  Measured differences are a few 1e-6 on logits of std ~1; the
bound is 1e-4.  Storage in bf16 (8 mantissa bits) of the weights or of the
cache moves a logit by 1e-2, a hundred times the bound, and the two cases
that round them must FAIL it.  The router runs at "highest" precision on
both sides, so both keep the same experts but on an exact tie.
"""

import os
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import olmoe_1b_7b as reference  # noqa: E402
from paddle_tpu.flags import set_flags  # noqa: E402
from paddle_tpu.inference import decode as dec  # noqa: E402
from paddle_tpu.inference.decode import (GenerativePredictor,  # noqa: E402
                                         build_tiny_decode_model,
                                         save_decode_model)
from paddle_tpu.obs import tracing as obs_tracing  # noqa: E402
from paddle_tpu.serving import (DecodeBatcher,  # noqa: E402
                                InferenceServer, ServingClient)

TOL_LOGITS = 1e-4
OLMOE_BLOCK = {"norm": "rmsnorm", "norm_eps": 1e-5, "position": "rope",
               "rope_theta": 10000.0, "qk_norm": True, "ffn": "moe_swiglu",
               "n_experts": 8, "experts_per_token": 2, "expert_width": 32,
               "norm_topk_prob": False}
TINY = dict(vocab_size=97, d_model=64, n_heads=4, n_layers=2,
            max_seq_len=64, eos_id=0, seed=11, prefill_buckets=[16, 32, 64])


def _open(dirname):
    pred = GenerativePredictor(dirname)
    state = {n: jnp.asarray(v) for n, v in pred._state_host.items()}
    return pred, state


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("olmoe") / "lm")
    return build_tiny_decode_model(d, block=OLMOE_BLOCK, **TINY)


@pytest.fixture(scope="module")
def opened(artifact):
    return _open(artifact)


_REF = {}


def _ref_logits(state, seq, meta):
    """The reference's logits for `seq`, through ONE jitted program: the
    sequence padded to max_seq_len (causal, so the pad changes nothing
    before it)."""
    fn = _REF.get("fn")
    if fn is None:
        model = {k: meta[k] for k in sorted(meta)}
        fn = _REF["fn"] = jax.jit(
            lambda st, t: reference.forward(st, t, model)[0])
    tokens = np.zeros(TINY["max_seq_len"], np.int32)
    tokens[:len(seq)] = seq
    return np.asarray(fn(state, jnp.asarray(tokens)))[:len(seq)]


def _prompts(lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, TINY["vocab_size"], n, dtype=np.int32)
            for n in lens]


def _logit_diff(pred, ref_state, prompts, steps=6, spoil_cache=False):
    """max |program - reference| over the logits of `steps` decode steps
    after each prompt's prefill, the program teacher-forced on its own
    tokens; plus the prefill tokens' worst gap below the reference top-1."""
    sess = pred.new_session(len(prompts))
    seqs = [list(p) + [sess.prefill(i, p)] for i, p in enumerate(prompts)]
    if spoil_cache:
        sess._kc = sess._kc.astype(jnp.bfloat16).astype(jnp.float32)
        sess._vc = sess._vc.astype(jnp.bfloat16).astype(jnp.float32)
    got = []
    for _ in range(steps):
        toks, logits = sess.decode_logits()
        got.append(logits)
        for i, s in enumerate(seqs):
            s.append(int(toks[i]))
    worst, gap = 0.0, 0.0
    for i, s in enumerate(seqs):
        want = _ref_logits(ref_state, s, pred.meta)
        n = len(prompts[i])
        gap = max(gap, float(want[n - 1].max() - want[n - 1, s[n]]))
        for t in range(steps):
            worst = max(worst, float(np.max(np.abs(got[t][i]
                                                   - want[n + t]))))
    return worst, gap


# (a) prefill + decode through the cache against the full forward ---------

def test_prefill_and_decode_match_the_reference_by_logits(opened):
    pred, state = opened
    prompts = _prompts([10, 27])                 # buckets 16 and 32
    assert {pred.prompt_bucket(len(p)) for p in prompts} == {16, 32}
    diff, gap = _logit_diff(pred, state, prompts)
    assert diff <= TOL_LOGITS and gap <= 2 * TOL_LOGITS, (diff, gap)


@pytest.mark.parametrize("what", ["weights", "cache"])
def test_bf16_storage_fails_the_tolerance(opened, tmp_path, what):
    pred, state = opened
    if what == "weights":
        rounded = {n: np.asarray(jnp.asarray(v).astype(jnp.bfloat16)
                                 .astype(jnp.float32))
                   for n, v in pred._state_host.items()}
        pred = GenerativePredictor(save_decode_model(
            str(tmp_path / "bf16"), rounded, pred.meta))
    diff, _ = _logit_diff(pred, state, _prompts([10, 27]),
                          spoil_cache=(what == "cache"))
    assert diff > 10 * TOL_LOGITS, diff


def test_int8_cache_runs_the_block_and_stays_near_fp32(artifact, opened):
    _, state = opened
    pred = GenerativePredictor(artifact, kv_cache_dtype="int8")
    diff, _ = _logit_diff(pred, state, _prompts([10, 27]))
    assert TOL_LOGITS < diff < 0.5, diff


# (b) the batcher and the wire, streams joining and leaving ---------------

def _held_to_reference_top1(state, meta, prompt, out):
    seq = list(prompt) + list(out)
    want = _ref_logits(state, seq, meta)
    n = len(prompt)
    return max(float(want[n - 1 + t].max() - want[n - 1 + t, tok])
               for t, tok in enumerate(out))


def test_batcher_streams_join_and_leave(opened):
    pred, state = opened
    b = DecodeBatcher(pred, n_slots=2)
    prompts = _prompts([3, 12, 5, 20, 2, 9], seed=5)
    budgets = [7, 3, 9, 2, 6, 5]
    try:
        streams = [b.submit([int(t) for t in p], max_new_tokens=m)
                   for p, m in zip(prompts, budgets)]
        outs = [s.result(timeout=120)[0].tolist() for s in streams]
    finally:
        b.close()
    for p, m, out in zip(prompts, budgets, outs):
        assert 1 <= len(out) <= m
        assert _held_to_reference_top1(state, pred.meta, p, out) \
            <= 2 * TOL_LOGITS
    assert b.slot_occupancy() == (0, 2)


def test_served_through_the_wire_with_the_default_placement(artifact,
                                                            opened):
    pred, state = opened
    server = InferenceServer().start()
    boot = ServingClient(server.endpoint)
    prompts = _prompts([4, 18, 7], seed=9)
    outs, errs = [None] * 3, []
    try:
        boot.load_model("olmoe", artifact, decode_slots=2)

        def worker(i):
            cli = ServingClient(server.endpoint)
            try:
                outs[i] = [t for c in cli.infer_stream(
                    "olmoe", prompts[i], max_new_tokens=5 + i,
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
        for p, out in zip(prompts, outs):
            assert _held_to_reference_top1(state, pred.meta, p, out) \
                <= 2 * TOL_LOGITS
    finally:
        boot.close()
        server.shutdown(drain=True)


# (c) the routed FFN alone against dense-and-mask --------------------------

def _dense_and_mask(h, router, wg, wu, wd, k, norm):
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(h @ router, axis=-1)
        _, idx = jax.lax.top_k(p, k)
        w = p * jnp.sum(jax.nn.one_hot(idx, p.shape[1]), axis=1)
        if norm:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        act = jax.nn.silu(jnp.einsum("td,edf->tef", h, wg)) \
            * jnp.einsum("td,edf->tef", h, wu)
        return jnp.einsum("tef,efd->td", act * w[:, :, None], wd), w


@pytest.mark.parametrize("tokens,one_expert,norm", [
    (3, False, False), (40, False, False), (40, True, False),
    (5, False, True)])
def test_routed_ffn_is_dropless_and_exact(tokens, one_expert, norm):
    D, E, F, k = 32, 8, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(tokens), 5)
    h = jax.random.normal(ks[0], (tokens, D))
    router = jax.random.normal(ks[1], (D, E)) / np.sqrt(D)
    wg, wu = (jax.random.normal(kk, (E, D, F)) / np.sqrt(D)
              for kk in ks[2:4])
    wd = jax.random.normal(ks[4], (E, F, D)) / np.sqrt(F)
    if one_expert:
        # every token's largest router logit is expert 5's: dropless means
        # expert 5 takes all 40 rows and none is lost
        h = jnp.abs(h)
        router = router.at[:, 5].set(1.0)
    got, facts = jax.jit(dec.moe_ffn, static_argnums=(5, 6))(
        h, router, wg, wu, wd, k, norm)
    want, w = _dense_and_mask(h, router, wg, wu, wd, k, norm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)
    per_expert = np.asarray(jnp.sum(w > 0, axis=0))
    assert int(per_expert.sum()) == tokens * k          # none dropped
    if one_expert:
        assert per_expert[5] == tokens
    # (the third: the pairs that stayed here, all of them without `held`)
    assert facts.tolist() == [int((per_expert > 0).sum()),
                              int(per_expert.max()), tokens * k]


# (d) the routing counters against counts made by hand ---------------------

def test_routing_counters_ride_the_fetch(opened):
    pred, state = opened
    L, k = TINY["n_layers"], OLMOE_BLOCK["experts_per_token"]
    set_flags({"trace": True})
    obs_tracing.clear()
    try:
        sess = pred.new_session(3)
        prompt = _prompts([9])[0]
        sess.prefill(1, prompt)
        pre = sess.last_routing.copy()
        sess.decode()
        step = sess.last_routing.copy()
        fetches = [s for s in obs_tracing.recent_spans()
                   if s["name"] == "decode/fetch"]
    finally:
        set_flags({"trace": False})
    # by hand: the reference's router over the prompt, layer by layer
    x = reference.embed(state["embed"], jnp.asarray(prompt))
    for i in range(L):
        w = {n: state["l%d_%s" % (i, n)] for n in reference.LAYER_WEIGHTS}
        with jax.default_matmul_precision("highest"):
            h = reference._rms(reference.layer(x, dict(
                w, w_down=jnp.zeros_like(w["w_down"])), pred.meta)[0],
                w["ln2_g"], 1e-5)
            _, idx = jax.lax.top_k(jax.nn.softmax(h @ w["router"]), k)
        counts = np.bincount(np.asarray(idx).reshape(-1), minlength=8)
        assert pre[i].tolist() == [int((counts > 0).sum()),
                                   int(counts.max())]
        x = reference.layer(x, w, pred.meta)[0]
    # the step: ONE live slot of three, so k experts a layer, one token each
    assert step.tolist() == [[k, 1]] * L
    by_phase = {s["attrs"]["phase"]: s["attrs"] for s in fetches}
    assert by_phase["prefill"]["moe_experts_touched"] == int(pre[:, 0].sum())
    assert by_phase["prefill"]["moe_tokens_per_expert_max"] \
        == int(pre[:, 1].max())
    assert by_phase["step"]["moe_experts_touched"] == k * L
    assert by_phase["step"]["moe_tokens_per_expert_max"] == 1
    # everything a dispatch returns came in ONE int32 vector: the step
    # window's token block, 3 counts, the trips, 3 facts per layer
    from paddle_tpu.inference.decode import STEP_WINDOW
    assert by_phase["step"]["d2h_bytes"] \
        == 4 * (3 * STEP_WINDOW + 3 + 1 + 3 * L)
    assert by_phase["step"]["moe_pairs_held"] == k * L
    assert by_phase["step"]["trips"] == 1


def test_default_block_step_returns_what_it_did(tmp_path):
    pred = GenerativePredictor(build_tiny_decode_model(
        str(tmp_path / "gpt"), vocab_size=32, d_model=16, n_heads=2,
        n_layers=2, max_seq_len=32))
    set_flags({"trace": True})
    obs_tracing.clear()
    try:
        sess = pred.new_session(2)
        sess.prefill(0, [3, 4, 5])
        assert sess.decode().shape == (2,)
        attrs = [s["attrs"] for s in obs_tracing.recent_spans()
                 if s["name"] == "decode/fetch"]
    finally:
        set_flags({"trace": False})
    assert pred.routed_layers == 0 and sess.last_routing is None
    assert all("moe_experts_touched" not in a for a in attrs)
    # a prefill's token; a step's window block, 2 counts and the trips
    from paddle_tpu.inference.decode import STEP_WINDOW
    assert [a["d2h_bytes"] for a in attrs] \
        == [4, 4 * (2 * STEP_WINDOW + 2 + 1)]


# (e) every phase runs the block: `_block` is the one decoder layer ---------

@pytest.fixture(scope="module")
def other_draft(tmp_path_factory):
    """A draft that disagrees with the target: a GPT-2-shaped block of
    the same vocabulary, so one fused round traces both kinds of block."""
    d = str(tmp_path_factory.mktemp("draft") / "lm")
    return GenerativePredictor(build_tiny_decode_model(
        d, **dict(TINY, n_layers=1, seed=5)))


def _spec_streams(target, draft, prompts, n, fused):
    """`n` tokens a prompt through speculative rounds of depth 3, the
    session left open for the caller to look at its tables."""
    sp = dec.SpeculativeDecodeSession(target, draft, len(prompts), 3)
    streams = [[sp.prefill(i, p)] for i, p in enumerate(prompts)]
    while min(len(s) for s in streams) < n:
        g, counts = sp.step(fused=fused)
        for i, s in enumerate(streams):
            s.extend(int(t) for t in g[i, :counts[i]])
    assert not sp.degraded, sp.degrade_error
    return sp, [s[:n] for s in streams]


@pytest.mark.parametrize("phase", ["fused_window", "spec", "spec_fused",
                                   "other_draft", "other_draft_fused"])
def test_every_phase_runs_the_block_and_keeps_the_plain_stream(
        opened, other_draft, phase):
    """Token for token against `decode()`: on the CPU a chunk of C
    positions through the routed FFN (C x N rows sorted by expert) rounds
    like C single steps, so no logit tolerance is needed here."""
    pred = opened[0]
    prompts, n = _prompts([9, 4, 12], seed=8), 12
    plain = []
    for p in prompts:
        sess = pred.new_session(1)
        plain.append([sess.prefill(0, p)]
                     + [int(sess.decode()[0]) for _ in range(n - 1)])
    if phase == "fused_window":
        sess = pred.new_session(4)               # slot 3 stays free
        first = [sess.prefill(i, p) for i, p in enumerate(prompts)]
        got = [[f] for f in first]
        while len(got[0]) < n:                   # windows of 8 and of 3
            toks, counts, trips = sess.decode_fused(n - len(got[0]))
            assert trips == min(n - len(got[0]), dec.STEP_WINDOW) \
                and counts.tolist() == [trips] * 3 + [0]
            for i, g in enumerate(got):
                g.extend(toks[i, :trips].tolist())
        assert got == plain
        assert sess.slot_is_zero(3)
        return
    twin = not phase.startswith("other_draft")
    sp, streams = _spec_streams(pred, pred if twin else other_draft,
                                prompts, n, fused=phase.endswith("fused"))
    assert streams == plain
    if twin:
        assert sp.accepted == sp.proposed > 0
    else:
        assert sp.accepted < sp.proposed
    # a rejected suffix leaves nothing behind: zeros from the committed
    # length on, in the target's tables and in the draft's
    for s in (sp.session, sp.draft_session):
        for table in (np.asarray(s._kc), np.asarray(s._vc)):
            for i, length in enumerate(s.lengths):
                assert table[:, i, :length].any()
                assert not table[:, i, length:].any()
    for i in range(len(prompts)):
        sp.free(i)
        assert sp.slot_is_zero(i) and sp.draft_session.slot_is_zero(i)


def test_tp_lane_refuses_instead_of_falling_back(artifact):
    from paddle_tpu.parallel.mesh import MeshGroup
    set_flags({"mesh_tp": True})
    try:
        with pytest.raises(NotImplementedError, match="mesh_tp.*norm="):
            GenerativePredictor(artifact,
                                device=MeshGroup(jax.devices()[:2]))
    finally:
        set_flags({"mesh_tp": False})


def test_unknown_block_value_and_missing_weight_are_typed_errors(tmp_path):
    with pytest.raises(ValueError, match="norm='batchnorm'"):
        dec.block_of({"norm": "batchnorm"})
    meta = dict(OLMOE_BLOCK, vocab_size=8, d_model=8, n_heads=2, n_layers=1,
                max_seq_len=8, eos_id=0)
    state = {n: np.zeros(s, np.float32)
             for n, s in dec.decode_state_shapes(meta).items()}
    del state["l0_router"]
    with pytest.raises(ValueError, match="l0_router"):
        save_decode_model(str(tmp_path / "x"), state, meta)


# (f) the block is in the fingerprint --------------------------------------

def test_block_keys_reach_the_fingerprint(tmp_path):
    preds = []
    for i, theta in enumerate((10000.0, 500000.0)):
        preds.append(GenerativePredictor(build_tiny_decode_model(
            str(tmp_path / str(i)), block=dict(OLMOE_BLOCK, rope_theta=theta),
            **TINY)))
    a, b = (p._fingerprint(("step", 2), p._step_specs(2)) for p in preds)
    assert a["state"] == b["state"] and a["args"] == b["args"]
    assert a["block"] != b["block"]
    assert dict(a["block"])["rope_theta"] == 10000.0
    # and on its own, with the model's hash held equal
    b2 = dict(b, model=a["model"])
    from paddle_tpu import compile_cache as cc
    assert cc.fingerprint_key(a) != cc.fingerprint_key(b2)


# ---------------------------------------------------------------------------
# a window's records (PR 29): what the step's dispatch stamps and returns
# when it runs several trips, and what the benchmark's readers make of it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trips", [1, 5])
def test_a_window_reports_what_its_trips_touched(opened, trips):
    """`trips` one-trip dispatches against ONE dispatch of `trips`: the
    same tokens, `moe_experts_touched` the sum over the trips (and the
    layers), `moe_tokens_per_expert_max` the largest; the window's three
    `decode/*` spans are `phase="step"` and carry the trips it ran."""
    pred = opened[0]
    prompts = _prompts([9, 4, 12], seed=21)

    def admitted():
        sess = pred.new_session(4)               # slot 3 stays free
        for i, p in enumerate(prompts):
            sess.prefill(i, p)
        return sess
    one = admitted()
    steps, facts = [], []
    for _ in range(trips):
        steps.append(one.decode()[:3].tolist())
        facts.append(one.last_routing.copy())
    set_flags({"trace": True})
    obs_tracing.clear()
    try:
        win = admitted()
        obs_tracing.clear()
        toks, counts, ran = win.decode_fused(trips)
        spans = {s["name"]: s["attrs"] for s in obs_tracing.recent_spans()
                 if s["name"].startswith("decode/")}
    finally:
        set_flags({"trace": False})
    assert ran == trips and counts.tolist() == [trips] * 3 + [0]
    assert toks[:3].T.tolist() == steps
    facts = np.stack(facts)                                 # [trips, L, 2]
    assert win.last_routing.tolist() == np.stack(
        [facts[:, :, 0].sum(axis=0), facts[:, :, 1].max(axis=0)],
        axis=1).tolist()
    assert set(spans) == {"decode/put", "decode/launch", "decode/fetch"}
    assert {a["phase"] for a in spans.values()} == {"step"}
    assert {a["trips"] for a in spans.values()} == {trips}
    assert spans["decode/fetch"]["moe_experts_touched"] \
        == int(facts[:, :, 0].sum())
    assert spans["decode/fetch"]["moe_tokens_per_expert_max"] \
        == int(facts[:, :, 1].max())


def test_a_slot_that_stops_mid_window_keeps_its_rows_and_its_experts(opened):
    """Its K/V rows after the window are those of its own stop, bit for
    bit; the experts a window counts are those of the slots that ran each
    trip, and the neighbour's stream is unmoved
    (`tests/test_decode_window.py`)."""
    from tests.test_decode_window import a_slot_that_stops_sits_out_the_window
    a_slot_that_stops_sits_out_the_window(
        opened[0], _prompts([9, 4], seed=21), 2)


def test_benchmark_readers_read_a_lane_of_windows(opened):
    """The rehearsed tiny cell's spans (a closed loop over a full lane,
    so the lane runs windows) through the benchmark's own readers:
    `moe_ffn_roofline` finds its `phase="step"` fetches with the summed
    routing facts and reads a share between 0 and 100, not `null`;
    `decode_trips_per_dispatch` reads more than one trip a dispatch and
    `decode_launch_ms_per_round` finds the dispatches' launches."""
    import time
    from benchmark import run as bench_run
    from benchmark import xplane
    pred = opened[0]
    set_flags({"trace": True})
    obs_tracing.clear()
    b = DecodeBatcher(pred, n_slots=2)
    t0 = time.monotonic()
    try:
        with b._cv:
            streams = [b.submit(p, max_new_tokens=20)
                       for p in _prompts([9, 4, 12, 7], seed=4)]
        for s in streams:
            s.result(timeout=120)
    finally:
        b.close()
        set_flags({"trace": False})
    t1 = time.monotonic()
    off = time.time() - time.monotonic()
    spans = [{"name": s["name"], "t0": s["ts"] - off,
              "t1": s["ts"] - off + s["dur_ms"] * 1e-3,
              "attrs": s.get("attrs", {})}
             for s in obs_tracing.recent_spans()]
    steps = [s for s in spans if s["name"] == "serving/decode_step"]
    assert max(s["attrs"]["trips"] for s in steps) > 1
    # a device that spent a third of every dispatch in the routed FFN
    ops = [("%fusion.3 = f32[4,64] fusion(...)", s["t0"] - t0,
            s["t0"] - t0 + (s["t1"] - s["t0"]) / 3.0) for s in steps]
    trace = xplane.Trace({0: ops})
    trace.anchor = (0.0, 0.0, t0)
    run = {"window": (t0, t1), "trace_window_monotonic": (t0, t1),
           "slots": 2, "scope_ops": {"moe_ffn": ["fusion.3"]},
           "device_kind": "TPU v5 lite",
           "meta": dict(pred.meta, **pred._block_meta)}
    share = bench_run.load_reader("moe_ffn_roofline")(spans, trace, run)
    assert share is not None and 0.0 < share < 100.0
    # the cost is linear in tokens and in experts touched: the reader's
    # numerator is what the same rounds cost dispatched one trip at a time
    from benchmark import costs_moe, peaks
    L = pred.meta["n_layers"]
    fetch = {s["attrs"]["round"]: s["attrs"] for s in spans
             if s["name"] == "decode/fetch"
             and s["attrs"].get("phase") == "step"}
    bytes_ = sum(L * costs_moe.moe_ffn_cost(
        s["attrs"]["tokens"],
        fetch[s["attrs"]["round"]]["moe_experts_touched"] / float(L),
        pred.meta["d_model"], 32, 8, 2)[1] for s in steps)
    busy = sum(e - a for _, a, e in ops)
    assert share == pytest.approx(
        100.0 * bytes_ / peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
        / busy, rel=1e-6)
    trips = bench_run.load_reader("decode_trips_per_dispatch")(
        spans, trace, run)
    assert trips == pytest.approx(
        sum(s["attrs"]["trips"] for s in steps) / float(len(steps)))
    assert 1.0 < trips <= dec.STEP_WINDOW
    assert bench_run.load_reader("decode_launch_ms_per_round")(
        spans, trace, run) > 0.0
    # a program whose step is one decode step stamps no trips on its
    # fetches (the parent commit): each counts as one trip.  No step's
    # fetch at all: no reading
    bare = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                           if k != "trips"}) for s in spans]
    assert bench_run.load_reader("decode_trips_per_dispatch")(
        bare, trace, run) == 1.0
    assert bench_run.load_reader("decode_trips_per_dispatch")(
        [s for s in spans if s["name"] != "decode/fetch"], trace, run) is None
