"""Speculative decoding tests (SERVING.md "Speculative decoding",
paddle_tpu/inference/decode.py SpeculativeDecodeSession + the serving
DecodeBatcher's variable-accept lanes).

The load-bearing contracts, in rough dependency order:

* `DecodeSession.rollback(slot, n, last_token=)` leaves the slot
  BIT-IDENTICAL to one that never advanced — the primitive the draft
  sync is built on;
* the speculative stream is bit-identical to the fp32-only greedy
  stream: with a same-weights twin draft accept rate is exactly 1.0
  (any verify-vs-step numeric drift would reject a draft), with a
  mismatched draft accepts drop but tokens never change;
* nearly-full slots fall back to plain rounds (progress is never
  blocked), and a draft failure degrades the session to target-only
  decode within the same round, stream intact (`spec_degraded`);
* prefill prompts past every configured bucket fall through to an
  exact-length compile with a once-per-size warning (the Predictor
  batch-bucket overflow parity);
* serving wiring end to end: load_model(draft=, spec_k=) over the
  wire, drafts/accepts telemetry (stats, Prometheus, serving_top ACC%),
  draft+verify spans tiling serving/decode_step, the admission fit
  check covering target + draft together, and the verify executable
  riding the persistent compile cache.

Everything CPU-safe under JAX_PLATFORMS=cpu.
"""

import os
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.inference.decode import (DecodeSession,
                                         GenerativePredictor,
                                         SpeculativeDecodeSession,
                                         build_tiny_decode_model,
                                         greedy_decode,
                                         save_decode_model,
                                         set_draft_poison)
from paddle_tpu.serving import (DecodeBatcher, InferenceServer,
                                ServingClient, ServingMetrics,
                                set_dispatch_delay, set_draft_delay)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


@pytest.fixture(autouse=True)
def _clear_chaos():
    yield
    set_dispatch_delay(0.0)
    set_draft_delay(0.0)
    set_draft_poison(None)


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("spec_model") / "lm")
    build_tiny_decode_model(d, vocab_size=32, d_model=16, n_heads=2,
                            n_layers=2, max_seq_len=64, eos_id=0,
                            seed=7)
    return d


@pytest.fixture(scope="module")
def other_artifact(tmp_path_factory):
    """Same vocab/eos/geometry family, DIFFERENT weights — the
    low-accept draft."""
    d = str(tmp_path_factory.mktemp("spec_model_alt") / "lm2")
    build_tiny_decode_model(d, vocab_size=32, d_model=16, n_heads=2,
                            n_layers=1, max_seq_len=64, eos_id=0,
                            seed=101)
    return d


@pytest.fixture(scope="module")
def predictor(artifact):
    return GenerativePredictor(artifact)


def _drain_spec(sess, prompts, max_new, fused=False):
    """Drive a SpeculativeDecodeSession to completion for `prompts`
    (slot i = prompt i); returns the per-prompt token streams with the
    same per-token EOS/max-new cuts the serving loop applies.  `fused`
    runs every round through the single-dispatch fused program."""
    eos = sess.predictor.eos_id
    streams = {i: [sess.prefill(i, p)] for i, p in enumerate(prompts)}
    done = {i for i, s in streams.items()
            if s[-1] == eos or len(s) >= max_new}
    for i in done:
        sess.free(i)
    rounds = 0
    while len(done) < len(prompts):
        rounds += 1
        assert rounds < 500, "speculative session wedged"
        toks, counts = sess.step(fused=fused)
        for i in list(streams):
            if i in done:
                continue
            for j in range(int(counts[i])):
                streams[i].append(int(toks[i, j]))
                if streams[i][-1] == eos or len(streams[i]) >= max_new:
                    break
            if streams[i][-1] == eos or len(streams[i]) >= max_new:
                done.add(i)
                sess.free(i)
    return [streams[i] for i in range(len(prompts))]


# ---------------------------------------------------------------------------
# the rollback primitive
# ---------------------------------------------------------------------------

class TestRollback:
    def test_rollback_bit_identical_to_never_advanced(self, predictor):
        a = predictor.new_session(2)
        b = predictor.new_session(2)
        first_a = a.prefill(0, [3, 5, 7])
        first_b = b.prefill(0, [3, 5, 7])
        assert first_a == first_b
        for _ in range(3):
            a.decode()
        a.rollback(0, 3, last_token=first_b)
        # the whole slot table — cache bits, length pointers, pending
        # tokens — must equal the session that never advanced
        assert np.array_equal(np.asarray(a._kc), np.asarray(b._kc))
        assert np.array_equal(np.asarray(a._vc), np.asarray(b._vc))
        assert a.lengths.tolist() == b.lengths.tolist()
        assert a.last_tokens.tolist() == b.last_tokens.tolist()
        # and decode identically afterwards
        for _ in range(4):
            ta, tb = a.decode(), b.decode()
            assert int(ta[0]) == int(tb[0])

    def test_rollback_partial_keeps_prefix_rows(self, predictor):
        a = predictor.new_session(1)
        a.prefill(0, [3, 5, 7])
        t1 = int(a.decode()[0])
        kc_after_one = np.asarray(a._kc).copy()
        len_after_one = int(a.lengths[0])
        for _ in range(2):
            a.decode()
        a.rollback(0, 2, last_token=t1)
        assert int(a.lengths[0]) == len_after_one
        assert np.array_equal(np.asarray(a._kc), kc_after_one)

    def test_rollback_validation(self, predictor):
        a = predictor.new_session(1)
        a.prefill(0, [3, 5])
        with pytest.raises(ValueError):
            a.rollback(0, -1)
        with pytest.raises(ValueError):
            a.rollback(0, int(a.lengths[0]) + 1)
        # n=0 with a pin only retargets the pending token
        a.rollback(0, 0, last_token=9)
        assert int(a.last_tokens[0]) == 9


# ---------------------------------------------------------------------------
# the speculative session: bit-exactness is the whole contract
# ---------------------------------------------------------------------------

class TestSpeculativeSession:
    def test_twin_draft_full_accept_bit_exact(self, artifact,
                                              predictor):
        prompts = [[3, 5, 7], [9, 4]]
        refs = [greedy_decode(predictor, p, 24)[0] for p in prompts]
        draft = GenerativePredictor(artifact)
        sess = SpeculativeDecodeSession(predictor, draft, 2, spec_k=3)
        streams = _drain_spec(sess, prompts, 24)
        assert streams == refs
        # same weights -> the draft IS the sequential stream, so any
        # verify-vs-step numeric drift would show as a reject first
        assert sess.proposed > 0
        assert sess.accepted == sess.proposed
        assert sess.rounds > 0 and sess.plain_steps == 0

    def test_fused_round_twin_draft_bit_exact(self, artifact,
                                              predictor):
        """The fused speculative round (SERVING.md "Fused multi-step
        decode"): k draft steps + the batched verify + in-graph
        commit/rollback/catch-up compile into ONE dispatch.  Streams
        must equal the host-driven rounds AND the N=1 greedy oracle,
        with the twin draft accepting EXACTLY 1.0 — the bar that proves
        the in-graph bookkeeping moved no token."""
        prompts = [[3, 5, 7], [9, 4]]
        refs = [greedy_decode(predictor, p, 24)[0] for p in prompts]
        draft = GenerativePredictor(artifact)
        sess = SpeculativeDecodeSession(predictor, draft, 2, spec_k=3)
        streams = _drain_spec(sess, prompts, 24, fused=True)
        assert streams == refs
        assert sess.proposed > 0
        assert sess.accepted == sess.proposed, \
            "twin-draft accept under fusion must be exactly 1.0"
        assert sess.rounds > 0 and sess.plain_steps == 0

    def test_fused_round_uploads_neither_model(self, artifact,
                                               other_artifact):
        """The fused call hands both predictors' `_state` straight to
        the executable, outside `DecodeSession._call` and so under no
        `decode/launch` span: under the default placement every leaf
        it gets must already be a device array."""
        import jax
        from paddle_tpu.inference.decode import _host_nbytes
        target = GenerativePredictor(artifact)
        draft = GenerativePredictor(other_artifact)
        assert target.device is None and draft.device is None
        seen = []
        real = target.fused_spec_fn

        def spying(*a):
            fn = real(*a)

            def call(tstate, dstate, *rest):
                seen.append((tstate, dstate))
                return fn(tstate, dstate, *rest)
            return call

        target.fused_spec_fn = spying
        sess = SpeculativeDecodeSession(target, draft, 2, spec_k=2)
        sess.prefill(0, [11, 12, 13, 14])
        sess.prefill(1, [2])
        sess.step(fused=True)
        assert sess.last_spec and len(seen) == 1
        for state in seen[0]:
            leaves = list(state.values())
            assert leaves and _host_nbytes(leaves) == 0
            assert all(isinstance(v, jax.Array) and not v.committed
                       for v in leaves)

    def test_fused_round_mismatched_draft_rollback_bit_exact(
            self, artifact, other_artifact, predictor):
        """Fused rounds with a DISAGREEING draft: the in-graph rollback
        (stale draft rows zeroed, pointers rewound) must keep streams
        bit-exact, and the draft table must end IDENTICAL to the
        host-driven session's after the same rounds."""
        prompts = [[11, 12, 13, 14], [2]]
        refs = [greedy_decode(predictor, p, 16)[0] for p in prompts]
        draft = GenerativePredictor(other_artifact)
        sess = SpeculativeDecodeSession(predictor, draft, 2, spec_k=2)
        streams = _drain_spec(sess, prompts, 16, fused=True)
        assert streams == refs
        assert sess.accepted < sess.proposed

    def test_mismatched_draft_low_accept_still_bit_exact(
            self, artifact, other_artifact, predictor):
        prompts = [[11, 12, 13, 14], [2]]
        refs = [greedy_decode(predictor, p, 16)[0] for p in prompts]
        draft = GenerativePredictor(other_artifact)
        sess = SpeculativeDecodeSession(predictor, draft, 2, spec_k=2)
        streams = _drain_spec(sess, prompts, 16)
        assert streams == refs
        # a different model mostly disagrees — but tokens never moved
        assert sess.accepted < sess.proposed

    def test_near_full_slot_falls_back_to_plain_rounds(self, artifact,
                                                       predictor):
        draft = GenerativePredictor(artifact)
        sess = SpeculativeDecodeSession(predictor, draft, 1, spec_k=4)
        # prompt of 57 on a 64-cache: the first spec round (room 7)
        # fits, but it pushes the slot past room < k+1 — the session
        # must switch to plain rounds mid-stream and still finish
        # exactly
        prompt = (list(range(1, 30)) * 2)[:57]
        ref, _ = greedy_decode(predictor, prompt, 8)
        streams = _drain_spec(sess, [prompt], 8)
        assert streams[0] == ref
        assert sess.plain_steps > 0, \
            "a nearly-full slot must decode via plain fallback rounds"

    def test_draft_poison_degrades_same_round_bit_exact(
            self, artifact, predictor):
        prompts = [[3, 5, 7], [9, 4]]
        refs = [greedy_decode(predictor, p, 20)[0] for p in prompts]
        draft = GenerativePredictor(artifact)
        sess = SpeculativeDecodeSession(predictor, draft, 2, spec_k=3)
        streams = {i: [sess.prefill(i, p)]
                   for i, p in enumerate(prompts)}
        toks, counts = sess.step()   # one healthy speculative round
        for i in streams:
            streams[i] += [int(toks[i, j])
                           for j in range(int(counts[i]))]
        set_draft_poison(0)
        rounds = 0
        while any(len(s) < 20 for s in streams.values()):
            rounds += 1
            assert rounds < 100
            toks, counts = sess.step()
            for i in streams:
                for j in range(int(counts[i])):
                    if len(streams[i]) < 20:
                        streams[i].append(int(toks[i, j]))
        assert sess.degraded
        assert "poison" in sess.degrade_error
        for i, r in enumerate(refs):
            assert streams[i] == r[:len(streams[i])] and \
                len(streams[i]) == 20

    def test_incompatible_draft_rejected(self, predictor, tmp_path):
        bad = str(tmp_path / "bad_vocab")
        build_tiny_decode_model(bad, vocab_size=16, d_model=16,
                                n_heads=2, n_layers=1, max_seq_len=64,
                                eos_id=0, seed=3)
        with pytest.raises(ValueError, match="vocab"):
            SpeculativeDecodeSession(predictor,
                                     GenerativePredictor(bad), 2, 2)
        short = str(tmp_path / "bad_len")
        build_tiny_decode_model(short, vocab_size=32, d_model=16,
                                n_heads=2, n_layers=1, max_seq_len=32,
                                eos_id=0, seed=3)
        with pytest.raises(ValueError, match="max_seq_len"):
            SpeculativeDecodeSession(predictor,
                                     GenerativePredictor(short), 2, 2)
        with pytest.raises(ValueError, match="spec_k"):
            SpeculativeDecodeSession(predictor, predictor, 2, 0)


# ---------------------------------------------------------------------------
# prefill bucket overflow: warn-once fall-through (Predictor parity)
# ---------------------------------------------------------------------------

class TestPrefillOverflowWarn:
    def test_overflow_warns_once_per_size_and_serves(self, tmp_path):
        # custom meta whose buckets stop well short of max_seq_len
        d = str(tmp_path / "smallbuckets")
        base = str(tmp_path / "base")
        build_tiny_decode_model(base, vocab_size=32, d_model=16,
                                n_heads=2, n_layers=1, max_seq_len=64,
                                eos_id=0, seed=5)
        from paddle_tpu.native import wire
        with open(os.path.join(base, "decode_state.bin"), "rb") as f:
            state = wire.decode(f.read())
        with open(os.path.join(base, "decode_meta.bin"), "rb") as f:
            meta = wire.decode(f.read())
        meta["prefill_buckets"] = [8]
        save_decode_model(d, state, meta)
        pred = GenerativePredictor(d)
        prompt = list(range(1, 13))   # 12 tokens > bucket 8
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert pred.prompt_bucket(12) == 12
            assert pred.prompt_bucket(12) == 12   # second call silent
        overflow = [x for x in w if "prefill" in str(x.message)]
        assert len(overflow) == 1, [str(x.message) for x in w]
        assert "12" in str(overflow[0].message)
        # and the fall-through actually serves, matching a same-length
        # decode on the untouched artifact (same weights)
        ref, _ = greedy_decode(GenerativePredictor(base), prompt, 4)
        got, _ = greedy_decode(pred, prompt, 4)
        assert got == ref
        with pytest.raises(ValueError, match="max_seq_len"):
            pred.prompt_bucket(65)


# ---------------------------------------------------------------------------
# the serving batcher: variable-accept lanes
# ---------------------------------------------------------------------------

class TestSpecBatcher:
    def test_spec_streams_bit_exact_join_leave(self, artifact,
                                               predictor):
        metrics = ServingMetrics().model("lm")
        draft = GenerativePredictor(artifact)
        b = DecodeBatcher(predictor, n_slots=2, metrics=metrics,
                          draft=draft, spec_k=2)
        try:
            prompts = [[3, 5, 7], [9, 4], [11, 12, 13, 14], [2],
                       [7, 7, 7]]
            budgets = [12, 7, 16, 9, 5]
            streams = [b.submit(p, max_new_tokens=n)
                       for p, n in zip(prompts, budgets)]
            outs = [s.result(timeout=120)[0].tolist() for s in streams]
            for p, n, out in zip(prompts, budgets, outs):
                assert out == greedy_decode(predictor, p, n)[0]
            snap = metrics.snapshot()
            assert snap["spec_rounds"] > 0
            assert snap["draft_tokens"] > 0
            assert snap["spec_accept_rate"] == 1.0
            assert snap["accept_rate"]["count"] == snap["spec_rounds"]
            assert snap["spec_degraded"] == 0
        finally:
            b.close(drain=False, timeout=5.0)

    def test_spec_rides_fused_batcher_bit_exact(self, artifact,
                                                predictor):
        """spec_k>0 + fuse_steps>1: the lane routes rounds through the
        fused spec program (one dispatch per round) and streams stay
        bit-exact with accept exactly 1.0 on the twin draft."""
        metrics = ServingMetrics().model("lm")
        draft = GenerativePredictor(artifact)
        b = DecodeBatcher(predictor, n_slots=2, metrics=metrics,
                          draft=draft, spec_k=2, fuse_steps=4)
        try:
            prompts = [[3, 5, 7], [9, 4], [11, 12, 13, 14]]
            budgets = [12, 7, 9]
            streams = [b.submit(p, max_new_tokens=n)
                       for p, n in zip(prompts, budgets)]
            outs = [s.result(timeout=120)[0].tolist() for s in streams]
            for p, n, out in zip(prompts, budgets, outs):
                assert out == greedy_decode(predictor, p, n)[0]
            snap = metrics.snapshot()
            assert snap["spec_rounds"] > 0
            assert snap["spec_accept_rate"] == 1.0
            assert snap["spec_degraded"] == 0
            assert snap["decode_dispatches"] > 0
        finally:
            b.close(drain=False, timeout=5.0)

    def test_draft_and_verify_spans_tile_decode_step(self, artifact,
                                                     predictor):
        from paddle_tpu.obs import tracing as obs_tracing
        if not obs_tracing.enabled():
            pytest.skip("tracing disabled")
        draft = GenerativePredictor(artifact)
        b = DecodeBatcher(predictor, n_slots=2, draft=draft, spec_k=2)
        try:
            b.submit([3, 5, 7], max_new_tokens=8).result(timeout=120)
        finally:
            b.close(drain=False, timeout=5.0)
        spans = obs_tracing.recent_spans(limit=4096, kind="serving")
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        assert by_name.get("serving/draft"), "no draft spans"
        assert by_name.get("serving/verify"), "no verify spans"
        steps = [s for s in by_name.get("serving/decode_step", [])]
        assert steps, "no decode_step spans"
        # the last round's draft + verify must tile its decode_step
        d, v, st = (by_name["serving/draft"][-1],
                    by_name["serving/verify"][-1], steps[-1])
        assert abs((d["dur_ms"] + v["dur_ms"]) - st["dur_ms"]) < 0.05, \
            (d["dur_ms"], v["dur_ms"], st["dur_ms"])
        assert d["attrs"]["spec_k"] == 2
        assert "accepted" in v["attrs"]

    def test_draft_death_degrades_with_event(self, artifact,
                                             predictor):
        from paddle_tpu.obs import events as obs_events
        metrics = ServingMetrics().model("lm")
        draft = GenerativePredictor(artifact)
        b = DecodeBatcher(predictor, n_slots=2, metrics=metrics,
                          draft=draft, spec_k=2)
        try:
            first = b.submit([3, 5, 7], max_new_tokens=6)
            first.result(timeout=120)
            set_draft_poison(0)
            out = b.submit([9, 4], max_new_tokens=10).result(
                timeout=120)[0].tolist()
            assert out == greedy_decode(predictor, [9, 4], 10)[0]
            snap = metrics.snapshot()
            assert snap["spec_degraded"] == 1
            ev = obs_events.recent_events(kind="spec_degraded")
            assert ev and "poison" in str(ev[-1].get("error"))
        finally:
            b.close(drain=False, timeout=5.0)


# ---------------------------------------------------------------------------
# registry + wire + admission fit + compile cache
# ---------------------------------------------------------------------------

class TestSpecServing:
    def test_wire_roundtrip_spec_fields_and_acc_column(self, artifact,
                                                       capsys):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import serving_top
        pred = GenerativePredictor(artifact)
        server = InferenceServer().start()
        cli = ServingClient(server.endpoint)
        try:
            r = cli.load_model("lm", artifact, decode_slots=2,
                               draft=artifact, spec_k=2)
            assert r["spec_k"] == 2 and r["draft"] == artifact
            got = [t for ch in cli.infer_stream(
                "lm", [3, 5, 7], max_new_tokens=10,
                deadline_ms=60000.0) for t in ch]
            assert got == greedy_decode(pred, [3, 5, 7], 10)[0]
            stats = cli.stats()
            snap = stats["stats"]["models"]["lm"]
            assert snap["spec_accept_rate"] == 1.0
            assert snap["spec_rounds"] > 0
            desc = stats["models"]["lm"]
            assert desc["spec_k"] == 2 and desc["draft"] == artifact
            txt = cli.metrics_text()
            assert "paddle_tpu_serving_spec_rounds" in txt
            assert "paddle_tpu_serving_spec_accept_rate" in txt
            serving_top.main([server.endpoint])
            out = capsys.readouterr().out
            assert "ACC%" in out and "spec_k=2" in out
            assert "100.0" in out
        finally:
            cli.close()
            server.shutdown(drain=True)

    def test_fit_check_covers_target_plus_draft(self, artifact):
        from paddle_tpu.analysis import ResourceFitError
        from paddle_tpu.serving import ModelRegistry
        from paddle_tpu import compile_cache as cc
        # size the budget so the target's KV table fits alone but
        # target + draft together do not: KV bytes dominate at large
        # slot counts (2*L*slots*S*H*Dh*4 = 32 MiB per model here)
        slots = 2048
        old = fluid.get_flags(["serving_device_mem_mb"])
        fluid.set_flags({"serving_device_mem_mb": 40})
        try:
            reg = ModelRegistry()
            before = cc.stats()
            with pytest.raises(ResourceFitError) as ei:
                reg.load_model("lm", artifact, decode_slots=slots,
                               draft=artifact, spec_k=2)
            assert "draft" in str(ei.value)
            # rejected BEFORE any build/compile work
            assert reg.model_names() == []
            delta = cc.stats_delta(before)
            assert delta["misses"] == 0 and delta["hits"] == 0, delta
            # without the draft the same placement fits
            entry = reg.load_model("lm", artifact, decode_slots=slots,
                                   warm=False)
            assert entry.batcher.spec_k == 0
            reg.close_all(drain=False, timeout=5.0)
        finally:
            fluid.set_flags(old)

    def test_verify_executable_rides_compile_cache(self, artifact,
                                                   tmp_path):
        from paddle_tpu import compile_cache as cc
        from paddle_tpu.serving import ModelRegistry
        old = fluid.get_flags(["compile_cache", "compile_cache_dir"])
        fluid.set_flags({"compile_cache": True,
                         "compile_cache_dir": str(tmp_path / "cc")})
        cc.reset_stats()
        try:
            reg = ModelRegistry()
            reg.load_model("lm", artifact, decode_slots=2,
                           draft=artifact, spec_k=2)
            cold = cc.stats()
            # prefill buckets + step + VERIFY on the target, prefill
            # buckets + step on the draft
            assert cold["misses"] >= 3, cold
            reg.close_all(drain=False, timeout=5.0)
            before = cc.stats()
            reg2 = ModelRegistry()
            reg2.load_model("lm", artifact, decode_slots=2,
                            draft=artifact, spec_k=2)
            delta = cc.stats_delta(before)
            assert delta["misses"] == 0, delta
            assert delta["hits"] >= cold["misses"], delta
            out = reg2.submit("lm", {"tokens": [5, 9, 3]},
                              max_new_tokens=6).result(timeout=120)
            ref, _ = greedy_decode(GenerativePredictor(artifact),
                                   [5, 9, 3], 6)
            assert out[0].tolist() == ref
            reg2.close_all(drain=False, timeout=5.0)
        finally:
            fluid.set_flags(old)
            cc.reset_stats()


# ---------------------------------------------------------------------------
# tools: bench sweep subprocess (the ci_checks `specdec` gate) + chaos
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_spec_bench_smoke_subprocess():
    """Fresh-process proof of the whole speculative lane: the --spec_k
    sweep's k>0 point must beat the k=0 baseline tokens/sec per slot
    at equal step cost, accept ~1.0 with the twin draft, bit-exact
    replay at every point.  Slow-marked (subprocess + open-loop load,
    the test_quantize bench-smoke precedent): the ci_checks.sh
    `specdec` gate runs it as its own tier — tier-1 covers the same
    path in-process via TestSpecBatcher/TestSpecServing."""
    import json
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_serving.py"),
         "--smoke", "--decode", "--decode_mode", "cb",
         "--decode_slots", "2", "--spec_k", "0,2",
         "--step_cost_ms", "20", "--qps", "20", "--duration", "3"],
        capture_output=True, text=True, timeout=600, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = [json.loads(l) for l in proc.stdout.splitlines()
            if l.startswith("{")]
    by_k = {r["spec_k"]: r for r in recs}
    assert set(by_k) == {0, 2}, sorted(by_k)
    for r in recs:
        assert r["bit_exact"] is True, r
        assert r["errors"] == 0, r
    assert by_k[2]["accept_rate"] == 1.0, by_k[2]
    assert by_k[2]["spec_degraded"] == 0
    assert by_k[2]["draft_cost_ms"] == pytest.approx(6.0)
    ratio = by_k[2]["tokens_per_sec_per_slot"] \
        / by_k[0]["tokens_per_sec_per_slot"]
    assert ratio > 1.1, \
        "spec_k=2 should beat the k=0 baseline (got %.2fx)" % ratio


@pytest.mark.slow
def test_chaos_spec_fallback_scenario():
    """The chaos scenario doubles as the draft-failure acceptance test
    (degrade within one step, zero dropped/corrupted streams); run it
    in-process — it asserts internally.  Slow-marked: the in-tier-1
    TestSpecBatcher.test_draft_death_degrades_with_event pins the same
    degrade contract in-process; `python tools/chaos.py --scenario
    spec-fallback` and this test cover the full wire shape."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import chaos
    res = chaos.scenario_spec_fallback(verbose=False)
    assert res["victim_tokens"] == 32
    assert res["accept_rate"] == 1.0
