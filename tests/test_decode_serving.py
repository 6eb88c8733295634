"""Continuous batching + streaming decode tests (SERVING.md
"Continuous batching & streaming", paddle_tpu/inference/decode.py,
serving DecodeBatcher + infer_stream).

The load-bearing contracts, in rough dependency order:

* the Pallas decode-attention kernel matches the plain-XLA oracle on
  the slot-cache shape (mixed live lengths, empty and full slots);
* greedy token streams are BIT-EXACT between a continuous batch with
  requests of mixed lengths joining and leaving mid-flight and a
  single-request non-batched DecodeSession — per-slot independence is
  exact, not approximate;
* slot recycling: a freed slot is ZEROED before reuse (no cross-request
  KV leakage) and more requests than slots all complete;
* streaming chunk ordering/completeness over the wire under concurrent
  clients; deadline eviction MID-DECODE with a typed error frame;
* prefill-bucket executables ride the persistent compile cache (a
  second load of the same artifact is all hits, zero fresh compiles).

Everything CPU-safe under JAX_PLATFORMS=cpu.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.inference.decode import (DecodeSession,
                                         GenerativePredictor,
                                         build_tiny_decode_model,
                                         greedy_decode)
from paddle_tpu.serving import (DeadlineExceeded, DecodeBatcher,
                                InferenceServer, ServerOverloaded,
                                ServingClient, ServingMetrics,
                                set_dispatch_delay)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


@pytest.fixture(autouse=True)
def _clear_chaos():
    yield
    set_dispatch_delay(0.0)


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("decode_model") / "lm")
    build_tiny_decode_model(d, vocab_size=32, d_model=16, n_heads=2,
                            n_layers=2, max_seq_len=64, eos_id=0,
                            seed=7)
    return d


@pytest.fixture(scope="module")
def predictor(artifact):
    return GenerativePredictor(artifact)


# ---------------------------------------------------------------------------
# decode-attention kernel
# ---------------------------------------------------------------------------

class TestDecodeKernel:
    def test_kernel_matches_reference_mixed_lengths(self):
        from paddle_tpu.ops.pallas_kernels import (
            decode_attention, decode_attention_reference)
        rng = np.random.RandomState(3)
        N, S, H, D = 5, 32, 2, 8
        q = rng.randn(N, H, D).astype(np.float32)
        k = rng.randn(N, S, H * D).astype(np.float32)
        v = rng.randn(N, S, H * D).astype(np.float32)
        lengths = np.array([1, 7, 32, 13, 2], np.int32)
        ref = np.asarray(decode_attention_reference(q, k, v, lengths))
        for bkv in (8, 16, 32):
            out = np.asarray(decode_attention(q, k, v, lengths,
                                              block_kv=bkv))
            np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)

    def test_empty_slot_is_welldefined_and_isolated(self):
        """A length-0 (dead) slot must not disturb live slots' rows."""
        from paddle_tpu.ops.pallas_kernels import decode_attention
        rng = np.random.RandomState(4)
        N, S, H, D = 3, 16, 2, 8
        q = rng.randn(N, H, D).astype(np.float32)
        k = rng.randn(N, S, H * D).astype(np.float32)
        v = rng.randn(N, S, H * D).astype(np.float32)
        live = np.asarray(decode_attention(
            q, k, v, np.array([5, 9, 16], np.int32), block_kv=8))
        mixed = np.asarray(decode_attention(
            q, k, v, np.array([5, 0, 16], np.int32), block_kv=8))
        assert np.array_equal(live[0], mixed[0])
        assert np.array_equal(live[2], mixed[2])
        assert np.all(np.isfinite(mixed[1]))

    def test_block_config_resolution_and_tuning_record(self, tmp_path):
        from paddle_tpu.ops import attention_tuning as at
        old = fluid.get_flags(["flash_block_kv", "compile_cache_dir",
                               "attention_tune_cache"])
        fluid.set_flags({"flash_block_kv": 0,
                         "compile_cache_dir": str(tmp_path / "cc"),
                         "attention_tune_cache": ""})
        try:
            # heuristic: largest candidate <= 128 dividing S
            assert at.get_decode_config(64, 8, "float32") == 64
            # tuned entry wins over the heuristic
            at.record_decode(64, 8, "float32", 16)
            assert at.get_decode_config(64, 8, "float32") == 16
            # FLAGS override wins over the tuned entry
            fluid.set_flags({"flash_block_kv": 32})
            assert at.get_decode_config(64, 8, "float32") == 32
            # a non-dividing override degrades to None (XLA fallback)
            fluid.set_flags({"flash_block_kv": 48})
            assert at.get_decode_config(64, 8, "float32") is None
        finally:
            fluid.set_flags(old)


# ---------------------------------------------------------------------------
# DecodeSession: slot table, parity, zeroing
# ---------------------------------------------------------------------------

class TestDecodeSession:
    def test_join_leave_parity_bit_exact(self, predictor):
        """The acceptance contract: greedy tokens from a running batch
        with mixed-length requests joining and LEAVING mid-flight are
        bit-identical to single-request non-batched decode."""
        sess = predictor.new_session(4)
        prompts = {0: [5, 9, 3], 1: [1, 2, 3, 4, 5, 6, 7], 2: [31, 30]}
        outs = {i: [sess.prefill(i, p)] for i, p in prompts.items()}
        for _ in range(3):
            t = sess.decode()
            for i in prompts:
                outs[i].append(int(t[i]))
        sess.free(2)                       # leaves mid-batch
        outs[3] = [sess.prefill(3, [8, 8, 8, 8])]   # joins mid-batch
        for _ in range(5):
            t = sess.decode()
            for i in (0, 1, 3):
                outs[i].append(int(t[i]))
        for i, p in [(0, prompts[0]), (1, prompts[1]),
                     (3, [8, 8, 8, 8])]:
            ref, _ = greedy_decode(predictor, p, len(outs[i]))
            assert outs[i] == ref[:len(outs[i])], \
                "slot %d diverged from single-request decode" % i
        ref2, _ = greedy_decode(predictor, prompts[2], 4)
        assert outs[2] == ref2[:4]

    def test_freed_slot_is_zeroed_and_reusable(self, predictor):
        sess = predictor.new_session(2)
        sess.prefill(0, [5, 9, 3])
        for _ in range(4):
            sess.decode()
        assert not sess.slot_is_zero(0)
        sess.free(0)
        assert sess.slot_is_zero(0), \
            "freed slot still holds the previous request's KV"
        # reuse: same prompt in the recycled slot reproduces exactly
        ref, _ = greedy_decode(predictor, [4, 4], 5)
        out = [sess.prefill(0, [4, 4])]
        for _ in range(4):
            out.append(int(sess.decode()[0]))
        assert out == ref

    def test_admission_and_release_write_the_table_in_place(self, predictor):
        """prefill() and free() land their rows through `_slot_writers`
        with the table donated: the table a write was given is consumed
        (no second table in memory), K's donation leaves V alone (two
        buffers from the start), only the written slot changes, and the
        neighbour's stream is what it would be alone."""
        sess = predictor.new_session(3)
        assert sess._inplace and sess._kc is not sess._vc
        sess.prefill(1, [7, 2, 9])
        k_old, v_old = sess._kc, sess._vc
        # a copy: a zero-copy view of the buffer would pin it, and a
        # pinned buffer is copied and not donated
        before = np.array(k_old, copy=True)
        sess.prefill(0, [5, 9, 3])
        assert k_old.is_deleted() and v_old.is_deleted(), \
            "prefill copied the slot table instead of writing in place"
        after = np.array(sess._kc, copy=True)
        assert np.array_equal(after[:, 1:], before[:, 1:])
        assert after[:, 0].any() and not sess.slot_is_zero(0)
        k_old = sess._kc
        sess.free(0)
        assert k_old.is_deleted(), "free copied the slot table"
        assert sess.slot_is_zero(0) and not sess.slot_is_zero(1)
        assert np.array_equal(np.asarray(sess._kc)[:, 1:], before[:, 1:])
        ref, _ = greedy_decode(predictor, [7, 2, 9], 4)
        out = [int(sess.decode()[1]) for _ in range(3)]
        assert out == ref[1:4]

    def test_prompt_bucket_and_oversize_rejection(self, predictor):
        assert predictor.prompt_bucket(3) == 8
        assert predictor.prompt_bucket(8) == 8
        assert predictor.prompt_bucket(9) == 16
        # past the cache entirely still rejects; past every configured
        # bucket but inside the cache falls through with a warn-once
        # (tests/test_spec_decode.py pins the fall-through)
        with pytest.raises(ValueError, match="max_seq_len"):
            predictor.prompt_bucket(65)

    def test_eos_and_length_finish(self, predictor):
        toks, reason = greedy_decode(predictor, [5, 9, 3], 4)
        assert len(toks) == 4 and reason == "length"
        # eos finish: pick the token the model actually repeats as eos
        eos_tok = toks[-1]
        import tempfile
        d = tempfile.mkdtemp()
        build_tiny_decode_model(d, vocab_size=32, d_model=16,
                                n_heads=2, n_layers=2, max_seq_len=64,
                                eos_id=int(eos_tok), seed=7)
        p2 = GenerativePredictor(d)
        toks2, reason2 = greedy_decode(p2, [5, 9, 3], 50)
        assert reason2 == "eos"
        assert toks2[-1] == eos_tok and len(toks2) < 50

    def test_decode_logits_is_decode_plus_its_logits(self, predictor):
        """`decode_logits` advances exactly like `decode` and hands back
        the logits its tokens are the argmax of; forcing `last_tokens`
        afterwards replays another stream on this session (what
        chip_smoke.py holds the Mosaic step to its reference with)."""
        a, b = predictor.new_session(3), predictor.new_session(3)
        for sess in (a, b):
            sess.prefill(0, [5, 9, 3])
            sess.prefill(2, [7])
        stream = []
        for _ in range(4):
            want = a.decode()
            got, logits = b.decode_logits()
            assert logits.shape == (3, predictor.vocab_size)
            assert logits.dtype == np.float32
            assert np.array_equal(got[[0, 2]], want[[0, 2]])
            assert np.array_equal(logits.argmax(-1)[[0, 2]],
                                  want[[0, 2]])
            stream.append(want.copy())
        assert np.array_equal(a.lengths, b.lengths) and a.steps == b.steps
        # teacher forcing: a third session fed session a's tokens walks
        # the same logits whatever it would have sampled itself
        c = predictor.new_session(3)
        c.prefill(0, [5, 9, 3])
        c.prefill(2, [7])
        for want in stream:
            got, _ = c.decode_logits()
            assert np.array_equal(got[[0, 2]], want[[0, 2]])
            c.last_tokens[[0, 2]] = want[[0, 2]]

    def test_export_failure_raises_not_warns(self, artifact, tmp_path,
                                             monkeypatch):
        """A decode phase that cannot be exported is broken, not
        uncacheable: no warn-and-compile-another-way path hides it."""
        import warnings
        from jax import export as jax_export

        def boom(*a, **k):
            raise RuntimeError("lowering refused")

        monkeypatch.setattr(jax_export, "export", boom)
        old = fluid.get_flags(["compile_cache_dir"])
        fluid.set_flags({"compile_cache_dir": str(tmp_path / "empty")})
        try:
            pred = GenerativePredictor(artifact)
            with warnings.catch_warnings():
                warnings.simplefilter("error")
                with pytest.raises(RuntimeError, match="lowering refused"):
                    pred.step_fn(3)
        finally:
            fluid.set_flags(old)

    def test_artifact_pins_prefill_buckets(self, tmp_path):
        d = str(tmp_path / "lm")
        build_tiny_decode_model(d, max_seq_len=64, prefill_buckets=(64, 16))
        p = GenerativePredictor(d)
        assert p.prefill_buckets() == (16, 64)
        assert p.prompt_bucket(5) == 16 and p.prompt_bucket(17) == 64


# ---------------------------------------------------------------------------
# DecodeBatcher: continuous batching semantics (in-process)
# ---------------------------------------------------------------------------

class TestDefaultPlacement:
    """The default placement (`device=None`, what `load_model` gives
    when nothing is said) keeps the weights on jax's default device,
    placed once at open and pinned nowhere: a launch uploads its small
    arguments, never the model."""

    def test_load_model_places_weights_once_and_uncommitted(
            self, artifact):
        import jax
        from paddle_tpu.obs import tracing as obs_tracing
        from paddle_tpu.serving import ModelRegistry
        n_slots = 2
        was = obs_tracing.enabled()
        obs_tracing.set_enabled(True)
        reg = ModelRegistry()
        try:
            entry = reg.load_model("lm", artifact, decode_slots=n_slots)
            pred = entry.predictor
            assert entry.devices == [None] and pred.device is None
            leaves = list(pred._state.values())
            assert leaves and len(leaves) == len(pred._state_host)
            assert all(isinstance(v, jax.Array) for v in leaves)
            assert not any(v.committed for v in leaves)
            assert pred.state_host_bytes() == 0
            obs_tracing.clear()
            out = reg.submit_stream("lm", [5, 9, 3],
                                    max_new_tokens=5).result(timeout=60)
            assert len(out[0]) >= 1
            launches = obs_tracing.recent_spans(name="decode/launch")
        finally:
            reg.close_all()
            obs_tracing.set_enabled(was)
        bucket = pred.prompt_bucket(3)
        small = {"prefill": 4 * bucket + 4,   # padded prompt, its length
                 # lengths, last_tokens, active, budget, max_trips
                 "step": 4 * n_slots + 4 * n_slots + n_slots
                 + 4 * n_slots + 4}
        by_phase = {}
        for s in launches:
            by_phase.setdefault(s["attrs"]["phase"], set()).add(
                s["attrs"]["h2d_bytes"])
        assert by_phase == {ph: {n} for ph, n in small.items()}

    @pytest.mark.parametrize("kv", ["float32", "int8"])
    @pytest.mark.parametrize(
        "other", ["opened_on_a_device", "cloned_to_a_device",
                  "cloned_back_to_default"])
    def test_same_tokens_and_logits_as_a_pinned_replica(
            self, artifact, other, kv):
        """Where the weights live moves no bit: streams and step logits
        of the default placement equal a pinned replica's and a
        clone's, for both cache widths."""
        import jax
        dev = jax.devices()[0]
        default = GenerativePredictor(artifact, kv_cache_dtype=kv)
        if other == "opened_on_a_device":
            peer = GenerativePredictor(artifact, device=dev,
                                       kv_cache_dtype=kv)
        elif other == "cloned_to_a_device":
            peer = default.clone_to(dev)
        else:
            peer = default.clone_to(dev).clone_to(None)
            assert not any(v.committed for v in peer._state.values())
        assert peer.kv_cache_dtype == default.kv_cache_dtype == kv
        assert default.state_host_bytes() == peer.state_host_bytes() == 0
        a, b = default.new_session(3), peer.new_session(3)
        for sess in (a, b):
            sess.prefill(0, [5, 9, 3])
            sess.prefill(2, [1, 2, 3, 4, 5, 6, 7])
        for _ in range(6):
            ta, la = a.decode_logits()
            tb, lb = b.decode_logits()
            assert np.array_equal(ta[[0, 2]], tb[[0, 2]])
            assert np.array_equal(la[[0, 2]], lb[[0, 2]])
        for prompt in ([5, 9, 3], [31, 30]):
            assert greedy_decode(default, prompt, 12) == \
                greedy_decode(peer, prompt, 12)


class TestDecodeBatcher:
    def test_slot_recycling_more_requests_than_slots(self, predictor):
        metrics = ServingMetrics().model("lm")
        b = DecodeBatcher(predictor, n_slots=2, metrics=metrics)
        rng = np.random.RandomState(0)
        reqs = [[int(x) for x in rng.randint(1, 32, size=n)]
                for n in (2, 5, 3, 7, 1, 4)]
        budgets = [6, 3, 9, 2, 5, 7]
        try:
            streams = [b.submit(p, max_new_tokens=m)
                       for p, m in zip(reqs, budgets)]
            outs = [s.result(timeout=60)[0].tolist() for s in streams]
        finally:
            b.close()
        for p, m, out in zip(reqs, budgets, outs):
            ref, _ = greedy_decode(predictor, p, m)
            assert out == ref, "recycled-slot stream diverged"
        assert metrics.streams.value == len(reqs)
        assert metrics.decode_tokens.value == sum(
            len(o) for o in outs)
        occupied, total = b.slot_occupancy()
        assert (occupied, total) == (0, 2)

    def test_deadline_evicts_mid_decode(self, predictor):
        """The PR 8 deadline fix: a stream past its deadline while
        GENERATING is evicted from its slot (typed error), and the slot
        serves the next request."""
        from paddle_tpu.obs import events as obs_events
        b = DecodeBatcher(predictor, n_slots=1)
        set_dispatch_delay(0.03)
        try:
            s = b.submit([5, 9, 3], max_new_tokens=200,
                         deadline=time.monotonic() + 0.2,
                         trace_id="dl-test")
            with pytest.raises(DeadlineExceeded):
                s.result(timeout=30)
            assert len(s.tokens) >= 1, \
                "expired before generating — not an in-decode eviction"
            ev = [e for e in obs_events.recent_events(
                kind="deadline_expired")
                if e.get("trace_id") == "dl-test"]
            assert ev and ev[-1].get("tokens", 0) >= 1
            set_dispatch_delay(0.0)
            # the slot is free and clean for the next stream
            ref, _ = greedy_decode(predictor, [4, 4], 5)
            nxt = b.submit([4, 4], max_new_tokens=5)
            assert nxt.result(timeout=60)[0].tolist() == ref
        finally:
            set_dispatch_delay(0.0)
            b.close()

    def test_cancel_frees_slot(self, predictor):
        b = DecodeBatcher(predictor, n_slots=1)
        set_dispatch_delay(0.02)
        try:
            s = b.submit([5, 9, 3], max_new_tokens=500)
            for _ in s.events(timeout=30):
                break  # first chunk arrived: mid-stream
            s.cancel()
            t0 = time.monotonic()
            while b.slot_occupancy()[0] and time.monotonic() - t0 < 10:
                time.sleep(0.005)
            assert b.slot_occupancy()[0] == 0, \
                "cancelled stream still pinned its slot"
        finally:
            set_dispatch_delay(0.0)
            b.close()

    def test_overload_sheds_lowest_priority_first(self, predictor):
        b = DecodeBatcher(predictor, n_slots=1, max_queue=2)
        set_dispatch_delay(0.05)
        try:
            keep = b.submit([1], max_new_tokens=50)       # occupies slot
            t0 = time.monotonic()
            while not b.slot_occupancy()[0] and \
                    time.monotonic() - t0 < 10:
                time.sleep(0.002)
            low = b.submit([2], max_new_tokens=2, priority=0)
            b.submit([3], max_new_tokens=2, priority=0)
            # queue full: a higher-priority arrival evicts `low`
            b.submit([4], max_new_tokens=2, priority=5)
            with pytest.raises(ServerOverloaded):
                low.result(timeout=5)
            # and an equal-priority arrival sheds itself
            with pytest.raises(ServerOverloaded):
                b.submit([5], max_new_tokens=2, priority=0)
            keep.cancel()
        finally:
            set_dispatch_delay(0.0)
            b.close()

    def test_static_mode_waits_for_whole_batch(self, predictor):
        """The bench baseline: a static lane admits only when idle, so
        a short request entering behind a long batch waits for ALL of
        it — the idle-slot cost continuous batching removes."""
        b = DecodeBatcher(predictor, n_slots=2, continuous=False)
        set_dispatch_delay(0.005)
        try:
            long1 = b.submit([1], max_new_tokens=40)
            long2 = b.submit([2], max_new_tokens=40)
            time.sleep(0.05)  # batch is running
            short = b.submit([3], max_new_tokens=1)
            short.result(timeout=60)
            assert long1.done() and long2.done(), \
                "static mode admitted into a running batch"
        finally:
            set_dispatch_delay(0.0)
            b.close()


# ---------------------------------------------------------------------------
# wire streaming end-to-end
# ---------------------------------------------------------------------------

class TestServerStream:
    def test_three_concurrent_clients_ordered_complete_streams(
            self, artifact, predictor):
        """Acceptance: 3 concurrent streaming clients with different
        lengths; every client's concatenated chunks equal its
        single-request reference IN ORDER, with a final frame naming
        the finish reason."""
        server = InferenceServer().start()
        boot = ServingClient(server.endpoint)
        prompts = [[5, 9, 3], [1, 2, 3, 4, 5, 6, 7], [31, 30]]
        budgets = [9, 4, 12]
        outs = [None] * 3
        infos = [None] * 3
        errs = []
        try:
            boot.load_model("lm", artifact, decode_slots=2)

            def worker(i):
                cli = ServingClient(server.endpoint)
                try:
                    chunks = list(cli.infer_stream(
                        "lm", prompts[i], max_new_tokens=budgets[i],
                        deadline_ms=60000.0))
                    outs[i] = [t for c in chunks for t in c]
                    infos[i] = cli.last_stream_info
                except Exception as e:
                    errs.append(e)
                finally:
                    cli.close()

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not errs, errs[:3]
            for i in range(3):
                ref, reason = greedy_decode(predictor, prompts[i],
                                            budgets[i])
                assert outs[i] == ref, \
                    "client %d stream diverged: %s vs %s" \
                    % (i, outs[i], ref)
                assert infos[i]["finish_reason"] == reason
                assert infos[i]["new_tokens"] == len(ref)
                assert infos[i].get("trace_id")
        finally:
            boot.close()
            server.shutdown(drain=True)

    def test_chunk_grouping_and_oneshot_verb(self, artifact, predictor):
        server = InferenceServer().start()
        cli = ServingClient(server.endpoint)
        try:
            # one trip a dispatch: a window's tokens go out as one frame
            # however small the chunk asked for
            cli.load_model("lm", artifact, decode_slots=2, fuse_steps=1)
            ref, reason = greedy_decode(predictor, [5, 9, 3], 9)
            # grouped flush: every chunk <= 4 tokens, nothing lost
            chunks = list(cli.infer_stream("lm", [5, 9, 3],
                                           max_new_tokens=9,
                                           deadline_ms=60000.0,
                                           chunk_tokens=4))
            assert all(len(c) <= 4 for c in chunks)
            assert [t for c in chunks for t in c] == ref
            # one-shot verb on a decode model: whole greedy stream
            out = cli.infer("lm", {"tokens": np.array([5, 9, 3])},
                            max_new_tokens=9, deadline_ms=60000.0)
            assert out[0].tolist() == ref
            # stats carry the decode telemetry
            snap = cli.stats()["stats"]["models"]["lm"]
            assert snap["streams"] == 2
            assert snap["decode_tokens"] == 2 * len(ref)
            assert snap["ttft_ms"]["count"] == 2
            assert "slot_occupancy" in snap
            desc = cli.stats()["models"]["lm"]
            assert desc.get("decode") is True
            assert desc.get("decode_slots") == 2
        finally:
            cli.close()
            server.shutdown(drain=True)

    def test_stream_deadline_error_frame(self, artifact):
        server = InferenceServer().start()
        cli = ServingClient(server.endpoint)
        set_dispatch_delay(0.03)
        try:
            cli.load_model("lm", artifact, decode_slots=1)
            got = []
            with pytest.raises(DeadlineExceeded):
                for chunk in cli.infer_stream("lm", [5, 9, 3],
                                              max_new_tokens=300,
                                              deadline_ms=250.0):
                    got.extend(chunk)
            assert got, "typed error frame should follow streamed tokens"
        finally:
            set_dispatch_delay(0.0)
            cli.close()
            server.shutdown(drain=False, timeout=10.0)

    def test_client_disconnect_frees_slot(self, artifact):
        server = InferenceServer().start()
        boot = ServingClient(server.endpoint)
        set_dispatch_delay(0.02)
        try:
            boot.load_model("lm", artifact, decode_slots=1)
            victim = ServingClient(server.endpoint)
            it = victim.infer_stream("lm", [5, 9, 3],
                                     max_new_tokens=500)
            next(it)           # stream is live
            it.close()         # connection drops mid-stream
            victim.close()
            t0 = time.monotonic()
            while time.monotonic() - t0 < 10:
                snap = boot.stats()["stats"]["models"]["lm"]
                if snap.get("decode_slots_busy", 1) == 0:
                    break
                time.sleep(0.01)
            else:
                pytest.fail("slot still occupied after disconnect")
            set_dispatch_delay(0.0)
            # lane is not wedged: the freed slot serves new traffic
            out = boot.infer("lm", {"tokens": np.array([4, 4])},
                             max_new_tokens=3, deadline_ms=60000.0)
            assert len(out[0]) == 3
        finally:
            set_dispatch_delay(0.0)
            boot.close()
            server.shutdown(drain=False, timeout=10.0)

    def test_metrics_rpc_exports_decode_families(self, artifact):
        server = InferenceServer().start()
        cli = ServingClient(server.endpoint)
        try:
            cli.load_model("lm", artifact, decode_slots=2)
            list(cli.infer_stream("lm", [5, 9, 3], max_new_tokens=4,
                                  deadline_ms=60000.0))
            text = cli.metrics_text()
            for family in ("serving_decode_tokens_total",
                           "serving_tokens_per_sec",
                           "serving_slot_occupancy",
                           "serving_ttft_ms"):
                assert family in text, "missing %s in:\n%s" \
                    % (family, text[:2000])
        finally:
            cli.close()
            server.shutdown(drain=True)


# ---------------------------------------------------------------------------
# compile-cache warm hit for the decode phases
# ---------------------------------------------------------------------------

class TestDecodeCompileCache:
    def test_prefill_buckets_warm_hit_zero_fresh_compiles(
            self, artifact, tmp_path):
        from paddle_tpu import compile_cache as cc
        from paddle_tpu.serving import ModelRegistry
        old = fluid.get_flags(["compile_cache", "compile_cache_dir"])
        fluid.set_flags({"compile_cache": True,
                         "compile_cache_dir": str(tmp_path / "cc")})
        cc.reset_stats()
        try:
            reg = ModelRegistry()
            reg.load_model("lm", artifact, decode_slots=2)
            cold = cc.stats()
            assert cold["misses"] >= 2, \
                "cold load should compile+commit prefill buckets + step"
            reg.close_all()
            # second load of the same artifact: every decode-phase
            # executable deserializes from the store — zero fresh
            # compiles, same tokens
            before = cc.stats()
            reg2 = ModelRegistry()
            reg2.load_model("lm", artifact, decode_slots=2)
            delta = cc.stats_delta(before)
            assert delta["misses"] == 0, delta
            assert delta["hits"] >= cold["misses"], delta
            out = reg2.submit("lm", {"tokens": [5, 9, 3]},
                              max_new_tokens=4).result(timeout=60)
            pred = GenerativePredictor(artifact)
            ref, _ = greedy_decode(pred, [5, 9, 3], 4)
            assert out[0].tolist() == ref
            reg2.close_all()
        finally:
            fluid.set_flags(old)
            cc.reset_stats()

    def test_fingerprint_covers_weights_not_just_meta(self, tmp_path):
        """Two artifacts with IDENTICAL meta (same dims/vocab/eos) but
        different weights must never resolve each other's persisted
        executables: the int8 phases bake weight-derived kv scales as
        trace constants, so a meta-only fingerprint let a stale
        ("step", n) blob quantize one model's rows with another
        model's scales (the cross-artifact cache-poisoning bug the
        decode-disconnect-int8 chaos scenario caught)."""
        a = str(tmp_path / "seed21")
        b = str(tmp_path / "seed22")
        kw = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                  max_seq_len=64, eos_id=-1)
        build_tiny_decode_model(a, seed=21, **kw)
        build_tiny_decode_model(b, seed=22, **kw)
        pa = GenerativePredictor(a, kv_cache_dtype="int8")
        pb = GenerativePredictor(b, kv_cache_dtype="int8")
        assert pa.meta == pb.meta
        assert pa._model_fp != pb._model_fp
        # and the full phase fingerprints diverge too — the store can
        # never hand one model the other's baked-scale executable
        import jax
        spec = (jax.ShapeDtypeStruct((2, 2, 64, 4, 8),
                                     __import__("numpy").int8),)
        fpa = pa._fingerprint(("step", 2), spec)
        fpb = pb._fingerprint(("step", 2), spec)
        assert fpa != fpb
        # same artifact reopened: fingerprint is stable (warm reloads
        # keep deserializing)
        assert GenerativePredictor(
            a, kv_cache_dtype="int8")._model_fp == pa._model_fp


# ---------------------------------------------------------------------------
# fused multi-step decode (SERVING.md "Fused multi-step decode")
# ---------------------------------------------------------------------------

class TestFusedDecode:
    def test_fused_vs_single_step_churn_parity(self, predictor):
        """The fused acceptance contract: a batcher dispatching N=8
        steps per device call, with more requests than slots (joins and
        leaves land at window boundaries), streams BIT-IDENTICAL tokens
        to the single-step greedy oracle — and cuts dispatches ~N-fold
        (decode_dispatches + tokens_per_dispatch tell the story)."""
        metrics = ServingMetrics().model("lm")
        b = DecodeBatcher(predictor, n_slots=2, metrics=metrics,
                          fuse_steps=8)
        rng = np.random.RandomState(1)
        reqs = [[int(x) for x in rng.randint(1, 32, size=n)]
                for n in (2, 5, 3, 7, 1, 4)]
        budgets = [6, 3, 9, 2, 12, 7]
        try:
            streams = [b.submit(p, max_new_tokens=m)
                       for p, m in zip(reqs, budgets)]
            outs = [s.result(timeout=60)[0].tolist() for s in streams]
        finally:
            b.close()
        for p, m, out in zip(reqs, budgets, outs):
            ref, _ = greedy_decode(predictor, p, m)
            assert out == ref, "fused stream diverged from N=1 oracle"
        total = sum(len(o) for o in outs)
        assert metrics.decode_tokens.value == total
        dispatches = metrics.decode_dispatches.value
        assert dispatches >= 1
        # windows amortize: far fewer dispatches than tokens, and the
        # histogram saw every dispatch
        assert dispatches < total, (dispatches, total)
        assert metrics.tokens_per_dispatch.count == dispatches

    def test_fused_eos_early_exit_mid_window(self, predictor):
        """An EOS landing mid-window stops ITS slot in-graph: the
        dispatch returns the slot's own count (the EOS token itself its
        last), the other slot runs the window out, and the window ends
        once no slot is left alive; each stream equals the greedy
        oracle."""
        from paddle_tpu.inference.decode import STEP_WINDOW
        # pick an eos id whose FIRST occurrence in the greedy stream is
        # mid-window (index >= 4) so the early exit is provoked for
        # real, not at the prefill token
        probe, _ = greedy_decode(predictor, [5, 9, 3], 14)
        j = next(i for i in range(4, len(probe))
                 if probe[i] not in probe[:i])
        eos_tok = int(probe[j])
        import tempfile
        d = tempfile.mkdtemp()
        build_tiny_decode_model(d, vocab_size=32, d_model=16,
                                n_heads=2, n_layers=2, max_seq_len=64,
                                eos_id=eos_tok, seed=7)
        p2 = GenerativePredictor(d)
        ref, reason = greedy_decode(p2, [5, 9, 3], 50)
        assert reason == "eos" and len(ref) == j + 1
        # a lane in miniature: two streams, each cut and released at its
        # own EOS; every dispatch asks for a full window
        sess = p2.new_session(2)
        prompts = {0: [5, 9, 3], 1: [7, 2]}
        refs = {i: greedy_decode(p2, p, 50)[0] for i, p in prompts.items()}
        outs = {i: [sess.prefill(i, p)] for i, p in prompts.items()}
        live = {i for i, o in outs.items() if o[-1] != eos_tok}
        ran = []
        while live:
            # each live slot runs to its own EOS, the window's end at
            # most; the window runs while any of them is alive
            own = [min(STEP_WINDOW, len(refs[i]) - len(outs[i]))
                   if i in live else 0 for i in (0, 1)]
            toks, counts, trips = sess.decode_fused(STEP_WINDOW)
            assert (trips, counts.tolist()) == (max(own), own), ran
            for i in sorted(live):
                outs[i] += toks[i, :counts[i]].tolist()
                if outs[i][-1] == eos_tok:
                    sess.free(i)
                    live.discard(i)
            ran.append((trips, own))
        assert outs == refs, "fused EOS streams diverged: %s vs %s" \
            % (outs, refs)
        assert outs[0][-1] == eos_tok \
            and any(0 < min(own) < trips for trips, own in ran), \
            "no slot met its EOS while its neighbour ran on: %r" % ran

    def test_fused_warm_reload_all_hits(self, artifact, tmp_path):
        """The fused executables ride the persistent compile cache
        under their own fingerprints: a second fuse_steps>1 load is
        all hits, zero fresh compiles, same tokens."""
        from paddle_tpu import compile_cache as cc
        from paddle_tpu.serving import ModelRegistry
        old = fluid.get_flags(["compile_cache", "compile_cache_dir"])
        fluid.set_flags({"compile_cache": True,
                         "compile_cache_dir": str(tmp_path / "cc")})
        cc.reset_stats()
        try:
            reg = ModelRegistry()
            reg.load_model("lm", artifact, decode_slots=2,
                           fuse_steps=4)
            cold = cc.stats()
            assert cold["misses"] >= 2
            reg.close_all()
            before = cc.stats()
            reg2 = ModelRegistry()
            reg2.load_model("lm", artifact, decode_slots=2,
                            fuse_steps=4)
            delta = cc.stats_delta(before)
            assert delta["misses"] == 0, delta
            assert delta["hits"] >= cold["misses"], delta
            out = reg2.submit("lm", {"tokens": [5, 9, 3]},
                              max_new_tokens=6).result(timeout=60)
            pred = GenerativePredictor(artifact)
            ref, _ = greedy_decode(pred, [5, 9, 3], 6)
            assert out[0].tolist() == ref
            reg2.close_all()
        finally:
            fluid.set_flags(old)
            cc.reset_stats()

    def test_fused_deadline_overshoot_bounded(self, predictor):
        """The satellite bugfix: deadline checks only fire between
        dispatches, so the EWMA trip clamp must bound the overshoot to
        about ONE fused dispatch — and the deadline_expired event
        stamps `overshoot_ms`."""
        from paddle_tpu.obs import events as obs_events
        b = DecodeBatcher(predictor, n_slots=1, fuse_steps=4)
        try:
            # warm the fused executable first: the clamp guarantee is
            # about steady-state step cost, not the one-off compile
            b.submit([4, 4], max_new_tokens=8).result(timeout=60)
            set_dispatch_delay(0.03)
            s = b.submit([5, 9, 3], max_new_tokens=200,
                         deadline=time.monotonic() + 0.25,
                         trace_id="fdl-test")
            with pytest.raises(DeadlineExceeded):
                s.result(timeout=30)
            assert len(s.tokens) >= 1
            ev = [e for e in obs_events.recent_events(
                kind="deadline_expired")
                if e.get("trace_id") == "fdl-test"]
            assert ev, "no deadline_expired event"
            over = ev[-1].get("overshoot_ms")
            assert over is not None, "event missing overshoot_ms"
            # one fused dispatch is 4 x 30ms; generous host slack on
            # top still proves the clamp beat the unclamped window tail
            assert over <= 4 * 30.0 + 500.0, over
        finally:
            set_dispatch_delay(0.0)
            b.close()


# ---------------------------------------------------------------------------
# the lane picks each dispatch's window from its own slot table (PR 29)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def endless(tmp_path_factory):
    """A tiny model that never emits EOS: a stream ends by length alone,
    so the trips of every dispatch follow from the budgets."""
    d = str(tmp_path_factory.mktemp("endless_model") / "lm")
    build_tiny_decode_model(d, vocab_size=32, d_model=16, n_heads=2,
                            n_layers=2, max_seq_len=256, eos_id=-1,
                            seed=11)
    return GenerativePredictor(d)


def _dispatches(t0=None):
    """The `serving/decode_step` spans since `t0`, in dispatch order."""
    from paddle_tpu.obs import tracing as obs_tracing
    steps = [s for s in obs_tracing.recent_spans(name="serving/decode_step")
             if t0 is None or s["t0"] >= t0]
    return sorted(steps, key=lambda s: s["attrs"]["round"])


@pytest.fixture
def traced():
    from paddle_tpu.obs import tracing as obs_tracing
    was = obs_tracing.enabled()
    obs_tracing.set_enabled(True)
    obs_tracing.clear()
    yield obs_tracing
    obs_tracing.set_enabled(was)


# what the rule dispatches for streams admitted together: [(trips, [tokens
# a slot])] until all have ended
from tests.test_decode_window import _windows as _rule  # noqa: E402


@pytest.mark.parametrize("n_steps,budget,live,total", [
    # 2 layers x 2 blocks of 128 over S = 256.  Three trips: slot 0
    # attends under 127, 128, 129 positions (1, 1, 2 blocks), slot 1
    # under 6, 7, 8 (1, 1, 1), the idle slot 2 under 0 + 1 (1, 1, 1)
    (3, None, 2 * (4 + 3 + 3), 3 * 3 * 2 * 2),
    (1, None, 2 * (1 + 1 + 1), 1 * 3 * 2 * 2),
    # slot 0 stops with its second trip and sits the third out: under
    # 127 and 128 positions, then as an idle slot does (1, 1, 1), and
    # not under 129 (2 blocks), which it would have needed had it run
    (3, [2, 3, 0], 2 * (3 + 3 + 3), 3 * 3 * 2 * 2)])
def test_step_fetch_span_counts_the_kv_blocks_streamed(
        endless, traced, n_steps, budget, live, total):
    """`kv_blocks_live` / `kv_blocks_total` of a step's `decode/fetch`
    span against a count by hand (the kernel's rule, `kv_last_block`:
    ceil((lengths + t + 1) / block) blocks a slot, a trip and a layer,
    over the slot's OWN trips; one block a trip once it has stopped)."""
    sess = endless.new_session(3)
    assert sess._kv_block == 128 and sess._kc.shape[:3] == (2, 3, 256)
    sess.prefill(0, [1 + i % 30 for i in range(126)])
    sess.prefill(1, [5, 9, 3, 7, 2])
    traced.clear()
    _, counts, trips = sess.decode_fused(n_steps, budget=budget)
    assert trips == n_steps \
        and counts.tolist() == (budget or [trips, trips, 0])
    fetch, = [s for s in traced.recent_spans(name="decode/fetch")
              if s["attrs"]["phase"] == "step"]
    assert fetch["attrs"]["trips"] == trips
    assert fetch["attrs"]["kv_blocks_live"] == live
    assert fetch["attrs"]["kv_blocks_total"] == total


class TestWindowRule:
    @pytest.mark.parametrize("cap,max_new", [
        (None, (20, 13)), (None, (9, 9)), (4, (20, 13)), (None, (3, 30))])
    def test_every_slot_assigned_runs_to_the_last_end(
            self, endless, traced, cap, max_new):
        """Each dispatch runs min(cap, LARGEST remaining budget of the
        live slots) trips: a slot whose budget ends inside the window
        stops there and sits the rest out, so its last tokens come with
        the window's and the dispatch counts its own tokens only; a slot
        that is free afterwards (nothing queued to refill it) changes
        nothing for the stream that is left."""
        from paddle_tpu.inference.decode import STEP_WINDOW
        b = DecodeBatcher(endless, n_slots=2, fuse_steps=cap)
        assert b.fuse_steps == (cap or STEP_WINDOW)
        try:
            with b._cv:     # both admitted by ONE lane iteration
                streams = [b.submit([5, 9, 3], max_new_tokens=m)
                           for m in max_new]
            outs = [s.result(timeout=60)[0].tolist() for s in streams]
        finally:
            b.close()
        for m, out in zip(max_new, outs):
            assert out == greedy_decode(endless, [5, 9, 3], m)[0]
        steps = _dispatches()
        # the prefill emitted each stream's first token
        want = _rule([m - 1 for m in max_new], b.fuse_steps)
        assert [(s["attrs"]["trips"], s["attrs"]["tokens"])
                for s in steps] == [(t, sum(c)) for t, c in want]
        assert want[0][0] > 1 and sum(s["attrs"]["tokens"] for s in steps) \
            == sum(max_new) - 2

    def test_a_free_slot_runs_windows_too(self, endless, traced):
        """A slot free: the live streams still get windows (a dispatch
        of host work for every token is what they would pay otherwise),
        and a late joiner waits for the end of one window, no longer."""
        b = DecodeBatcher(endless, n_slots=3)
        try:
            with b._cv:
                first = [b.submit([5, 9, 3], max_new_tokens=12),
                         b.submit([7, 2], max_new_tokens=10)]
            for s in first:
                s.result(timeout=60)
        finally:
            b.close()
        steps = _dispatches()
        assert [(s["attrs"]["trips"], s["attrs"]["tokens"])
                for s in steps] == [(t, sum(c)) for t, c in
                                    _rule([11, 9], b.fuse_steps)] \
            == [(8, 16), (3, 4)]
        assert [s["attrs"]["slots"] for s in steps] == [2, 2]

    def test_cancel_inside_a_window_is_honoured_at_its_boundary(
            self, endless, traced):
        """A cancel that lands while a window runs frees the slot at
        that window's end: no dispatch BEGINS after the cancel but the
        housekeeping one that finds it."""
        set_dispatch_delay(0.02)        # a window of 8 lasts ~160 ms
        b = DecodeBatcher(endless, n_slots=1)
        try:
            s = b.submit([5, 9, 3], max_new_tokens=200)
            deadline = time.monotonic() + 30
            while len(s.tokens) < 9 and time.monotonic() < deadline:
                time.sleep(0.005)
            t_cancel = time.monotonic()
            s.cancel()
            while b.slot_occupancy()[0] and time.monotonic() < deadline:
                time.sleep(0.005)
            assert b.slot_occupancy()[0] == 0
            freed_after = time.monotonic() - t_cancel
        finally:
            set_dispatch_delay(0.0)
            b.close()
        from paddle_tpu.obs import tracing as obs_tracing
        off = time.time() - time.monotonic()
        began_after = [d for d in _dispatches()
                       if d["ts"] - off > t_cancel]
        assert len(began_after) <= 1, began_after
        # within the window that was running (8 x 20 ms) plus host slack
        assert freed_after < 8 * 0.02 + 0.5, freed_after

    def test_deadline_inside_a_window_overshoots_under_one_dispatch(
            self, endless, traced):
        """The governor at the built-in cap: the lane's EWMA step time
        clamps the trips of the dispatch that would cross the deadline,
        so the eviction lands within about one dispatch of it."""
        from paddle_tpu.obs import events as obs_events
        b = DecodeBatcher(endless, n_slots=1)
        try:
            b.submit([4, 4], max_new_tokens=10).result(timeout=60)
            set_dispatch_delay(0.03)
            s = b.submit([5, 9, 3], max_new_tokens=200,
                         deadline=time.monotonic() + 1.0,
                         trace_id="window-deadline")
            with pytest.raises(DeadlineExceeded):
                s.result(timeout=30)
        finally:
            set_dispatch_delay(0.0)
            b.close()
        ev = [e for e in obs_events.recent_events(kind="deadline_expired")
              if e.get("trace_id") == "window-deadline"]
        assert ev and ev[-1]["overshoot_ms"] is not None
        # one dispatch is at most 8 x 30 ms; the clamp keeps the last one
        # shorter than that
        assert ev[-1]["overshoot_ms"] <= 8 * 30.0 + 300.0, ev[-1]
        # the warm-up stream ran [8, 1]; then full windows while the
        # deadline is far, and a clamped one once the EWMA has seen the
        # step's cost
        trips = [d["attrs"]["trips"] for d in _dispatches()][2:]
        assert trips[0] == 8 and trips[-1] < 8, trips

    @pytest.mark.parametrize("n_slots", [2, 3])
    def test_churn_streams_equal_a_lane_pinned_to_one_trip(
            self, predictor, n_slots):
        """Joins and leaves over more requests than slots (EOS cuts
        among them): every stream of the lane that chooses its windows
        equals the stream of a lane pinned to one trip."""
        rng = np.random.RandomState(5)
        reqs = [[int(x) for x in rng.randint(1, 32, size=n)]
                for n in (2, 5, 3, 7, 1, 4, 6, 2, 3)]
        budgets = [6, 3, 19, 2, 12, 7, 25, 1, 9]
        outs, dispatches = {}, {}
        for cap in (None, 1):
            metrics = ServingMetrics().model("lm")
            b = DecodeBatcher(predictor, n_slots=n_slots, metrics=metrics,
                              fuse_steps=cap)
            try:
                streams = []
                for i, (p, m) in enumerate(zip(reqs, budgets)):
                    streams.append(b.submit(p, max_new_tokens=m))
                    if i % 3 == 2:
                        time.sleep(0.02)        # some join mid-flight
                outs[cap] = [s.result(timeout=60)[0].tolist()
                             for s in streams]
            finally:
                b.close()
            dispatches[cap] = metrics.decode_dispatches.value
            assert metrics.decode_tokens.value \
                == sum(len(o) for o in outs[cap])
        assert outs[None] == outs[1]
        # (how many dispatches each lane took depends on when the joins
        # land against its rounds; the rule's arithmetic is held by
        # `test_every_slot_assigned_runs_to_the_last_end`)
        assert min(dispatches.values()) >= 1


def test_fused_gate_smoke(artifact, predictor):
    """The ci_checks.sh `fused_decode` gate body (exit 17): a served
    stream of a lane whose window is pinned to 4 is BIT-EXACT vs the
    one-step greedy oracle and, with every slot of the lane assigned,
    the dispatch count amortizes (~N tokens per dispatch)."""
    server = InferenceServer().start()
    cli = ServingClient(server.endpoint)
    try:
        loaded = cli.load_model("lm", artifact, decode_slots=1,
                                fuse_steps=4)
        assert loaded.get("fuse_steps") == 4
        for prompt, budget in [([5, 9, 3], 12), ([1, 2, 3, 4], 9)]:
            ref, _ = greedy_decode(predictor, prompt, budget)
            out = [t for c in cli.infer_stream(
                "lm", prompt, max_new_tokens=budget,
                deadline_ms=60000.0) for t in c]
            assert out == ref, "fused served stream diverged"
        snap = cli.stats()["stats"]["models"]["lm"]
        assert snap["decode_dispatches"] >= 1
        tpd = snap["decode_tokens"] / float(snap["decode_dispatches"])
        assert tpd >= 2.0, \
            "tokens/dispatch %.2f — fusion not amortizing" % tpd
        desc = cli.stats()["models"]["lm"]
        assert desc.get("fuse_steps") == 4
    finally:
        cli.close()
        server.shutdown(drain=True)


# ---------------------------------------------------------------------------
# tools
# ---------------------------------------------------------------------------

def test_serving_top_renders_decode_columns(artifact, capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import serving_top
    server = InferenceServer().start()
    cli = ServingClient(server.endpoint)
    try:
        cli.load_model("lm", artifact, decode_slots=2)
        list(cli.infer_stream("lm", [5, 9, 3], max_new_tokens=4,
                              deadline_ms=60000.0))
        serving_top.main([server.endpoint])
        out = capsys.readouterr().out
        assert "TTFT95" in out and "TPS" in out and "OCC%" in out
        assert "TPD" in out
        assert "decode_slots=2" in out
    finally:
        cli.close()
        server.shutdown(drain=True)


def test_bench_serving_decode_smoke_subprocess():
    """Tier-1-adjacent proof of the whole decode lane in a fresh
    process: build artifact, serve, stream under open-loop load, JSON
    record with bit_exact=True."""
    import json
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_serving.py"),
         "--decode", "--smoke", "--duration", "3", "--qps", "6"],
        capture_output=True, text=True, timeout=600, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert lines, proc.stdout[-500:]
    rec = json.loads(lines[-1])
    assert rec["metric"] == "serving_decode"
    assert rec["mode"] == "cb"
    assert rec["ok"] > 0 and rec["errors"] == 0
    assert rec["bit_exact"] is True
    assert rec["tokens_per_sec"] > 0
    assert rec["ttft_p95_ms"] is not None


def test_chaos_decode_disconnect_scenario():
    """The chaos scenario doubles as the slot-reclaim + no-leakage
    acceptance test; run it in-process (it asserts internally)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import chaos
    res = chaos.scenario_decode_disconnect(verbose=False)
    assert res["freed_steps"] <= 6
    assert res["expired_tokens"] >= 1


def test_chaos_decode_disconnect_fused_scenario():
    """The fused-boundary chaos scenario: mid-window disconnects free
    at the next dispatch boundary, deadline overshoot is clamped to
    ~one fused dispatch with overshoot_ms stamped, reused slots stream
    bit-exact (it asserts internally)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import chaos
    res = chaos.scenario_decode_disconnect_fused(verbose=False)
    assert res["freed_steps"] <= 3 * res["fuse_steps"]
    assert res["overshoot_ms"] is not None


# ---------------------------------------------------------------------------
# the step updates the slot table in place (PR 27): the table is carried
# through the layers, its N new rows scattered, and every phase that takes
# it consumes it
# ---------------------------------------------------------------------------

ROUTED_BLOCK = {"norm": "rmsnorm", "norm_eps": 1e-5, "position": "rope",
                "rope_theta": 10000.0, "qk_norm": True, "ffn": "moe_swiglu",
                "n_experts": 8, "experts_per_token": 2, "expert_width": 32,
                "norm_topk_prob": False}
INPLACE_CASES = [(blk, kv) for blk in ("default", "routed")
                 for kv in ("float32", "int8")]


@pytest.fixture(scope="module")
def inplace_artifacts(tmp_path_factory):
    root = tmp_path_factory.mktemp("inplace")
    common = dict(vocab_size=48, d_model=32, n_heads=2, n_layers=2,
                  max_seq_len=32, eos_id=0, seed=5,
                  prefill_buckets=[8, 32])
    return {"default": build_tiny_decode_model(str(root / "d"), **common),
            "routed": build_tiny_decode_model(str(root / "r"),
                                              block=ROUTED_BLOCK, **common)}


@pytest.fixture(scope="module", params=INPLACE_CASES,
                ids=["%s-%s" % c for c in INPLACE_CASES])
def inplace_pred(request, inplace_artifacts):
    blk, kv = request.param
    return GenerativePredictor(inplace_artifacts[blk], kv_cache_dtype=kv)


def _tables(sess):
    # copies: a zero-copy view would pin the buffer, and a pinned buffer
    # is copied instead of donated
    return (np.array(sess._kc, copy=True), np.array(sess._vc, copy=True))


def _where_stack_step(pred, state, kc, vc, lengths, last_tokens, active):
    """The step as it was before PR 27, as an oracle: each layer selects
    its new row into a copy of the layer (`jnp.where` over [N, S, H * Dh],
    a position one flat row), the kernel reads that copy, and the layers
    are stacked back."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_kernels import decode_attention
    L, H, Dh, _ = pred._dims()
    S = kc.shape[2]
    x = state["embed"][last_tokens]
    if pred.block["position"] == "learned":
        x = x + state["pos"][lengths]
    wmask = ((jnp.arange(S)[None, :] == lengths[:, None])
             & active[:, None])[:, :, None]
    kcs, vcs = [], []
    for i in range(L):
        def attend(q, k_new, v_new, i=i):
            if pred._kv_quant:
                sc = pred._kv_scales[:, i]
                k_new = pred._quantize_kv(k_new, sc[0]).astype(jnp.int8)
                v_new = pred._quantize_kv(v_new, sc[1]).astype(jnp.int8)
            k_new, v_new = (t.reshape(t.shape[0], 1, -1)
                            for t in (k_new, v_new))
            kcs.append(jnp.where(wmask, k_new, kc[i]))
            vcs.append(jnp.where(wmask, v_new, vc[i]))
            return decode_attention(
                q, kcs[-1], vcs[-1], lengths + 1, scale=1.0 / np.sqrt(Dh),
                kv_scales=pred._kv_scales[:, i] if pred._kv_quant
                else None)
        x, _ = pred._block(state, i, x, lengths, attend, active)
    logits = pred._norm(x, state, "lnf") @ state["lm_head"]
    return logits, jnp.stack(kcs), jnp.stack(vcs)


class TestStepInPlace:
    def test_a_step_consumes_its_table(self, inplace_pred):
        """The table a step is given is donated (no second table): both
        tables are deleted after the call, and the session holds the
        results."""
        sess = inplace_pred.new_session(4)
        sess.prefill(1, [7, 2, 9])
        for step in (sess.decode, sess.decode_logits):
            k_old, v_old = sess._kc, sess._vc
            step()
            assert k_old.is_deleted() and v_old.is_deleted(), \
                "the step copied the slot table instead of updating it"
            assert sess._kc is not k_old and not sess._kc.is_deleted()
        both = int(sess._kc.nbytes) + int(sess._vc.nbytes)
        assert both == sess.cache_bytes() - (
            int(np.asarray(inplace_pred._kv_scales).nbytes)
            if inplace_pred._kv_quant else 0)

    def test_only_the_new_row_of_each_active_slot_changes(self,
                                                          inplace_pred):
        """After a step the table differs from the one before in row
        `lengths[n]` of each active slot and nowhere else: a slot never
        admitted and a freed slot stay exactly zero, and a slot at
        `lengths == S` (no room) is not touched at all."""
        pred = inplace_pred
        S = pred.max_seq_len
        sess = pred.new_session(5)
        sess.prefill(0, [3, 1, 4, 1, 5])
        sess.prefill(1, list(range(1, S + 1)))     # fills its row: no room
        sess.prefill(2, [9, 2])
        sess.prefill(3, [6])
        sess.free(3)                               # slot 4: never admitted
        assert sess.room(1) == 0
        for _ in range(3):
            lengths = sess.lengths.copy()
            k0, v0 = _tables(sess)
            sess.decode()
            k1, v1 = _tables(sess)
            for a, b in ((k0, k1), (v0, v1)):
                changed = np.argwhere((a != b).any(axis=(0, 3)))
                assert sorted(map(tuple, changed)) \
                    == [(0, lengths[0]), (2, lengths[2])]
                assert (a[:, 0, lengths[0]] == 0).all() \
                    and b[:, 0, lengths[0]].any()
            assert sess.slot_is_zero(3) and sess.slot_is_zero(4)
        # the full slot ran no trip: its length stays at S
        assert list(sess.lengths) == [8, S, 5, 0, 0]

    def test_tokens_logits_and_table_equal_the_where_stack_form(
            self, inplace_pred):
        """N steps through the session against the deleted
        `where`/`stack` form written out above: the same tokens; the same
        table TO THE BIT in every row no step addressed, in layer 0's new
        rows (they are written before any attention ran) and throughout
        an int8 table; and, to rounding, the same logits and the same
        new rows of the layers above.  The two are two programs, and the
        emulated kernel's MXU-form contractions are compiled with the
        program around them, so what has passed through the attention
        agrees to a few ulps and no further (largest readings over the
        four cases: 1.46e-6 in a logit of size 3.3, 9.54e-7 in a row;
        the VPU body's sums agreed to the bit)."""
        import jax
        import jax.numpy as jnp
        pred = inplace_pred
        sess = pred.new_session(3)
        sess.prefill(0, [5, 9, 3, 7])
        sess.prefill(2, [11, 4])
        state = {n: jnp.asarray(v) for n, v in pred._state_host.items()}
        kc, vc = (jnp.asarray(t) for t in _tables(sess))
        oracle = jax.jit(lambda *a: _where_stack_step(pred, *a))
        # [L, N, S]: rows written after an attention, by any step so far
        rounded = np.zeros(kc.shape[:3], bool)
        for _ in range(5):
            lengths, last = sess.lengths.copy(), sess.last_tokens.copy()
            want, kc, vc = oracle(state, kc, vc, lengths, last,
                                  sess.active.copy())
            toks, logits = sess.decode_logits()
            act = sess.active
            np.testing.assert_allclose(logits[act], np.asarray(want)[act],
                                       rtol=0, atol=5e-6)
            assert np.array_equal(
                toks[act], np.asarray(want).argmax(-1)[act])
            rounded[1:, np.flatnonzero(act), lengths[act]] = True
            for mine, want_t in zip(_tables(sess), (kc, vc)):
                want_t = np.asarray(want_t)
                assert np.array_equal(mine[~rounded], want_t[~rounded])
                if pred._kv_quant:
                    assert np.array_equal(mine, want_t)
                else:
                    np.testing.assert_allclose(
                        mine[rounded], want_t[rounded], rtol=0, atol=5e-6)

    def test_a_call_that_fails_after_donation_kills_the_session(
            self, inplace_pred):
        """A phase call that raises AFTER consuming the table leaves a
        session that says so on its next use, whatever the use; one that
        raises before it donates leaves the session as it was."""
        from paddle_tpu.inference.decode import DecodeSessionDead
        sess = inplace_pred.new_session(2)
        first = sess.prefill(0, [5, 9, 3])

        def refuses(state, kc, vc, *small):
            raise ValueError("bad argument")

        def dies(state, kc, vc, *small):
            kc.delete()
            vc.delete()
            raise RuntimeError("the device fell over")

        with pytest.raises(ValueError):
            sess._call("step", refuses, (sess._kc, sess._vc), ())
        ref, _ = greedy_decode(inplace_pred, [5, 9, 3], 3)
        assert [first, int(sess.decode()[0])] == ref[:2]
        with pytest.raises(RuntimeError, match="fell over"):
            sess._call("step", dies, (sess._kc, sess._vc), ())
        for use in (sess.decode, sess.decode_logits,
                    lambda: sess.decode_fused(2),
                    lambda: sess.prefill(1, [4]), lambda: sess.free(0),
                    lambda: sess.rollback(0, 1),
                    lambda: sess.slot_is_zero(1)):
            with pytest.raises(DecodeSessionDead,
                               match="step call failed.*fell over"):
                use()


@pytest.mark.parametrize("kv,elem", [("float32", 4), ("int8", 1)])
def test_kv_bytes_are_the_datas_and_a_step_returns_its_table(
        inplace_artifacts, kv, elem):
    """K/V bytes at rest are the data's: at GPT-2 small's dims 32 slots
    hold 2 x 12 x 32 x 1024 x 768 values at the cache's width exactly
    (75.5 MB a slot in fp32, where rows padded to the kernel's tile held
    201), plus the int8 cache's scale table.  And a donated step hands back
    the buffer it was given: one table, updated in place."""
    from paddle_tpu.inference import decode as dec
    meta = dict(vocab_size=50257, d_model=768, n_heads=12, n_layers=12,
                max_seq_len=1024, eos_id=0)
    gpt2 = object.__new__(GenerativePredictor)
    gpt2.meta, gpt2._block_meta = meta, dec.block_of(meta)
    gpt2._kv_dtype, gpt2._tp_size, gpt2._device = kv, 0, None
    assert gpt2.table_shape(32) == (12, 32, 1024, 768)
    assert gpt2.kv_cache_bytes(32) == 2 * 12 * 32 * 1024 * 768 * elem \
        + (2 * 12 * 12 * 4 if kv == "int8" else 0)
    sess = GenerativePredictor(inplace_artifacts["default"],
                               kv_cache_dtype=kv).new_session(3)
    sess.prefill(1, [7, 2, 9])
    for step in (sess.decode, lambda: sess.decode_fused(4)):
        given = [t.unsafe_buffer_pointer() for t in sess._tables()]
        step()
        assert [t.unsafe_buffer_pointer() for t in sess._tables()] == given


@pytest.mark.parametrize("kv", ["float32", "int8"])
def test_fused_window_is_the_sequential_steps_table_and_all(
        inplace_artifacts, kv):
    """The fused window carries the same table through its `while_loop`
    and lands the same rows: tokens AND tables bit-identical to the
    sequential steps, and the window consumes the table it was given."""
    pred = GenerativePredictor(inplace_artifacts["default"],
                               kv_cache_dtype=kv)
    a, b = pred.new_session(3), pred.new_session(3)
    for sess in (a, b):
        sess.prefill(0, [5, 9, 3, 7])
        sess.prefill(2, [11, 4])
    k_old = a._kc
    toks, counts, trips = a.decode_fused(6)
    assert k_old.is_deleted() and trips == 6
    seq = np.stack([b.decode() for _ in range(6)], axis=1)
    for s in (0, 2):
        assert list(toks[s, :counts[s]]) == list(seq[s, :counts[s]])
    for x, y in zip(_tables(a), _tables(b)):
        assert np.array_equal(x, y)
    assert a.slot_is_zero(1) and list(a.lengths) == list(b.lengths)


def test_a_failed_fused_speculative_round_kills_both_sessions(
        inplace_artifacts):
    from paddle_tpu.inference.decode import (DecodeSessionDead,
                                             SpeculativeDecodeSession)
    target = GenerativePredictor(inplace_artifacts["default"])
    draft = GenerativePredictor(inplace_artifacts["default"],
                                kv_cache_dtype="int8")
    sp = SpeculativeDecodeSession(target, draft, 2, 2)
    sp.prefill(0, [5, 9, 3])

    def dies(state, dstate, t_kc, t_vc, t_len, t_last, d_kc, d_vc, *rest):
        for t in (t_kc, t_vc, d_kc, d_vc):
            t.delete()
        raise RuntimeError("the device fell over")

    target._fns[("fused_spec", 2, 3, draft._model_fp[:16],
                 draft._kv_dtype)] = dies
    with pytest.raises(RuntimeError, match="fell over"):
        sp.step(fused=True)
    for sess in (sp.session, sp.draft_session):
        with pytest.raises(DecodeSessionDead, match="fused_spec"):
            sess.decode()


# -- the kernel over the stacked table, and what each placement donates -----

@pytest.mark.parametrize("kv", ["float32", "int8"])
@pytest.mark.parametrize("layer", [0, 2])
def test_kernel_reads_a_layer_of_the_stacked_table(kv, layer):
    """`decode_attention(..., layer=i)` over the stacked table [L, N, S,
    H * D] is the call on layer i alone to the bit (the block index map
    replaces the slice), for the Pallas body and for the plain-XLA fallback
    alike; so is the head-sliced entry over a member's lanes; and a table
    without a layer, a layer without a table, or caches that keep the heads
    apart [.., H, D] are refused."""
    from paddle_tpu.ops.pallas_kernels import (decode_attention,
                                               decode_attention_head_slice)
    rng = np.random.RandomState(11 + layer)
    L, N, S, H, D = 3, 4, 32, 2, 8
    q = rng.randn(N, H, D).astype(np.float32)
    k = rng.randn(L, N, S, H * D)
    v = rng.randn(L, N, S, H * D)
    scales = None
    if kv == "int8":
        k, v = ((np.clip(t * 40, -127, 127)).astype(np.int8) for t in (k, v))
        scales = (rng.rand(2, H).astype(np.float32) + 0.5) / 127
    else:
        k, v = k.astype(np.float32), v.astype(np.float32)
    lengths = np.array([1, 9, 32, 0], np.int32)
    for bkv in (8, 32, 5):            # 5 divides nothing: the fallback
        want = np.asarray(decode_attention(
            q, k[layer], v[layer], lengths, block_kv=bkv, kv_scales=scales))
        got = np.asarray(decode_attention(
            q, k, v, lengths, block_kv=bkv, kv_scales=scales, layer=layer))
        assert np.array_equal(got, want)
    want = np.asarray(decode_attention_head_slice(
        q[:, 1:], k[layer][..., D:], v[layer][..., D:], lengths, 1, 1,
        block_kv=8, kv_scales=scales))
    got = np.asarray(decode_attention_head_slice(
        q[:, 1:], k[..., D:], v[..., D:], lengths, 1, 1,
        block_kv=8, kv_scales=scales, layer=layer))
    assert np.array_equal(got, want)
    with pytest.raises(ValueError, match="stacked table"):
        decode_attention(q, k, v, lengths, kv_scales=scales)
    with pytest.raises(ValueError, match="stacked table"):
        decode_attention(q, k[0], v[0], lengths, kv_scales=scales, layer=0)
    with pytest.raises(ValueError, match="stacked table"):
        decode_attention(q, *(t[0].reshape(N, S, H, D) for t in (k, v)),
                         lengths, kv_scales=scales)


@pytest.mark.parametrize("placement", ["pinned", "gather_mesh", "tp_mesh"])
def test_what_each_placement_donates(inplace_artifacts, placement):
    """One device (pinned here; the default one above): the step consumes
    the table.  A tensor-parallel mesh: its tables stay head-sharded in
    and out, consumed too.  A gather-mode mesh: `_mesh_wrap` gathers the
    table and re-shards the result, and the call is not donated.  The
    streams are the same everywhere."""
    import jax
    from paddle_tpu.flags import get_flags, set_flags
    from paddle_tpu.parallel.mesh import MeshGroup
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs two devices")
    saved = get_flags(["mesh_tp"])
    set_flags({"mesh_tp": placement == "tp_mesh"})
    try:
        device = devs[1] if placement == "pinned" else MeshGroup(devs[:2])
        pred = GenerativePredictor(inplace_artifacts["default"],
                                   device=device)
        assert pred.tp_active == (placement == "tp_mesh")
        sess = pred.new_session(2)
        toks, donated = [sess.prefill(0, [5, 9, 3])], []
        for _ in range(3):
            k_old, v_old = sess._kc, sess._vc
            toks.append(int(sess.decode()[0]))
            donated.append((k_old.is_deleted(), v_old.is_deleted()))
    finally:
        set_flags(saved)
    if placement == "gather_mesh":
        assert donated == [(False, False)] * 3
    else:
        assert donated == [(True, True)] * 3
        assert sess._inplace == (placement == "pinned")
    ref, _ = greedy_decode(
        GenerativePredictor(inplace_artifacts["default"]), [5, 9, 3], 4)
    assert toks == ref


# -- flat rows: what every placement holds --------------------------------

@pytest.mark.parametrize("blk,kv", INPLACE_CASES,
                         ids=["%s-%s" % c for c in INPLACE_CASES])
def test_flat_rows_serve_the_same_streams(inplace_artifacts, blk, kv):
    """A table of flat rows [L, N, S, H * Dh] holds the K/V heads' values
    and nothing else (the closed-form bytes are the measured ones and the
    data's), and the step consumes it.  Head c of a position is lanes
    c * Dh .. (c + 1) * Dh of its row, whoever wrote it: the rows the steps
    landed are, to rounding, the K and V that plain attention over the whole
    sequence computes with the heads apart (`_prefill_core`, [L, 1, B, H,
    Dh]) and the rows a prefill of the same tokens writes; that prefill's
    next token is the steps' (the kernel read the
    flat rows as plain attention reads the heads); rows past a slot's length
    and a slot never admitted are exact zeros."""
    import jax.numpy as jnp
    pred = GenerativePredictor(inplace_artifacts[blk], kv_cache_dtype=kv)
    sess = pred.new_session(3)
    assert sess._kc.shape == pred.table_shape(3) == (2, 3, 32, 32)
    elem = 1 if kv == "int8" else 4
    assert sess.cache_bytes() == pred.kv_cache_bytes(3) \
        == 2 * 2 * 3 * 32 * (2 * 16) * elem + (2 * 2 * 2 * 4
                                                if kv == "int8" else 0)
    seqs = {0: [5, 9, 3, 7], 2: [11, 4]}
    for slot, prompt in seqs.items():
        seqs[slot] = prompt + [sess.prefill(slot, prompt)]
    for _ in range(5):
        k_old = sess._kc
        toks = sess.decode()
        assert k_old.is_deleted()
        for slot in seqs:
            seqs[slot].append(int(toks[slot]))
    again = pred.new_session(3)
    state = {n: jnp.asarray(v) for n, v in pred._state_host.items()}
    tables = _tables(sess)
    for slot, seq in seqs.items():
        # the last token is pending: its K/V is not in the cache yet
        T = len(seq) - 1
        assert sess.lengths[slot] == T
        assert again.prefill(slot, seq[:-1]) == seq[-1]
        padded = np.zeros((1, 32), np.int32)
        padded[0, :T] = seq[:-1]
        core = pred._prefill_core(state, padded, np.int32(T))[1:3]
        for which, (t, apart) in enumerate(zip(tables, core)):
            apart = np.asarray(apart)[:, 0]          # [L, B, H, Dh]
            assert apart.shape == (2, 32, 2, 16)
            if kv == "int8":
                sc = np.asarray(pred._kv_scales)[which]      # [L, H, 1]
                apart = np.clip(np.round(apart / sc[:, None]), -127, 127)
            # (a step attends over the int8 rows, a prefill over the
            # floats they were made from: above layer 0 a tenth of the
            # int8 values land on the neighbouring step; readings in fp32
            # up to 1.4e-6)
            want = apart[:, :T].reshape(2, T, 32)
            np.testing.assert_allclose(t[:, slot, :T], want, rtol=0,
                                       atol=1 if kv == "int8" else 5e-6)
            if kv == "int8":
                assert np.array_equal(t[0, slot, :T], want[0])
            assert t[:, slot, :T].any() and not t[:, slot, T:].any()
    for t, r in zip(tables, _tables(again)):
        assert not t[:, 1].any() and not r[:, 1].any()
        if kv == "int8":
            assert np.abs(t.astype(np.int32) - r).max() <= 1
        else:
            np.testing.assert_allclose(t, r, rtol=0, atol=5e-6)


@pytest.mark.parametrize("kv", ["float32", "int8"])
def test_a_meshs_flat_rows_fused_window_and_speculative_round(
        inplace_artifacts, kv):
    """A mesh holds flat rows too, the row's axis sharded: each of two
    members its one head's 16 lanes of 32, a flat table of its own.  The
    phases that carry the table through more than one step over it (one
    device's are the tests above): the fused window equals the sequential
    steps (tables too),
    and a speculative session (verify lands its chunk through its own
    one-hot write, rollback zeroes a span) commits the plain stream with
    every draft accepted."""
    import jax
    from paddle_tpu.parallel.mesh import MeshGroup
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs two devices")
    mesh = MeshGroup(devs[:2])
    from paddle_tpu.inference.decode import SpeculativeDecodeSession
    pred = GenerativePredictor(inplace_artifacts["default"],
                               kv_cache_dtype=kv, device=mesh)
    a, b = pred.new_session(2), pred.new_session(2)
    assert a._kc.shape == (2, 2, 32, 32) and sorted(
        s.data.shape for s in a._kc.addressable_shards) \
        == [(2, 2, 32, 16)] * 2
    for sess in (a, b):
        sess.prefill(0, [5, 9, 3, 7])
    toks, counts, _ = a.decode_fused(5)
    seq = [int(b.decode()[0]) for _ in range(5)]
    assert list(toks[0, :counts[0]]) == seq
    for x, y in zip(_tables(a), _tables(b)):
        assert np.array_equal(x, y)
    a.rollback(0, 2, last_token=seq[2])
    assert int(a.decode()[0]) == seq[3]
    ref, _ = greedy_decode(
        GenerativePredictor(inplace_artifacts["default"]), [5, 9, 3, 7], 10)
    target = GenerativePredictor(inplace_artifacts["default"], device=mesh)
    for fused in (False, True):
        sp = SpeculativeDecodeSession(target, pred, 2, 2)
        out = [sp.prefill(0, [5, 9, 3, 7])]
        while len(out) < 10:
            g, n = sp.step(fused=fused)
            out += [int(t) for t in g[0, :n[0]]]
        assert out[:10] == ref and not sp.degraded
        if kv == "float32":
            assert sp.accepted == sp.proposed
