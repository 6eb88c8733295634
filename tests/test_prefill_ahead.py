"""An admission is a pipeline ONE deep (SERVING.md "Fused multi-step
decode", the lane's pass; PR 53): `DecodeBatcher._admit` launches each
prompt's prefill before it fetches the one ahead of it
(`DecodeSession.launch_prefill` / `fetch_prefill`), so the device finds the
next prefill queued when it ends one.

* a lane that admits 1, 2 and 5 prompts in one pass streams the tokens of
  the serial order, on a stack of K/V rows, one with conv + ssm state and a
  routed one (and a speculative lane, whose session does a whole prefill in
  the launch half);
* the session's calls: launch(i+1) BEFORE fetch(i), never two ahead, and
  with one admit the sequence `prefill` makes;
* the admission's `serving/prefill_compute` spans tile and carry `prompt`,
  `chunks` and `ahead`; the `decode/*` spans of `phase=prefill` pair up;
* a reserved slot is not handed out twice, a step with a prefill unfetched
  is refused, a fetch with none launched is refused;
* a cancelled and an expired admit between two live ones, a launch that
  raises, a fetch that raises with another in flight, a mesh member lost at
  the second of three;
* `replica_stats()["prefills_ahead"]`.

CPU-safe under JAX_PLATFORMS=cpu.
"""

import time

import numpy as np
import pytest

from paddle_tpu.flags import set_flags
from paddle_tpu.inference.decode import (GenerativePredictor,
                                         build_tiny_decode_model,
                                         greedy_decode)
from paddle_tpu.obs import tracing as obs_tracing
from paddle_tpu.parallel.mesh import MeshMemberLost
from paddle_tpu.serving import DeadlineExceeded
from paddle_tpu.serving.batcher import DecodeBatcher

STACKS = {
    "kv": dict(vocab_size=32, d_model=16, n_heads=2, n_layers=2,
               max_seq_len=64, seed=7, prefill_buckets=[8, 16]),
    "conv_ssm": dict(
        vocab_size=53, d_model=24, n_heads=4, n_layers=2, max_seq_len=32,
        seed=5, prefill_buckets=[8, 16],
        block={"norm": "rmsnorm", "norm_eps": 1e-5, "position": "rope",
               "rope_theta": 1e11, "n_kv_heads": 2, "head_dim": 8,
               "layer_types": ["attention+ssm"] * 2, "ffn": "swiglu",
               "dense_width": 48, "ssm_heads": 4, "ssm_head_dim": 8,
               "ssm_state": 16, "ssm_groups": 2, "ssm_conv_kernel": 4,
               "ssm_chunk": 4}),
    "routed": dict(
        vocab_size=97, d_model=64, n_heads=4, n_layers=2, max_seq_len=64,
        seed=11, prefill_buckets=[8, 16],
        block={"norm": "rmsnorm", "norm_eps": 1e-5, "position": "rope",
               "rope_theta": 10000.0, "qk_norm": True, "ffn": "moe_swiglu",
               "n_experts": 8, "experts_per_token": 2, "expert_width": 32,
               "norm_topk_prob": False}),
    # a stack that prefills in chunks: its spans say how many
    "chunked": dict(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, max_seq_len=128,
        seed=5, prefill_buckets=[64],
        block=dict(
            norm="rmsnorm", norm_eps=1e-6, position="rope",
            rope_theta=10000.0, rope_layers="linear", qk_norm="head",
            n_kv_heads=2, head_dim=8,
            layer_types=["sparse_attention", "linear_attention"],
            ssm_heads=4, ssm_head_dim=8, ssm_state=8, ssm_groups=4,
            ssm_chunk=16, linear_log_decay=[-0.6, -0.3, -0.1, -0.02],
            sparse_block=16, sparse_topk=6, sparse_init_blocks=1,
            sparse_window=32, sparse_kernel_size=8, sparse_kernel_stride=4,
            output_gate=True, output_norm=True, prefill_chunk=32,
            ffn="swiglu", dense_width=48, head="untied")),
}
PROMPTS = ([5, 9, 3], [7, 2], [1, 2, 3, 4], [11, 6, 8, 2, 9, 4, 1, 3, 12],
           [13, 4])
NEW = 6


@pytest.fixture(autouse=True)
def _quiet():
    was = obs_tracing.enabled()
    yield
    set_flags({"trace": was})


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory):
    """name -> (artifact, its predictor), built when first asked."""
    root, built = tmp_path_factory.mktemp("ahead"), {}

    def get(name):
        if name not in built:
            d = build_tiny_decode_model(str(root / name), eos_id=-1,
                                        **STACKS[name])
            built[name] = (d, GenerativePredictor(d))
        return built[name]
    return get


class _Lane(object):
    """A one-lane `DecodeBatcher` over `pred` whose session writes
    ("call" | "fetch", phase) of every `_call` / `_fetch` into `log`."""

    def __init__(self, pred, slots, **kw):
        self.pred, self.log = pred, []
        self.batcher = DecodeBatcher(pred, n_slots=slots, **kw)
        self.lane = self.batcher._lanes[0]
        self.sess = sess = self.lane.session
        if not self.lane.spec:
            call, fetch = sess._call, sess._fetch

            def _call(phase, *a, **k):
                self.log.append(("call", phase))
                return call(phase, *a, **k)

            def _fetch(phase, *a, **k):
                self.log.append(("fetch", phase))
                return fetch(phase, *a, **k)
            sess._call, sess._fetch = _call, _fetch

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.batcher.close(drain=False, timeout=10.0)

    def submit_together(self, requests):
        """Every request queued before the lane looks: it admits as many
        as it has slots in ONE pass."""
        with self.batcher._cv:
            return [self.batcher.submit(p, max_new_tokens=m)
                    for p, m in requests]

    def prefills(self):
        return [kind for kind, phase in self.log if phase == "prefill"]


def _tokens(stream, timeout=120):
    return [int(t) for t in stream.result(timeout=timeout)[0]]


def _pipeline(n):
    """The session's prefill calls of an admission of `n`: each launch
    but the first is made before the fetch ahead of it, none two ahead;
    with one admit the two calls of `prefill`."""
    return ["call"] + ["call", "fetch"] * (n - 1) + ["fetch"]


# ---------------------------------------------------------------------------
# (a), (b), (f): the streams, the order of the calls, the counter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 5])
@pytest.mark.parametrize("stack", ["kv", "conv_ssm", "routed"])
def test_an_admission_of_n_streams_the_serial_tokens(artifacts, stack, n):
    _, pred = artifacts(stack)
    want = [greedy_decode(pred, p, NEW)[0] for p in PROMPTS[:n]]
    with _Lane(pred, 5) as r:
        streams = r.submit_together([(p, NEW) for p in PROMPTS[:n]])
        assert [_tokens(s) for s in streams] == want
        # launch(i+1) before fetch(i), never two ahead; one admit: today's
        assert r.prefills() == _pipeline(n)
        # the step came after the last prefill's fetch
        first_step = r.log.index(("call", "step"))
        assert all(phase != "prefill" for _, phase in r.log[first_step:])
        assert r.batcher.replica_stats()[0]["prefills_ahead"] == n - 1
        # a second admission counts on; nothing stays in flight between two
        more = r.submit_together([(p, NEW) for p in PROMPTS[3:]])
        assert [_tokens(s) for s in more] == \
            [greedy_decode(pred, p, NEW)[0] for p in PROMPTS[3:]]
        assert r.batcher.replica_stats()[0]["prefills_ahead"] == n
        assert not r.sess._prefills


def test_a_speculative_lane_admits_through_the_same_two_halves(artifacts):
    d, pred = artifacts("kv")
    draft = GenerativePredictor(d)
    want = [greedy_decode(pred, p, NEW)[0] for p in PROMPTS[:3]]
    set_flags({"trace": True})
    obs_tracing.clear()
    with _Lane(pred, 3, draft=draft, spec_k=2) as r:
        assert r.lane.spec
        streams = r.submit_together([(p, NEW) for p in PROMPTS[:3]])
        assert [_tokens(s) for s in streams] == want
        assert not r.sess._firsts
        # its launch half is a whole prefill: nothing was queued behind
        # anything, and neither the counter nor the spans say it was
        assert r.batcher.replica_stats()[0]["prefills_ahead"] == 0
        assert [s["attrs"]["ahead"] for s in obs_tracing.recent_spans()
                if s["name"] == "serving/prefill_compute"] == [0, 0, 0]
        with pytest.raises(RuntimeError, match="no prefill in flight"):
            r.sess.fetch_prefill()


# ---------------------------------------------------------------------------
# (c) the spans
# ---------------------------------------------------------------------------

def test_an_admissions_spans_tile_and_say_who_was_ahead(artifacts):
    _, pred = artifacts("chunked")
    set_flags({"trace": True})
    obs_tracing.clear()
    ids = ["ahead-%d" % i for i in range(4)]
    with _Lane(pred, 4) as r:
        with r.batcher._cv:
            streams = [r.batcher.submit(p, max_new_tokens=3, trace_id=t)
                       for p, t in zip(PROMPTS, ids)]
        for s in streams:
            s.result(timeout=300)
    spans = obs_tracing.recent_spans()
    pcs = sorted((s for s in spans if s["name"] == "serving/prefill_compute"),
                 key=lambda s: s["t0"])
    assert [s["trace_id"] for s in pcs] == ids
    assert [s["attrs"]["ahead"] for s in pcs] == [0, 1, 1, 1]
    assert [s["attrs"]["prompt"] for s in pcs] == \
        [len(p) for p in PROMPTS[:4]]
    assert [s["attrs"]["chunks"] for s in pcs] == [2] * 4
    assert {s["parent"] for s in pcs} == {"serving/lane_iter"}
    ends = [s["t0"] + s["dur_ms"] * 1e-3 for s in pcs]
    for a, b in zip(ends, pcs[1:]):
        # no overlap and no hole: a span starts where the one ahead ended
        assert abs(b["t0"] - a) < 1e-6
    (it,) = [s for s in spans if s["name"] == "serving/lane_iter"
             and s["attrs"]["admits"] == 4]
    assert it["t0"] <= pcs[0]["t0"] \
        and ends[-1] <= it["t0"] + it["dur_ms"] * 1e-3 + 1e-6
    # the session's spans of a prefill: one put, launch and fetch a request,
    # under its span and its trace id, each at its own time
    by_id = {t: {s["name"]: s for s in spans if s.get("trace_id") == t
                 and s.get("attrs", {}).get("phase") == "prefill"}
             for t in ids}
    for i, t in enumerate(ids):
        mine = by_id[t]
        assert sorted(mine) == ["decode/fetch", "decode/launch", "decode/put"]
        assert {s["parent"] for s in mine.values()} == \
            {"serving/prefill_compute"}
        assert mine["decode/put"]["t0"] <= mine["decode/launch"]["t0"] \
            <= mine["decode/fetch"]["t0"]
        assert mine["decode/launch"]["attrs"]["chunks"] == 2
        # the fetch ends its own request's span; a launch made ahead lies in
        # the span of the request ahead of it
        fetch_end = mine["decode/fetch"]["t0"] \
            + mine["decode/fetch"]["dur_ms"] * 1e-3
        assert pcs[i]["t0"] <= mine["decode/fetch"]["t0"] \
            and fetch_end <= ends[i] + 1e-6
        holder = pcs[i - 1] if i else pcs[0]
        assert holder["t0"] - 1e-6 <= mine["decode/launch"]["t0"] \
            <= holder["t0"] + holder["dur_ms"] * 1e-3
    n_prefill = {name: len([s for s in spans if s["name"] == name
                            and s["attrs"].get("phase") == "prefill"])
                 for name in ("decode/launch", "decode/fetch")}
    assert n_prefill == {"decode/launch": 4, "decode/fetch": 4}


# ---------------------------------------------------------------------------
# (d) the session's two halves
# ---------------------------------------------------------------------------

def test_a_reserved_slot_is_not_handed_out_twice(artifacts):
    _, pred = artifacts("kv")
    sess = pred.new_session(3)
    with pytest.raises(RuntimeError, match="no prefill in flight"):
        sess.fetch_prefill()
    # a launch says whether it was queued behind an unfetched one
    assert sess.launch_prefill(0, PROMPTS[0]) is False
    assert sess.free_slots() == [1, 2] and not sess.active[0]
    with pytest.raises(ValueError, match="slot 0 is occupied"):
        sess.launch_prefill(0, PROMPTS[1])
    assert sess.launch_prefill(sess.free_slots()[0], PROMPTS[1]) is True
    assert sess.free_slots() == [2]
    # in launch order
    assert sess.fetch_prefill() == greedy_decode(pred, PROMPTS[0], 1)[0][0]
    assert sess.free_slots() == [2] and list(sess.active) == [True, False,
                                                             False]
    assert sess.fetch_prefill() == greedy_decode(pred, PROMPTS[1], 1)[0][0]
    assert list(sess.lengths) == [len(PROMPTS[0]), len(PROMPTS[1]), 0]
    with pytest.raises(RuntimeError, match="no prefill in flight"):
        sess.fetch_prefill()


@pytest.mark.parametrize("step", ["launch_fused", "decode", "decode_logits"])
def test_a_step_with_a_prefill_unfetched_is_refused(artifacts, step):
    _, pred = artifacts("kv")
    sess = pred.new_session(2)
    first = sess.prefill(0, PROMPTS[0])
    sess.launch_prefill(1, PROMPTS[1])
    with pytest.raises(RuntimeError, match="a prefill is not fetched yet"):
        getattr(sess, step)(*([2] if step == "launch_fused" else []))
    # refused before anything was donated: the session goes on
    sess.fetch_prefill()
    seqs = [[first], [int(sess.last_tokens[1])]]
    for _ in range(3):
        toks = sess.decode()
        for i in (0, 1):
            seqs[i].append(int(toks[i]))
    assert seqs == [greedy_decode(pred, p, 4)[0] for p in PROMPTS[:2]]
    # ... and a prefill is refused while a step is in flight
    sess.free(1)
    sess.launch_fused(1)
    with pytest.raises(RuntimeError, match="a step dispatch is in flight"):
        sess.launch_prefill(1, PROMPTS[1])
    sess.fetch_fused()


def test_prefill_is_the_two_halves_back_to_back(artifacts):
    _, pred = artifacts("conv_ssm")
    whole, halves = pred.new_session(2), pred.new_session(2)
    a = [whole.prefill(i, p) for i, p in enumerate(PROMPTS[:2])]
    for i, p in enumerate(PROMPTS[:2]):
        halves.launch_prefill(i, p)
    b = [halves.fetch_prefill() for _ in range(2)]
    assert a == b
    for t, u in zip(whole._tables(), halves._tables()):
        np.testing.assert_array_equal(np.asarray(t), np.asarray(u))
    np.testing.assert_array_equal(whole.decode(), halves.decode())


# ---------------------------------------------------------------------------
# (e) what can go wrong in an admission
# ---------------------------------------------------------------------------

def test_a_cancelled_and_an_expired_admit_between_two_live_ones(artifacts):
    _, pred = artifacts("kv")
    want = [greedy_decode(pred, p, NEW)[0] for p in PROMPTS[:4]]
    with _Lane(pred, 4) as r:
        past = time.monotonic() - 1.0
        with r.batcher._cv:
            a = r.batcher.submit(PROMPTS[0], max_new_tokens=NEW)
            b = r.batcher.submit(PROMPTS[1], max_new_tokens=NEW)
            c = r.batcher.submit(PROMPTS[2], max_new_tokens=NEW,
                                 deadline=past)
            d = r.batcher.submit(PROMPTS[3], max_new_tokens=NEW)
            b.cancel()
        assert _tokens(a) == want[0] and _tokens(d) == want[3]
        with pytest.raises(DeadlineExceeded):
            c.result(timeout=60)
        b._done.wait(60)
        assert b.done() and b.tokens == []
        # dropped before their launch: two prefills, the second behind the
        # first, which was still unfetched while the two were dropped
        assert r.prefills() == _pipeline(2)
        assert r.batcher.replica_stats()[0]["prefills_ahead"] == 1


def test_a_launch_that_raises_fails_its_own_request(artifacts):
    _, pred = artifacts("kv")
    want = [greedy_decode(pred, p, NEW)[0] for p in PROMPTS[:3]]
    with _Lane(pred, 3) as r:
        launch, n = r.sess.launch_prefill, [0]

        def failing(slot, tokens):
            n[0] += 1
            if n[0] == 2:
                raise ValueError("a bad prompt")
            return launch(slot, tokens)
        r.sess.launch_prefill = failing
        a, b, c = r.submit_together([(p, NEW) for p in PROMPTS[:3]])
        assert _tokens(a) == want[0] and _tokens(c) == want[2]
        with pytest.raises(ValueError, match="a bad prompt"):
            b.result(timeout=60)
        # the third was launched behind the first, as the second would have
        assert r.prefills() == _pipeline(2)
        assert not r.sess._prefills and r.lane.dead is None


def test_a_fetch_that_raises_with_another_in_flight(artifacts):
    _, pred = artifacts("kv")
    want = [greedy_decode(pred, p, NEW)[0] for p in PROMPTS[:3]]
    with _Lane(pred, 3) as r:
        fetch, n = r.sess._fetch, [0]

        def failing(phase, *a, **k):
            n[0] += 1
            if n[0] == 1:
                r.log.append(("fetch", phase))
                raise RuntimeError("the copy failed")
            return fetch(phase, *a, **k)
        r.sess._fetch = failing
        a, b, c = r.submit_together([(p, NEW) for p in PROMPTS[:3]])
        with pytest.raises(RuntimeError, match="the copy failed"):
            a.result(timeout=60)
        # the one already launched behind it is fetched, the third admitted
        assert _tokens(b) == want[1] and _tokens(c) == want[2]
        assert r.prefills() == _pipeline(3)
        assert not r.sess._prefills and r.lane.dead is None


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_a_member_lost_at_the_second_of_three(artifacts):
    """Two lanes; the lane that admits the three loses a member at its
    second launch.  The first request, in flight, is fetched and fails
    typed with its lane; the second fails typed; the third was never
    launched, goes back to the queue and streams from the other lane."""
    d, pred = artifacts("kv")
    want = greedy_decode(pred, PROMPTS[2], NEW)[0]
    batcher = DecodeBatcher(pred, replicas=[pred, GenerativePredictor(d)],
                            n_slots=3)
    try:
        n, launched = [0], []
        for lane in batcher._lanes:
            def failing(slot, tokens, lane=lane,
                        launch=lane.session.launch_prefill):
                n[0] += 1
                if n[0] == 2:
                    raise MeshMemberLost("member gone")
                launched.append((lane.index, list(tokens)))
                return launch(slot, tokens)
            lane.session.launch_prefill = failing
        with batcher._cv:
            a, b, c = [batcher.submit(p, max_new_tokens=NEW)
                       for p in PROMPTS[:3]]
        assert _tokens(c) == want
        for s in (a, b):
            with pytest.raises(MeshMemberLost, match="member gone"):
                s.result(timeout=60)
        # the first request's prefill was fetched before its lane died: its
        # first token was made, and the stream's failure came after
        assert a.tokens in ([], greedy_decode(pred, PROMPTS[0], 1)[0])
        (dead,) = [l for l in batcher._lanes if l.dead]
        assert "member gone" in dead.dead
        assert not dead.session._prefills
        assert launched == [(dead.index, list(PROMPTS[0])),
                            (1 - dead.index, list(PROMPTS[2]))]
        rows = batcher.replica_stats()
        assert [bool(r["dead"]) for r in rows] == \
            [l is dead for l in batcher._lanes]
    finally:
        batcher.close(drain=False, timeout=10.0)
