"""The same-bucket prompts of ONE admission run as ONE prefill call (SERVING.md
"Fused multi-step decode", the lane's pass; PR 55): `GenerativePredictor.
prefill_fn(bucket, prompts=P)` over tokens [P, B], `DecodeSession.
launch_prefill` / `fetch_prefill` of a group, `DecodeBatcher._prefill_calls`.

* a group of prompts of mixed lengths in one bucket leaves, slot for slot, the
  tables, lengths, first tokens and routing facts of as many one-prompt
  prefills, on a stack of each kind of slot state (K/V rows, latent rows, conv
  state, scanned state, K/V rings) and a routed one; a prompt's rows do not
  depend on its place in the group or on who rides beside it (bit for bit),
  and are those of its one-prompt prefill (to the CPU's rounding: its matmul
  picks its blocking by the number of rows; the tokens and the facts exactly);
* `prompts=1` resolves today's phase key;
* the width of a bucket and what a call takes of a run;
* the admission: grouped by bucket in arrival order, every admit landed in its
  pass; a cancelled or expired admit in no group; a raising group fails its
  members alone; a lost mesh member requeues the unlaunched; chunked, mesh and
  speculative lanes a prompt a call; the spans' `prompts`;
* `ModelEntry.warm` leaves no group phase a lane can call unresolved.

CPU-safe under JAX_PLATFORMS=cpu.
"""

import time

import numpy as np
import pytest

import jax

from paddle_tpu.flags import set_flags
from paddle_tpu.inference import decode as dec
from paddle_tpu.inference.decode import (GenerativePredictor,
                                         build_tiny_decode_model,
                                         greedy_decode)
from paddle_tpu.obs import tracing as obs_tracing
from paddle_tpu.parallel.mesh import MeshGroup, MeshMemberLost
from paddle_tpu.serving import DeadlineExceeded
from paddle_tpu.serving.batcher import DecodeBatcher
from tests import test_prefill_ahead as ahead
from tests.test_prefill_ahead import _Lane, _tokens
from tests.test_slot_state import STACKS as SIX

# the kind of slot state (and the routed FFN) each of the six tiny stacks is
# here for
KINDS = {"gpt2": "kv", "olmoe": "routed", "pangu": "latent", "lfm2": "conv",
         "falconh1": "ssm", "kexaone": "ring"}
BUCKET = 16
LENS = [11, 9, 16, 10, 12, 13, 15, 14]
NEW = 5


@pytest.fixture(autouse=True)
def _quiet():
    was = obs_tracing.enabled()
    yield
    set_flags({"trace": was})


@pytest.fixture(scope="module")
def six(tmp_path_factory):
    """name -> the predictor of one of the six tiny stacks."""
    root, built = tmp_path_factory.mktemp("group"), {}

    def get(name):
        if name not in built:
            block, size = SIX[name]
            built[name] = GenerativePredictor(build_tiny_decode_model(
                str(root / name), block=block, **size))
        return built[name]
    return get


@pytest.fixture(scope="module")
def artifacts(tmp_path_factory):
    """name -> (artifact, its predictor) of `test_prefill_ahead`'s stacks."""
    root, built = tmp_path_factory.mktemp("group_lane"), {}

    def get(name):
        if name not in built:
            d = build_tiny_decode_model(str(root / name), eos_id=-1,
                                        **ahead.STACKS[name])
            built[name] = (d, GenerativePredictor(d))
        return built[name]
    return get


def _prompts(pred, lens, seed=1):
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(1, pred.vocab_size, n)]
            for n in lens]


# ---------------------------------------------------------------------------
# the executable and the session
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("members", [5, 8])
@pytest.mark.parametrize("stack", sorted(KINDS), ids=lambda s: KINDS[s])
def test_a_group_leaves_what_its_prompts_leave_alone(six, stack, members):
    """`members` prompts of mixed lengths in one bucket (8: a whole group; 5:
    one padded with dead rows) against as many one-prompt prefills."""
    pred = six(stack)
    assert pred.prefill_width(BUCKET) == 8
    prompts = _prompts(pred, LENS[:members])
    slots = [4, 0, 7, 5, 1, 8, 2, 6][:members]
    alone, group, turned = (pred.new_session(9) for _ in range(3))
    firsts, facts = [], []
    for slot, p in zip(slots, prompts):
        firsts.append(alone.prefill(slot, p))
        facts.append(alone.last_routing)
    assert group.launch_prefill(slots, prompts) is False
    # reserved, not active yet: no slot of the group is handed out again
    assert group.free_slots() == sorted(set(range(9)) - set(slots))
    assert not group.active.any()
    assert group.fetch_prefill() == firsts
    # the same prompts the other way round: other rows, other neighbours
    turned.launch_prefill(slots[::-1], prompts[::-1])
    assert turned.fetch_prefill() == firsts[::-1]
    for sess in (group, turned):
        for name in ("lengths", "last_tokens", "active"):
            np.testing.assert_array_equal(getattr(sess, name),
                                          getattr(alone, name))
    if pred.routed_layers:
        assert group.last_routing.shape == (members, pred.routed_layers, 2)
        assert group.last_routing.tolist() == [f.tolist() for f in facts]
        assert turned.last_routing.tolist() == \
            group.last_routing.tolist()[::-1]
    else:
        assert group.last_routing is None
    for a, g, t in zip(alone._tables(), group._tables(), turned._tables()):
        a, g, t = (np.asarray(x) for x in (a, g, t))
        np.testing.assert_array_equal(g, t)
        np.testing.assert_allclose(g, a, rtol=1e-5, atol=2e-5)
        # exact zeros wherever a one-prompt prefill leaves them: past each
        # prompt's length, in every slot no prompt took
        assert not g[a == 0].any()
    # ... and the streams go on as they do alone
    for _ in range(3):
        np.testing.assert_array_equal(group.decode(), alone.decode())


@pytest.mark.parametrize("stack", sorted(KINDS), ids=lambda s: KINDS[s])
def test_one_prompt_resolves_the_phase_it_always_did(six, stack):
    pred = six(stack)
    fn = pred.prefill_fn(BUCKET)
    assert pred.prefill_fn(BUCKET, prompts=1) is fn
    assert pred._fns[("prefill", BUCKET)] is fn
    tokens = np.zeros((1, BUCKET), np.int32)
    tokens[0, :3] = [5, 9, 3]
    first, *rows = fn(pred._state, tokens, np.int32(3))
    many = pred.prefill_fn(BUCKET, prompts=3)
    assert many is not fn and pred._fns[("prefill", BUCKET, 3)] is many
    firsts, *group = many(pred._state, np.repeat(tokens, 3, axis=0),
                          np.int32([3, 3, 3]))
    # each leaf of `_table_names` with a leading P, the first tokens [P, ..]
    assert [g.shape for g in group] == [(3,) + r.shape for r in rows]
    assert firsts.shape == (3,) + first.shape
    np.testing.assert_array_equal(np.asarray(firsts),
                                  np.stack([np.asarray(first)] * 3))


@pytest.mark.parametrize("bucket,width", [
    (8, 8), (64, 8), (128, 8), (256, 4), (512, 2), (1024, 1), (4096, 1)])
def test_the_width_of_a_bucket(six, monkeypatch, bucket, width):
    pred = six("gpt2")
    monkeypatch.setattr(pred, "prefill_buckets", lambda: (bucket,))
    assert dec.PREFILL_GROUP_TOKENS == 1024
    assert pred.prefill_width(bucket) == width
    # a length past every configured bucket runs alone, as it compiles alone
    assert pred.prefill_width(bucket - 1) == 1


@pytest.mark.parametrize("width,waiting,takes", [
    (1, 1, 1), (1, 7, 1), (2, 1, 1), (2, 2, 2), (2, 5, 2), (4, 1, 1),
    (4, 2, 1), (4, 3, 3), (4, 4, 4), (4, 9, 4), (8, 4, 1), (8, 5, 5),
    (8, 7, 7), (8, 8, 8), (8, 64, 8)])
def test_what_a_call_takes_of_a_run(width, waiting, takes):
    assert dec.prefill_group(width, waiting) == takes


def test_what_a_group_is_refused(six):
    pred = six("gpt2")
    sess = pred.new_session(4)
    p = _prompts(pred, [3, 5, 20])
    with pytest.raises(ValueError, match="2 to 8 prompts of one bucket"):
        sess.launch_prefill([0], p[:1])
    with pytest.raises(ValueError, match="2 to 8 prompts of one bucket"):
        sess.launch_prefill([0, 1], [p[0], p[2]])        # two buckets
    with pytest.raises(ValueError, match="2 to 8 prompts of one bucket"):
        sess.launch_prefill([0, 0], p[:2])               # one slot twice
    with pytest.raises(ValueError, match="2 to 8 prompts of one bucket"):
        sess.launch_prefill([0, 1, 2], p[:2])
    with pytest.raises(ValueError, match="empty prompt"):
        sess.launch_prefill([0, 1], [p[0], []])
    sess.prefill(2, p[0])
    with pytest.raises(ValueError, match="slot 2 is occupied"):
        sess.launch_prefill([1, 2], p[:2])
    # nothing of it was launched: the session goes on, a group behind a
    # prompt, a step refused until both are fetched
    assert sess.launch_prefill(0, p[2]) is False
    assert sess.launch_prefill([1, 3], p[:2]) is True
    assert sess.free_slots() == []
    with pytest.raises(RuntimeError, match="a prefill is not fetched yet"):
        sess.decode()
    assert sess.fetch_prefill() == greedy_decode(pred, p[2], 1)[0][0]
    assert sess.fetch_prefill() == [greedy_decode(pred, q, 1)[0][0]
                                    for q in p[:2]]
    sess.decode()


def test_a_chunked_stack_and_a_mesh_run_a_prompt_a_call(artifacts):
    d, pred = artifacts("chunked")
    assert [pred.prefill_width(b) for b in pred.prefill_buckets()] == [1]
    d, pred = artifacts("kv")
    assert [pred.prefill_width(b) for b in pred.prefill_buckets()] == [8, 8]
    meshed = GenerativePredictor(d, device=MeshGroup(jax.devices()[:2]))
    assert [meshed.prefill_width(b) for b in meshed.prefill_buckets()] \
        == [1, 1]


# ---------------------------------------------------------------------------
# the admission
# ---------------------------------------------------------------------------

class _Grouping(_Lane):
    """`_Lane` whose session also notes every `launch_prefill` it is asked:
    ([slots], [prompts]) a call."""

    def __init__(self, pred, slots, **kw):
        super().__init__(pred, slots, **kw)
        self.calls = []
        launch = self.sess.launch_prefill

        def noting(slot, tokens):
            group = isinstance(slot, (list, tuple))
            self.calls.append((list(slot) if group else [slot],
                               [list(t) for t in tokens] if group
                               else [list(tokens)]))
            return launch(slot, tokens)
        self.sess.launch_prefill = noting


@pytest.fixture
def narrow(monkeypatch):
    """Buckets of 8 take 4 prompts a call, buckets of 16 take 2."""
    monkeypatch.setattr(dec, "PREFILL_GROUP_TOKENS", 32)


# arrival order; buckets 8 8 16 8 8 16 8 16 8
ARRIVALS = [[5, 9, 3], [7, 2], [11, 6, 8, 2, 9, 4, 1, 3, 12], [1, 2, 3, 4],
            [13, 4], [3] * 10, [6, 6, 6], [2] * 12, [9]]


@pytest.mark.parametrize("stack", ["kv", "conv_ssm", "routed"])
def test_an_admission_groups_by_bucket_in_arrival_order(artifacts, narrow,
                                                        stack):
    _, pred = artifacts(stack)
    want = [greedy_decode(pred, p, NEW)[0] for p in ARRIVALS]
    set_flags({"trace": True})
    obs_tracing.clear()
    with _Grouping(pred, 9) as r:
        streams = r.submit_together([(p, NEW) for p in ARRIVALS])
        assert [_tokens(s) for s in streams] == want
        small = [p for p in ARRIVALS if len(p) <= 8]
        large = [p for p in ARRIVALS if len(p) > 8]
        # a whole group of the small bucket, its remainder of two a prompt a
        # call, then the large bucket's group and ITS remainder
        assert [c[1] for c in r.calls] == [
            small[:4], small[4:5], small[5:6], large[:2], large[2:3]]
        # every admit landed in the pass that admitted it: no step between
        first_step = r.log.index(("call", "step"))
        assert r.prefills() == ahead._pipeline(5)
        assert all(phase != "prefill" for _, phase in r.log[first_step:])
        assert sorted(s for c in r.calls for s in c[0]) == list(range(9))
        # every request but the first call's rode a call queued behind one
        assert r.batcher.replica_stats()[0]["prefills_ahead"] == 9 - 4
    spans = sorted((s for s in obs_tracing.recent_spans()
                    if s["name"] == "serving/prefill_compute"),
                   key=lambda s: s["t0"])
    # one span a request, in the calls' order, tiling; `prompts` the call's
    assert [s["attrs"]["prompt"] for s in spans] == \
        [len(p) for p in small + large]
    assert [s["attrs"]["prompts"] for s in spans] == [4] * 4 + [1, 1, 2, 2, 1]
    assert [s["attrs"]["ahead"] for s in spans] == [0] * 4 + [1] * 5
    for a, b in zip(spans, spans[1:]):
        assert abs(a["t0"] + a["dur_ms"] * 1e-3 - b["t0"]) < 1e-6
    assert len({s["trace_id"] for s in spans}) == 9


def test_a_cancelled_and_an_expired_admit_enter_no_group(artifacts, narrow):
    _, pred = artifacts("kv")
    prompts = [[5, 9, 3], [7, 2], [1, 2, 3, 4], [13, 4], [6, 6], [9]]
    want = [greedy_decode(pred, p, NEW)[0] for p in prompts]
    with _Grouping(pred, 6) as r:
        past = time.monotonic() - 1.0
        with r.batcher._cv:
            streams = [r.batcher.submit(
                p, max_new_tokens=NEW, deadline=past if i == 2 else None)
                for i, p in enumerate(prompts)]
            streams[1].cancel()
        live = [0, 3, 4, 5]
        assert [_tokens(streams[i]) for i in live] == [want[i] for i in live]
        with pytest.raises(DeadlineExceeded):
            streams[2].result(timeout=60)
        streams[1]._done.wait(60)
        assert streams[1].done() and streams[1].tokens == []
        # the four that were left are one whole group, in arrival order
        assert [c[1] for c in r.calls] == [[prompts[i] for i in live]]


def test_a_group_that_raises_fails_its_members_and_no_others(artifacts,
                                                             narrow):
    _, pred = artifacts("kv")
    prompts = ARRIVALS[:2] + ARRIVALS[3:5] + [ARRIVALS[2], ARRIVALS[5]] \
        + [ARRIVALS[6]]
    want = [greedy_decode(pred, p, NEW)[0] for p in prompts]
    with _Grouping(pred, 7) as r:
        launch, n = r.sess.launch_prefill, [0]

        def failing(slot, tokens):
            n[0] += 1
            if n[0] == 1:
                raise ValueError("a bad group")
            return launch(slot, tokens)
        r.sess.launch_prefill = failing
        streams = r.submit_together([(p, NEW) for p in prompts])
        for s in streams[:4]:
            with pytest.raises(ValueError, match="a bad group"):
                s.result(timeout=60)
        assert [_tokens(s) for s in streams[4:]] == want[4:]
        # the small bucket's fifth prompt alone, then the large bucket's two
        assert [c[1] for c in r.calls] == [[prompts[6]], prompts[4:6]]
        assert not r.sess._prefills and r.lane.dead is None
    # ... and a FETCH that raises: the group in flight behind it is landed
    with _Grouping(pred, 7) as r:
        fetch, n = r.sess._fetch, [0]

        def failing(phase, *a, **k):
            n[0] += 1
            if n[0] == 1:
                raise RuntimeError("the copy failed")
            return fetch(phase, *a, **k)
        r.sess._fetch = failing
        streams = r.submit_together([(p, NEW) for p in prompts])
        for s in streams[:4]:
            with pytest.raises(RuntimeError, match="the copy failed"):
                s.result(timeout=60)
        assert [_tokens(s) for s in streams[4:]] == want[4:]
        assert [c[1] for c in r.calls] == [prompts[:4], [prompts[6]],
                                           prompts[4:6]]


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_a_member_lost_requeues_what_was_not_launched(artifacts, narrow):
    """Two lanes; the one that admits the nine loses a member at its second
    call.  The first call's group, in flight, is fetched and fails typed with
    its lane; the second call's request fails typed; the rest was never
    launched, goes back to the queue in arrival order and streams from the
    other lane, grouped anew."""
    d, pred = artifacts("kv")
    want = [greedy_decode(pred, p, NEW)[0] for p in ARRIVALS]
    batcher = DecodeBatcher(pred, replicas=[pred, GenerativePredictor(d)],
                            n_slots=9)
    try:
        n, launched = [0], []
        for lane in batcher._lanes:
            def failing(slot, tokens, lane=lane,
                        launch=lane.session.launch_prefill):
                n[0] += 1
                if n[0] == 2:
                    raise MeshMemberLost("member gone")
                launched.append((lane.index, tokens))
                return launch(slot, tokens)
            lane.session.launch_prefill = failing
        with batcher._cv:
            streams = [batcher.submit(p, max_new_tokens=NEW)
                       for p in ARRIVALS]
        small = [i for i, p in enumerate(ARRIVALS) if len(p) <= 8]
        large = [i for i, p in enumerate(ARRIVALS) if len(p) > 8]
        lost, kept = small[:5], small[5:] + large
        for i in kept:
            assert _tokens(streams[i]) == want[i]
        for i in lost:
            with pytest.raises(MeshMemberLost, match="member gone"):
                streams[i].result(timeout=60)
        (dead,) = [l for l in batcher._lanes if l.dead]
        other = 1 - dead.index
        assert not dead.session._prefills
        # the survivor took them back in ARRIVAL order and grouped them anew:
        # the small bucket's last prompt, then the large bucket's three
        assert launched == [
            (dead.index, [ARRIVALS[i] for i in small[:4]]),
            (other, ARRIVALS[small[5]]),
            (other, [ARRIVALS[i] for i in large[:2]]),
            (other, ARRIVALS[large[2]])]
    finally:
        batcher.close(drain=False, timeout=10.0)


@pytest.mark.parametrize("lane", ["chunked", "mesh", "speculative"])
def test_a_lane_that_groups_nothing_admits_in_arrival_order(artifacts, lane):
    d, pred = artifacts("chunked" if lane == "chunked" else "kv")
    kw = {}
    if lane == "mesh":
        pred = GenerativePredictor(d, device=MeshGroup(jax.devices()[:2]))
    if lane == "speculative":
        kw = dict(draft=GenerativePredictor(d), spec_k=2)
    prompts = ARRIVALS[:5] * 2
    want = [greedy_decode(pred, p, 3)[0] for p in prompts[:5]] * 2
    with _Lane(pred, 10, **kw) as r:
        assert r.lane.spec == (lane == "speculative")
        calls, launch = [], r.sess.launch_prefill

        def noting(slot, tokens):
            calls.append((slot, list(tokens)))
            return launch(slot, tokens)
        r.sess.launch_prefill = noting
        streams = r.submit_together([(p, 3) for p in prompts])
        assert [_tokens(s, timeout=300) for s in streams] == want
        assert [c[1] for c in calls] == prompts
        assert all(isinstance(c[0], (int, np.integer)) for c in calls)


# ---------------------------------------------------------------------------
# the warm-up
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("slots,members", [(8, 8), (5, 5), (4, 1)])
def test_a_warm_lane_compiles_no_group_under_traffic(artifacts, slots,
                                                     members):
    """`ModelEntry.warm` runs every bucket's group executable a lane of
    `slots` slots can call, with the write that lands it: admissions of 8, 5
    and 2 same-bucket prompts then lower and compile nothing.  A lane too
    small to fill more than half a group resolves none and calls none."""
    import jax.monitoring
    from benchmark.run import CompileWatch
    from tests.test_decode_window import _Served
    d, pred = artifacts("kv")
    events, on = [], [False]

    def listen(name, secs, **kw):
        if on[0] and name.startswith(CompileWatch.WATCHED):
            events.append(name)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        with _Served(d, slots, None) as s:
            served = s.entry.predictor
            groups = sorted(k for k in served._fns if k[0] == "prefill"
                            and len(k) == 3)
            assert groups == ([("prefill", b, 8)
                               for b in served.prefill_buckets()]
                              if members > 1 else [])
            calls = []
            sess = s.batcher._lanes[0].session
            launch = sess.launch_prefill
            sess.launch_prefill = lambda slot, tokens: calls.append(
                len(slot) if isinstance(slot, (list, tuple)) else 1) \
                or launch(slot, tokens)
            on[0] = True
            for n, bucket_len in ((8, 3), (5, 12), (2, 3)):
                n = min(n, slots)
                with s.batcher._cv:
                    streams = [s.batcher.submit([7] * bucket_len,
                                                max_new_tokens=3)
                               for _ in range(n)]
                for st in streams:
                    st.result(timeout=120)
            on[0] = False
            assert events == []
            assert calls == {8: [8, 5, 1, 1], 5: [5, 5, 1, 1],
                             4: [1] * 10}[slots]
    finally:
        on[0] = False
