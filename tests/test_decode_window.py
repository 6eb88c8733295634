"""A decode lane's dispatch is a WINDOW of steps (SERVING.md "Fused
multi-step decode"; PR 30), held here to the lane pinned to one step a
dispatch, through the path a deployment uses: `InferenceServer` +
`ServingClient.infer_stream`, on a GPT-2-shaped and an OLMoE-shaped tiny
block.

* streams under windows are those of one-trip dispatches token for token
  and frame fact for frame fact (`finish_reason`, `new_tokens`): a stream
  that hits EOS mid-window, one whose budget ends on a window's last and on
  its first trip, one cancelled mid-window, a join while a window runs, a
  deadline inside a window;
* after `ModelEntry.warm` a lane that runs windows of 1, 3 and
  `STEP_WINDOW` trips lowers and compiles NOTHING: one step executable a
  slot count, the trips a runtime argument of it.

CPU-safe under JAX_PLATFORMS=cpu.
"""

import threading
import time

import pytest

from paddle_tpu.flags import set_flags
from paddle_tpu.inference.decode import (STEP_WINDOW, GenerativePredictor,
                                         build_tiny_decode_model,
                                         greedy_decode)
from paddle_tpu.obs import tracing as obs_tracing
from paddle_tpu.serving import (DeadlineExceeded, InferenceServer,
                                ServingClient, set_dispatch_delay)

W = STEP_WINDOW
BLOCKS = {
    "gpt2": dict(vocab_size=32, d_model=16, n_heads=2, n_layers=2,
                 max_seq_len=128, seed=7),
    "olmoe": dict(vocab_size=97, d_model=64, n_heads=4, n_layers=2,
                  max_seq_len=128, seed=11, prefill_buckets=[16, 32],
                  block={"norm": "rmsnorm", "norm_eps": 1e-5,
                         "position": "rope", "rope_theta": 10000.0,
                         "qk_norm": True, "ffn": "moe_swiglu",
                         "n_experts": 8, "experts_per_token": 2,
                         "expert_width": 32, "norm_topk_prob": False}),
}
PROMPTS = ([5, 9, 3], [7, 2], [1, 2, 3, 4], [11, 6, 8, 2, 9])


@pytest.fixture(autouse=True)
def _quiet():
    yield
    set_dispatch_delay(0.0)
    set_flags({"trace": False})


@pytest.fixture(scope="module", params=sorted(BLOCKS))
def models(request, tmp_path_factory):
    """(artifact whose streams end by length alone, artifact of the same
    weights whose EOS is a token that FIRST occurs mid-window in the
    greedy stream of `prompt`, prompt, that token's index in the stream)."""
    cfg = BLOCKS[request.param]
    root = tmp_path_factory.mktemp("window_" + request.param)
    endless = build_tiny_decode_model(str(root / "endless"), eos_id=-1,
                                      **cfg)
    pred = GenerativePredictor(endless)
    for prompt in PROMPTS:
        probe = greedy_decode(pred, prompt, 3 * W)[0]
        # decode token j is trip (j - 1) % W of its window on a one-slot
        # lane (token 0 is the prefill's): neither the first nor the last
        mid = [j for j in range(2, len(probe))
               if probe[j] not in probe[:j] and 0 < (j - 1) % W < W - 1]
        if mid:
            eos_at = mid[0]
            with_eos = build_tiny_decode_model(
                str(root / "eos"), eos_id=int(probe[eos_at]), **cfg)
            return endless, with_eos, prompt, eos_at
    raise AssertionError("no prompt's greedy stream has a fresh token "
                         "mid-window: %r" % (cfg,))


class _Served(object):
    """One server with `artifact` loaded at `slots` decode slots, its
    lane's window capped at `cap` (None: the built-in window)."""

    def __init__(self, artifact, slots, cap):
        self.server = InferenceServer().start()
        self.cli = ServingClient(self.server.endpoint)
        self.cli.load_model("lm", artifact, decode_slots=slots,
                            fuse_steps=cap)
        reg = self.server.registry
        with reg._lock:
            self.entry = reg._entry_locked("lm", None)
        self.batcher = self.entry.batcher

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.cli.close()
        self.server.shutdown(drain=False, timeout=10.0)

    def stream(self, prompt, max_new, **kw):
        """(tokens, finish_reason, new_tokens) of one stream."""
        cli = ServingClient(self.server.endpoint)
        try:
            toks = [t for c in cli.infer_stream(
                "lm", prompt, max_new_tokens=max_new, **kw) for t in c]
            info = cli.last_stream_info
            return toks, info["finish_reason"], info["new_tokens"]
        finally:
            cli.close()

    def together(self, requests):
        """`requests` [(prompt, max_new)] from as many clients at once;
        their results in order."""
        out = [None] * len(requests)

        def one(i, prompt, max_new):
            out[i] = self.stream(prompt, max_new)
        threads = [threading.Thread(target=one, args=(i, p, m))
                   for i, (p, m) in enumerate(requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        return out


def _trips():
    """The trips of the lane's dispatches, in dispatch order."""
    steps = sorted(obs_tracing.recent_spans(name="serving/decode_step"),
                   key=lambda s: s["attrs"]["round"])
    return [s["attrs"]["trips"] for s in steps]


def _traced():
    set_flags({"trace": True})
    obs_tracing.clear()


CASES = ["eos_mid_window", "budget_ends_on_last_trip",
         "budget_ends_on_first_trip", "cancel_mid_window",
         "join_while_a_window_runs", "deadline_inside_a_window"]


@pytest.mark.parametrize("case", CASES)
def test_streams_under_windows_equal_one_trip_dispatches(models, case):
    endless, with_eos, prompt, eos_at = models
    run = globals()["_case_" + case]
    got = {}
    for cap in (None, 1):
        _traced()
        got[cap] = run(cap, endless, with_eos, prompt, eos_at)
        set_dispatch_delay(0.0)
    assert got[None] == got[1], case


def _case_eos_mid_window(cap, endless, with_eos, prompt, eos_at):
    """One slot, so every dispatch is a window: the window in which EOS
    lands ends in-graph with that trip, and the terminal frame counts the
    tokens that reached the client."""
    with _Served(with_eos, 1, cap) as s:
        toks, reason, n = s.stream(prompt, 4 * W)
    assert (reason, n, len(toks)) == ("eos", eos_at + 1, eos_at + 1)
    full, last = divmod(eos_at, W)              # eos_at decode tokens
    assert _trips() == ([W] * full + [last] if cap is None
                        else [1] * eos_at)
    return toks, reason, n


def _case_budget_ends_on_last_trip(cap, endless, *_):
    with _Served(endless, 1, cap) as s:
        out = s.stream(PROMPTS[0], 1 + 2 * W)   # the prefill's, 2 windows
    assert out[1:] == ("length", 1 + 2 * W)
    assert _trips() == ([W, W] if cap is None else [1] * 2 * W)
    return out


def _case_budget_ends_on_first_trip(cap, endless, *_):
    with _Served(endless, 1, cap) as s:
        out = s.stream(PROMPTS[0], 2 + 2 * W)
    assert out[1:] == ("length", 2 + 2 * W)
    assert _trips() == ([W, W, 1] if cap is None else [1] * (2 * W + 1))
    return out


def _case_cancel_mid_window(cap, endless, *_):
    """The client goes away while a window runs: the slot is freed at
    that window's end, what had arrived is the stream's own prefix, and
    the next stream in that slot is served from clean rows."""
    set_dispatch_delay(0.01)                    # a window lasts ~80 ms
    with _Served(endless, 1, cap) as s:
        whole = s.stream(PROMPTS[0], 6 * W)[0]
        cli = ServingClient(s.server.endpoint)
        it = cli.infer_stream("lm", PROMPTS[0], max_new_tokens=6 * W)
        seen = []
        for chunk in it:
            seen += chunk
            if len(seen) > W + 1:               # inside the second window
                break
        it.close()
        cli.close()
        deadline = time.monotonic() + 30
        while s.batcher.slot_occupancy()[0] and time.monotonic() < deadline:
            time.sleep(0.005)
        assert s.batcher.slot_occupancy()[0] == 0
        assert seen == whole[:len(seen)]
        after = s.stream(PROMPTS[1], 5)
    return whole, after


def _case_join_while_a_window_runs(cap, endless, *_):
    """Two slots, three streams: the third arrives while the full lane
    runs a window and is admitted when the first slot ends."""
    set_dispatch_delay(0.01)
    with _Served(endless, 2, cap) as s:
        first = [None, None]

        def two():
            first[:] = s.together([(PROMPTS[0], 5 * W), (PROMPTS[1], 2 * W)])
        t = threading.Thread(target=two)
        t.start()
        deadline = time.monotonic() + 30
        while s.batcher.slot_occupancy()[0] < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.002)
        third = s.stream(PROMPTS[2], W + 3)     # queues behind a full lane
        t.join(timeout=120)
    if cap is None:
        assert max(_trips()) > 1
    else:
        assert set(_trips()) == {1}
    return first, third


def _case_deadline_inside_a_window(cap, endless, *_):
    """A deadline that falls inside a window: the typed error at the
    window's end (the governor shortens the window that would cross it),
    and what had arrived is the stream's own prefix."""
    with _Served(endless, 1, cap) as s:
        whole = s.stream(PROMPTS[0], 100)[0]    # warms the lane's EWMA too
        set_dispatch_delay(0.02)                # 100 tokens need 2 s
        cli = ServingClient(s.server.endpoint)
        seen = []
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded):
            for chunk in cli.infer_stream("lm", PROMPTS[0],
                                          max_new_tokens=100,
                                          deadline_ms=600.0):
                seen += chunk
        late = time.monotonic() - t0 - 0.6
        cli.close()
        assert 0 < len(seen) < 100 and seen == whole[:len(seen)]
        # within about one dispatch (8 x 20 ms) of the deadline, with
        # room for a loaded host; the whole stream would end 1.4 s late
        assert late < 1.0, late
        after = s.stream(PROMPTS[1], 5)
    return whole, after


def test_a_warm_lane_compiles_nothing_whatever_the_window(models):
    """One step executable a slot count: after the load's warm-up, windows
    of 1, 3 and STEP_WINDOW trips (and the prefills, releases and the
    early end of a window between them) lower nothing, compile nothing and
    fetch nothing from jax's persistent cache: the events
    `benchmark/run.py::CompileWatch` counts inside a cell's window.  The
    prompts come in the order second bucket, first, second: a lane's first
    admission and its later ones must find the same executables."""
    import jax.monitoring
    from benchmark.run import CompileWatch
    endless, with_eos, prompt, eos_at = models
    events, on = [], [False]

    def listen(name, secs, **kw):
        if on[0] and name.startswith(CompileWatch.WATCHED):
            events.append(name)
    jax.monitoring.register_event_duration_secs_listener(listen)
    full, last = divmod(eos_at, W)
    long_prompt = list(range(1, 21))
    try:
        for artifact, requests, want in (
                (endless, ((long_prompt, 1 + W), (prompt, 1 + 3),
                           (long_prompt, 1 + 1)), [W, 3, 1]),
                (with_eos, ((prompt, 4 * W),), [W] * full + [last])):
            _traced()
            with _Served(artifact, 1, None) as s:
                pred = s.entry.predictor
                assert pred._fns, "the load did not warm the lane"
                assert pred.prompt_bucket(len(long_prompt)) \
                    > pred.prompt_bucket(len(prompt))
                on[0] = True
                for p, max_new in requests:
                    s.stream(p, max_new)
                on[0] = False
                assert _trips() == want
                assert events == []
    finally:
        on[0] = False
