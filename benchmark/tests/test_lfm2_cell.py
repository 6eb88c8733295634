"""Tests of what the `lfm2_24b_a2b` configuration and its cell add to the
benchmark, on the CPU: the configuration file against the catalog's numbers
and the issue's cut, the cell against the issue's traffic, the cost
arithmetic and the three readers (and that each reads nothing, without a
raise, from a program that lacks what they read), the driver's
layer-at-a-time comparison of a hybrid stack with the plain reference under
a TIED head (and that it can fail), and the cell's whole rehearsal (slow).

`rehearse.TINY` / `rehearse.TINY_TRAFFIC` shrink EVERY configuration and mix
of the manifest before any cell's CPU rehearsal and know only those of their
day (PERF.md section 7): both entries are made HERE, at import, as
test_olmoe_cell.py makes its own.
"""

import json
import os

import numpy as np
import pytest

from benchmark import costs_hybrid, xplane
from benchmark import run as bench_run
from benchmark.tests import rehearse

CELL, CONFIG, MIX = ("lfm2_decode_saturated", "lfm2_24b_a2b",
                     "lfm2_decode_saturated")

rehearse.TINY.setdefault(CONFIG, lambda c: (
    c["model"].update(vocab_size=97, d_model=64, n_heads=8, n_kv_heads=2,
                      max_seq_len=128, prefill_buckets=[16, 32, 64, 128],
                      n_experts=8, experts_per_token=2, expert_width=32,
                      dense_width=96),
    c["deployment"].update(decode_slots=4),
    c.update(reference_check={"prompt_tokens": [5, 20, 40], "steps": 4})))
rehearse.TINY_TRAFFIC.setdefault(MIX, lambda m: (
    m.update(requests=32),
    m["prompt_tokens"].update(min=8, max=30),
    m["output_tokens"].update(value=24)))

# The catalog's entry (model-configs guide, architectures.jsonl,
# LFM2-24B-A2B, `config`), number for number; `layer_types` by its pattern.
CATALOG = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
           "intermediate_size": 11776, "max_position_embeddings": 128000,
           "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
           "norm_eps": 1e-05, "norm_topk_prob": True,
           "num_attention_heads": 32, "num_dense_layers": 2,
           "num_experts": 64, "num_experts_per_tok": 4,
           "num_hidden_layers": 40, "num_key_value_heads": 8,
           "rope_parameters": {"rope_theta": 1000000,
                               "rope_type": "default"},
           "routed_scaling_factor": 1, "use_expert_bias": True,
           "vocab_size": 65536,
           "layer_types": ["conv", "conv"]
           + ["full_attention", "conv", "conv", "conv"] * 9
           + ["full_attention", "conv"]}


@pytest.fixture(scope="module")
def manifest():
    return bench_run.load_json(bench_run.MANIFEST)


@pytest.fixture(scope="module")
def config(manifest):
    return bench_run.resolve_cell(manifest, CELL)[1]


def test_configuration_keeps_every_published_width(manifest, config):
    entry = [c for c in manifest["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert len(CATALOG["layer_types"]) == 40
    for key, value in CATALOG.items():
        if key in config["reduced"]:
            assert config[key] < value
        else:
            assert config[key] == value, key
    m = config["model"]       # what the program is given says the same
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["n_layers"],
            m["vocab_size"], m["conv_kernel"], m["dense_width"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["num_hidden_layers"],
        config["vocab_size"], config["conv_L_cache"],
        config["intermediate_size"])
    assert (m["n_experts"], m["experts_per_token"], m["expert_width"],
            m["norm_topk_prob"], m["norm_eps"], m["rope_theta"]) == (
        config["num_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], config["norm_topk_prob"],
        config["norm_eps"], config["rope_parameters"]["rope_theta"])
    assert (m["norm"], m["position"], m["qk_norm"], m["ffn"], m["router"],
            m["head"]) == ("rmsnorm", "rope", "head", "moe_swiglu",
                           "sigmoid_bias", "tied")
    # the cut: published layers 0 and 2-5, the leading dense layers once,
    # a whole period and four layers after the dense one
    kept = config["layers_kept"]
    assert kept == [0, 2, 3, 4, 5] and m["n_dense_layers"] == 1
    assert m["layer_types"] == [
        {"full_attention": "attention"}.get(t, t)
        for t in (config["layer_types"][i] for i in kept)]
    assert m["max_seq_len"] == 4096 <= config["max_position_embeddings"]
    assert set(config["assumed"]) >= {
        "dtype", "head", "renormalisation_epsilon", "expert_bias", "weights",
        "sampling", "eos_id", "max_seq_len", "prefill_buckets",
        "decode_slots", "max_new_tokens", "routed_scaling_factor"}
    assert config["deployment"]["decode_slots"] in (16, 24, 32)
    # 10.80 GB of fp32 weights, every expert and every vocabulary row held
    from benchmark.reference import lfm2_24b_a2b as reference
    n = sum(int(np.prod(s)) for s in reference.tensor_shapes(m).values())
    assert 4 * n == pytest.approx(10.80e9, rel=2e-3)


def test_the_cell_is_the_issues(manifest):
    cell, config, mix, e2e, per_layer = bench_run.resolve_cell(manifest,
                                                               CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX,
                                                                1)
    assert (mix["loop"], mix["clients_per_slot"], mix["requests"]) == (
        "closed", 2, 256)
    assert mix["prompt_tokens"] == {"kind": "uniform", "min": 512,
                                    "max": 2048}
    assert mix["output_tokens"] == {"kind": "fixed", "value": 128}
    assert {m["name"] for m in e2e} == {"tokens_per_s", "setup_s"}
    names = {m["name"] for m in per_layer}
    assert names >= {"decode_round_ms.saturated", "moe_ffn_ms_per_round",
                     "moe_ffn_roofline", "gqa_attention_roofline",
                     "short_conv_ms_per_trip", "conv_state_bytes_per_slot",
                     "decode_trips_per_dispatch", "slots_busy_share"}
    # its reader counts multi-head rows in every layer: 20x this model's
    assert "decode_attention_roofline" not in names
    for m in per_layer:
        if m["name"] in ("gqa_attention_roofline", "short_conv_ms_per_trip",
                         "conv_state_bytes_per_slot"):
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
    buckets = config["model"]["prefill_buckets"]
    from benchmark import loadgen
    lens = loadgen.quantile_values(mix["prompt_tokens"], mix["requests"])
    assert {min(b for b in buckets if n <= b) for n in lens} == {1024, 2048}


def test_gqa_cost_by_hand():
    flops, bytes_ = costs_hybrid.gqa_attention_cost([100, 300], 32, 8, 64)
    assert flops == 2 * 2 * 400 * 32 * 64
    assert bytes_ == 2 * 400 * 8 * 64 * 4 + 2 * 32 * 64 * 8
    # the rows are read once a K/V head: a quarter of multi-head's
    from benchmark import costs
    _, mha = costs.decode_attention_cost([100, 300], 32, 64)
    assert (mha - 2 * 32 * 64 * 8) == 4 * (bytes_ - 2 * 32 * 64 * 8)


class _Rec(object):
    def __init__(self, prompt_len, token_times, max_new=128, done=None):
        self.prompt_len, self.token_times = prompt_len, token_times
        self.max_new, self.done = max_new, done


def test_the_three_readers_read_and_read_nothing_from_the_parent():
    # two dispatches of 3 trips, 10 ms each; per trip the kernel runs 1 ms
    # and the conv layers' operations 0.5 ms
    ops = []
    for r in (0.0, 0.010):
        for t in range(3):
            at = r + 0.003 * t
            ops += [("%_step_math.4 = f32[2,32,128] custom-call(...), "
                     "frontend_attributes={kernel_metadata={}}", at,
                     at + 0.001),
                    ("%fusion.7 = f32[2,6144] fusion(...)", at + 0.001,
                     at + 0.0015),
                    ("%fusion.9 = f32[2,2048] fusion(...)", at + 0.0015,
                     at + 0.003)]
    trace = xplane.Trace({0: ops})
    trace.anchor = (0.0, 0.0, 100.0)           # monotonic 100 s = trace 0 s
    spans = [{"name": "serving/decode_step", "t0": 100.0 + r,
              "t1": 100.0 + r + 0.010, "attrs": {"tokens": 6, "trips": 3}}
             for r in (0.0, 0.010)]
    spans += [{"name": "decode/fetch", "t0": s["t0"] + 0.001, "t1": s["t1"],
               "attrs": {"phase": "step", "trips": 3, "conv_layers": 4,
                         "attn_layers": 1, "conv_state_bytes": 4 * 2 * 2
                         * 2048 * 4}} for s in spans[:2]]
    recs = [_Rec(100, [99.0]), _Rec(300, [99.5, 99.9])]
    meta = {"n_layers": 5, "d_model": 2048, "n_heads": 32, "n_kv_heads": 8,
            "layer_types": ["conv", "attention", "conv", "conv", "conv"]}
    run = {"trace_window": (0.0, 0.021),
           "trace_window_monotonic": (100.0, 100.021),
           "window": (100.0, 100.02), "slots": 2, "records": recs,
           "scope_ops": {"short_conv": ["fusion.7"]},
           "kernel_match": {"gqa_attention": "kernel_metadata={}"},
           "device_kind": "TPU v5 lite", "meta": meta}
    ms = bench_run.load_reader("short_conv_ms_per_trip")(spans, trace, run)
    assert ms == pytest.approx(0.5)
    per_slot = bench_run.load_reader("conv_state_bytes_per_slot")(
        spans, trace, run)
    assert per_slot == 4 * 2 * 2048 * 4        # 4 layers x (3 - 1) x D x 4 B
    share = bench_run.load_reader("gqa_attention_roofline")(spans, trace,
                                                            run)
    # one attention layer; lengths 101 and 302 (prompt + tokens received),
    # a token longer each trip, in both dispatches
    want = sum(costs_hybrid.gqa_attention_cost([101 + t, 302 + t], 32, 8,
                                               64)[1] for t in range(3))
    assert share == pytest.approx(100 * (2 * want / 819e9) / 0.006)
    assert share < 100.0
    # the parent of the PR that added them: no scope, no attribute, a meta
    # without the keys - nothing to read, no raise
    bare = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                           if k in ("phase", "tokens")}) for s in spans]
    old = dict(run, scope_ops={}, kernel_match={"decode_attention": "x"},
               meta={"n_layers": 5, "d_model": 2048, "n_heads": 32})
    for name in ("short_conv_ms_per_trip", "conv_state_bytes_per_slot",
                 "gqa_attention_roofline"):
        assert bench_run.load_reader(name)(bare, trace, old) is None, name


class _Ctx(object):
    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.logged = []

    def log(self, **fields):
        self.logged.append(fields)


def _tiny_config():
    cfg = bench_run.load_json(os.path.join(
        bench_run.ROOT, "benchmark", "configs", CONFIG + ".json"))
    rehearse.TINY[CONFIG](cfg)
    return cfg


def test_reference_draws_a_tied_head_and_every_kind_of_layer():
    from benchmark.reference import lfm2_24b_a2b as reference
    from paddle_tpu.inference.decode import decode_state_shapes
    meta = _tiny_config()["model"]
    shapes = reference.tensor_shapes(meta)
    # the reference's tensors are the artifact's, name for name
    assert shapes == decode_state_shapes(meta) and "lm_head" not in shapes
    assert set(reference.layer_names(meta, 0)) >= {"conv_in", "ffn_gate"}
    assert set(reference.layer_names(meta, 1)) >= {"wq", "kn_g", "router",
                                                   "expert_bias"}
    bias = np.asarray(reference.draw_tensor("l1_expert_bias", (8,), 3))
    assert bias.std() > 0 and np.abs(bias).max() < 0.3
    again = np.asarray(reference.draw_tensor("l1_expert_bias", (8,), 3))
    assert (bias == again).all()                   # (seed, name) alone
    # imports nothing of the program
    src = open(reference.__file__).read()
    assert "paddle_tpu" not in src.split('"""')[2]


def test_driver_holds_a_hybrid_stack_to_the_reference_layer_by_layer(
        tmp_path, monkeypatch):
    from benchmark.drivers import serve_decode_arch as arch
    from benchmark.drivers import serve_decode_hybrid as drv
    from benchmark.reference import lfm2_24b_a2b as reference
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             save_decode_model)
    cfg = _tiny_config()
    cfg["reference_check"]["steps"] = 12        # past the cone's 7
    meta = dict(cfg["model"])
    # fp32 on the CPU: both sides agree to rounding; what the cell's file
    # allows a position whose cone holds a differing pick, allowed here too
    cfg["tolerances"] = {"logits": 1e-4, "top1_gap": 2e-4, "router_gap": 1.0,
                         "near_tie_share": 0.8, "logits_near_tie": 1e3}
    ctx = _Ctx(seed=2 ** 31 + 9, reference=reference, config=cfg)
    art = save_decode_model(str(tmp_path / "lm"),
                            arch.state_to_host(ctx, meta), meta)
    pred = GenerativePredictor(art)
    # as `run` does it: its functions put in place around the check
    monkeypatch.setattr(arch, "reference_rows", drv.reference_rows)
    monkeypatch.setattr(arch, "program_logits", drv.program_logits)
    assert arch.check_against_reference(ctx, pred, meta)
    facts = ctx.logged[-1]
    assert facts["buckets"] == [16, 32, 64]
    assert facts["positions"] == 3 * 13 and facts["max_logit_diff"] < 1e-4
    assert facts["precision_ratio"] < 0.01
    # the flag a position is given: excusable (0) while its cone reaches
    # into the prompt, whose picks the program does not hand out (the
    # prompt's last position and the first six steps), never afterwards
    flags = np.array([c[0] for c in facts["gap_diff_top1"]]).reshape(3, 13)
    assert (flags[:, :7] == 0).all() and (flags[:, 7:] == drv.AGREE).all()
    assert facts["near_ties"] == 3 * 7
    # the reference was hinted every decision of the steps; in fp32 on the
    # CPU the program chose as the reference does, everywhere
    hinted = [f for f in ctx.logged if f.get("phase") == "routing_check"]
    assert [f["dtype"] for f in hinted] == ["float32", "bfloat16"]
    assert hinted[0]["decisions"] == 3 * 4 * 12
    assert hinted[0]["program_chose_otherwise"] == 0
    assert hinted[0]["margin_largest_where_held"] == 0
    # the reference's weights come from the seed, not from the predictor:
    # another seed is another model, and the check must fail
    other = _Ctx(seed=ctx.seed + 1, reference=reference, config=cfg)
    assert not arch.check_against_reference(other, pred, meta)
    # the scopes the configuration lists are found in the step executable
    ops = drv.step_scope_ops(pred, 2, cfg)
    assert set(ops) == {"moe_ffn", "short_conv"}
    assert all(ops.values())


def test_cone_of_the_cut():
    """Behind the four routed layers (attention, conv, conv, conv) a
    position hangs on 7, 5, 3 and 1 positions of their decisions; an
    attention layer behind a routed FFN would make that every position."""
    from benchmark.drivers import serve_decode_hybrid as drv
    from paddle_tpu.inference.decode import layer_kinds
    meta = dict(_tiny_config()["model"])
    assert drv.cone(layer_kinds(meta), 3) == {0: 7, 1: 5, 2: 3, 3: 1}
    with pytest.raises(ValueError, match="attention layer behind"):
        drv.cone(layer_kinds(dict(meta, layer_types=[
            "conv", "conv", "attention", "conv", "conv"])), 3)


def test_reference_follows_a_hint_through_a_near_tie_and_nothing_else():
    """`_routed_ffn` with the other side's picks: the 4th expert swapped
    for the 5th is followed where the two biased scores lie within the
    margin and refused where they do not; no hint, or the reference's own
    picks as the hint, changes nothing."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import lfm2_24b_a2b as reference
    meta = dict(_tiny_config()["model"], n_experts=16, experts_per_token=4)
    k, rng = 4, np.random.default_rng(5)
    g = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    w = {n: reference.draw_tensor("l1_" + n, shape, 7) for n, shape in (
        ("router", (64, 16)), ("expert_bias", (16,)),
        ("w_gate", (16, 64, 32)), ("w_up", (16, 64, 32)),
        ("w_down", (16, 32, 64)))}
    with jax.default_matmul_precision("highest"):
        out, gap, own, short = reference._routed_ffn(g, w, meta)
        assert np.isinf(np.asarray(short)).all()
        biased = jax.nn.sigmoid(g @ w["router"]) + w["expert_bias"]
        order = np.asarray(jnp.argsort(-biased, axis=-1))
        assert (np.sort(order[:, :k], axis=-1) == np.asarray(own)).all()
        same = reference._routed_ffn(g, w, meta, jnp.asarray(own), 0.0)
        assert np.array_equal(np.asarray(same[0]), np.asarray(out))
        assert (np.asarray(same[3]) == 0).all()
        # the other side kept the 5th for the 4th, at the even positions
        swapped = np.sort(np.concatenate(
            [order[:, :k - 1], order[:, k:k + 1]], axis=1), axis=-1)
        hint = np.where((np.arange(64) % 2 == 0)[:, None], swapped, -1)
        margin = float(np.median(np.asarray(gap)))
        got, _, used, short = reference._routed_ffn(
            g, w, meta, jnp.asarray(hint, jnp.int32), margin)
    used, short, gap = np.asarray(used), np.asarray(short), np.asarray(gap)
    even = np.arange(64) % 2 == 0
    assert np.allclose(short[even], gap[even]) and np.isinf(short[~even]).all()
    follow = even & (gap <= margin)
    assert 5 < follow.sum() < even.sum()
    assert (used[follow] == swapped[follow]).all()
    assert (used[~follow] == np.asarray(own)[~follow]).all()
    moved = np.abs(np.asarray(got - out)).max(axis=-1) > 1e-6
    assert (moved == follow).all()
    # a margin a position: the first 16 followed whatever their gap
    within = np.where(np.arange(64) < 16, np.inf, margin).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        _, _, used, _ = reference._routed_ffn(
            g, w, meta, jnp.asarray(hint, jnp.int32), jnp.asarray(within))
    follow = even & ((gap <= margin) | (np.arange(64) < 16))
    assert (np.asarray(used)[follow] == swapped[follow]).all()
    assert (np.asarray(used)[~follow] == np.asarray(own)[~follow]).all()


@pytest.mark.parametrize("fault", ["lost_prefix", "off_by_one",
                                   "foreign_conv_state"])
def test_a_fault_in_one_slot_is_refused_where_a_flip_is_excused(
        tmp_path, monkeypatch, fault):
    """What the picks buy (PERF.md section 6, PR 31): with every position
    excusable up to the loose bound, a slot that lost its K/V prefix or ran
    one position off read `correct` on the chip.  A fault moves the picks
    too, but not through near-ties of the reference's scores: the reference
    does not follow, the positions past the cone's reach into the prompt
    have no excuse, and each fault is refused under the same loose bounds
    and a margin as wide as the cell's."""
    from benchmark.drivers import serve_decode_arch as arch
    from benchmark.drivers import serve_decode_hybrid as drv
    from benchmark.reference import lfm2_24b_a2b as reference
    from paddle_tpu.inference import decode
    cfg = _tiny_config()
    cfg["reference_check"] = {"prompt_tokens": [20, 40], "steps": 16}
    meta = dict(cfg["model"])
    cfg["tolerances"] = {"logits": 1e-4, "top1_gap": 1e3, "router_gap": 1.0,
                         "near_tie_share": 0.8, "logits_near_tie": 1e3,
                         "precision_ratio": 1e9, "router_margin": 0.02}
    ctx = _Ctx(seed=2 ** 31 + 11, reference=reference, config=cfg)
    pred = decode.GenerativePredictor(decode.save_decode_model(
        str(tmp_path / "lm"), arch.state_to_host(ctx, meta), meta))
    monkeypatch.setattr(arch, "reference_rows", drv.reference_rows)
    monkeypatch.setattr(arch, "program_logits", drv.program_logits)
    assert arch.check_against_reference(ctx, pred, meta)
    prefill = decode.DecodeSession.prefill

    def faulty(self, slot, tokens):
        tok = prefill(self, slot, tokens)
        if slot == 0 and fault == "lost_prefix":
            self._kc = self._kc.at[:, 0].set(0.0)
            self._vc = self._vc.at[:, 0].set(0.0)
        if slot == 0 and fault == "off_by_one":
            self.lengths[0] -= 1
        if slot == 1 and fault == "foreign_conv_state":
            self._cs = self._cs.at[:, 0].set(self._cs[:, 1])
        return tok
    monkeypatch.setattr(decode.DecodeSession, "prefill", faulty)
    assert not arch.check_against_reference(ctx, pred, meta)
    facts = ctx.logged[-1]
    assert facts["over_the_bounds"] > facts["excused"] + facts["strays"]


def test_driver_fails_at_once_on_a_program_without_the_keys(monkeypatch):
    from benchmark.drivers import serve_decode_hybrid as drv
    from paddle_tpu.inference import decode
    monkeypatch.setattr(decode, "BLOCK_DEFAULTS", tuple(
        kv for kv in decode.BLOCK_DEFAULTS if kv[0] != "layer_types"))
    with pytest.raises(SystemExit, match="layer_types"):
        drv.run(_Ctx(config=_tiny_config()))


@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
def test_lfm2_cell_rehearsal(manifest, trace, monkeypatch):
    from benchmark import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    rc, last, lines = rehearse.rehearse(CELL, trace, seconds=5.0)
    assert rc == 0, lines[-5:]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["device"]["platform"] == "cpu"
    want = manifest["per_layer"] if trace else manifest["end_to_end"]
    names = {m["name"] for m in want
             if "workloads" not in m or CELL in m["workloads"]}
    if trace:
        # no Mosaic call on the CPU, and its host-traced op names are not
        # the step executable's instruction names
        optional = {"gqa_attention_roofline", "short_conv_ms_per_trip",
                    "moe_ffn_ms_per_round", "moe_ffn_roofline"}
        assert names - optional <= set(last["metrics"]) <= names
        assert last["metrics"]["conv_state_bytes_per_slot"]["value"] \
            == 4 * 2 * 64 * 4
        fetch = [json.loads(ln) for ln in lines if '"served_check"' in ln]
        assert fetch and fetch[0]["ok"]
    else:
        assert set(last["metrics"]) == names
