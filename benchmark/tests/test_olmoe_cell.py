"""Tests of what the `olmoe_1b_7b` configuration and its cell add to the
benchmark, on the CPU: the configuration file against the catalog's numbers,
the routed FFN's cost arithmetic, how its device operations are found, the
driver's layer-at-a-time comparison with the plain reference (and that it
can fail), and the cell's whole rehearsal (slow).

`rehearse.TINY` / `rehearse.TINY_TRAFFIC` shrink EVERY configuration and mix
of the manifest before any cell's CPU rehearsal and know only those of their
day (PERF.md section 7), and neither rehearse.py nor benchmark/conftest.py
may be edited by the PR that adds a configuration: both entries are made
HERE, at import - pytest imports every test module before it runs a test.
"""

import json
import os

import numpy as np
import pytest

from benchmark import costs_moe, moe_trace, xplane
from benchmark import run as bench_run
from benchmark.tests import rehearse

CELL, CONFIG, MIX = ("olmoe_decode_saturated", "olmoe_1b_7b",
                     "olmoe_decode_saturated")

rehearse.TINY.setdefault(CONFIG, lambda c: (
    c["model"].update(vocab_size=97, d_model=64, n_heads=4, n_layers=2,
                      max_seq_len=128, prefill_buckets=[16, 32, 64, 128],
                      n_experts=8, experts_per_token=2, expert_width=32),
    c["deployment"].update(decode_slots=4),
    c.update(reference_check={"prompt_tokens": [5, 20, 40], "steps": 4})))
rehearse.TINY_TRAFFIC.setdefault(MIX, lambda m: (
    m.update(requests=32),
    m["prompt_tokens"].update(min=8, max=30),
    m["output_tokens"].update(value=24)))

# The catalog's entry (model-configs guide, architectures.jsonl,
# OLMoE-1B-7B-0125-Instruct, `config`), number for number.
CATALOG = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
           "hidden_size": 2048, "intermediate_size": 1024,
           "max_position_embeddings": 4096, "model_type": "olmoe",
           "norm_topk_prob": False, "num_attention_heads": 16,
           "num_experts": 64, "num_experts_per_tok": 8,
           "num_hidden_layers": 16, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
           "tie_word_embeddings": False, "vocab_size": 50304}


@pytest.fixture(scope="module")
def manifest():
    return bench_run.load_json(bench_run.MANIFEST)


@pytest.fixture(scope="module")
def config(manifest):
    return bench_run.resolve_cell(manifest, CELL)[1]


def test_configuration_keeps_every_published_width(manifest, config):
    entry = [c for c in manifest["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]
    for key, value in CATALOG.items():
        if key in config["reduced"]:
            assert config[key] < value
        else:
            assert config[key] == value, key
    m = config["model"]       # what the program is given says the same
    assert (m["d_model"], m["n_heads"], m["n_layers"], m["vocab_size"],
            m["max_seq_len"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_hidden_layers"], config["vocab_size"],
        config["max_position_embeddings"])
    assert config["num_key_value_heads"] == m["n_heads"]      # plain MHA
    assert (m["n_experts"], m["experts_per_token"], m["expert_width"],
            m["norm_topk_prob"], m["norm_eps"], m["rope_theta"]) == (
        config["num_experts"], config["num_experts_per_tok"],
        config["intermediate_size"], config["norm_topk_prob"],
        config["rms_norm_eps"], config["rope_theta"])
    assert (m["norm"], m["position"], m["qk_norm"], m["ffn"]) == (
        "rmsnorm", "rope", True, "moe_swiglu")
    assert set(config["assumed"]) >= {"dtype", "sampling", "eos_id",
                                      "weights", "prefill_buckets",
                                      "decode_slots", "expert_width"}
    assert config["deployment"]["decode_slots"] in (4, 8, 16)


def test_the_cell_is_the_issues(manifest):
    cell, config, mix, e2e, per_layer = bench_run.resolve_cell(manifest,
                                                               CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX,
                                                                1)
    assert (mix["loop"], mix["clients_per_slot"], mix["requests"]) == (
        "closed", 2, 256)
    assert mix["prompt_tokens"] == {"kind": "uniform", "min": 256,
                                    "max": 1024}
    assert mix["output_tokens"] == {"kind": "fixed", "value": 128}
    assert {m["name"] for m in e2e} == {"tokens_per_s", "setup_s"}
    assert {m["name"] for m in per_layer} >= {
        "decode_round_ms.saturated", "moe_ffn_ms_per_round",
        "moe_ffn_roofline",
        "decode_attention_roofline", "decode_h2d_bytes_per_round",
        "slots_busy_share"}
    # the prompts fall into the two buckets the why names
    buckets = config["model"]["prefill_buckets"]
    from benchmark import loadgen
    lens = loadgen.quantile_values(mix["prompt_tokens"], mix["requests"])
    assert {min(b for b in buckets if n <= b) for n in lens} == {512, 1024}


def test_moe_cost_by_hand():
    flops, bytes_ = costs_moe.moe_ffn_cost(
        tokens=4, experts_touched=25, d_model=2048, expert_width=1024,
        n_experts=64, experts_per_token=8)
    assert flops == 4 * 8 * 3 * 2 * 2048 * 1024 + 4 * 2 * 2048 * 64
    assert bytes_ == (25 * 3 * 2048 * 1024 * 4 + 2048 * 64 * 4
                      + 4 * 2048 * 2 * 4)
    # an untouched expert costs nothing, a second token on one no bytes
    f2, b2 = costs_moe.moe_ffn_cost(5, 25, 2048, 1024, 64, 8)
    assert b2 - bytes_ == 2048 * 2 * 4 and f2 > flops


HLO = '''
HloModule jit_step
%fused_computation.3 (p: f32[4,64]) -> f32[4,64] {
  %exp.1 = f32[4,64]{1,0} exponential(%p), metadata={op_name="jit(s)/moe_ffn/moe_router/exp"}
}
ENTRY %main {
  %fusion.3 = f32[4,64]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(s)/moe_ffn/moe_router/exp"}
  %fusion.9 = f32[4,2048]{1,0} fusion(%x), kind=kLoop, metadata={op_name="jit(s)/add"}
  %ragged-dot-none.2 = f32[32,1024]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %_step_math.4 = f32[4,16,128]{2,1,0} custom-call(%q, %k), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/pallas_call"}
  ROOT %sort.1 = s32[32]{0} sort(%i), metadata={op_name="jit(s)/moe_ffn/jit(argsort)/sort"}
}
'''


def test_scope_operations_are_found_and_timed():
    names = moe_trace.scope_instruction_names(HLO, "moe_ffn", "ragged-dot")
    assert names == {"exp.1", "fusion.3", "ragged-dot-none.2", "sort.1"}
    assert moe_trace.scope_instruction_names(HLO, "moe_ffn") == {
        "exp.1", "fusion.3", "sort.1"}
    # two rounds of 10 ms on the device; the FFN's ops take 3 ms of each
    ops = []
    for r in (0.0, 0.010):
        ops += [("%fusion.9 = f32[4,2048] fusion(...)", r, r + 0.004),
                ("%fusion.3 = f32[4,64] fusion(...)", r + 0.004, r + 0.005),
                ("%ragged-dot-none.2 = f32[32,1024] custom-call(...)",
                 r + 0.005, r + 0.007),
                ("%_step_math.4 = f32[4,16,128] custom-call(...)",
                 r + 0.007, r + 0.010)]
    trace = xplane.Trace({0: ops})
    trace.anchor = (0.0, 0.0, 100.0)           # monotonic 100 s = trace 0 s
    spans = [{"name": "serving/decode_step", "t0": 100.0 + r,
              "t1": 100.0 + r + 0.010, "attrs": {"tokens": 4}}
             for r in (0.0, 0.010)]
    spans += [{"name": "decode/fetch", "t0": s["t0"] + 0.001,
               "t1": s["t1"], "attrs": {"phase": "step",
                                        "moe_experts_touched": 50,
                                        "moe_tokens_per_expert_max": 2}}
              for s in spans[:2]]
    run = {"trace_window_monotonic": (100.0, 100.021),
           "scope_ops": {"moe_ffn": sorted(names)},
           "device_kind": "TPU v5 lite",
           "meta": {"n_layers": 2, "d_model": 2048, "expert_width": 1024,
                    "n_experts": 64, "experts_per_token": 8}}
    ms = bench_run.load_reader("moe_ffn_ms_per_round")(spans, trace, run)
    assert ms == pytest.approx(3.0)
    share = bench_run.load_reader("moe_ffn_roofline")(spans, trace, run)
    _, bytes_ = costs_moe.moe_ffn_cost(4, 25, 2048, 1024, 64, 8)
    assert share == pytest.approx(100 * (2 * bytes_ / 819e9) / 0.003)
    # a program without the scope (the parent): nothing to read, no raise
    for name in ("moe_ffn_ms_per_round", "moe_ffn_roofline"):
        assert bench_run.load_reader(name)(spans, trace,
                                           dict(run, scope_ops={})) is None
    assert bench_run.load_reader("decode_round_ms.saturated")(
        spans, trace, dict(run, window=(100.0, 100.02))) \
        == pytest.approx(10.0)


class _Ctx(object):
    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.logged = []

    def log(self, **fields):
        self.logged.append(fields)


def test_driver_holds_the_program_to_the_reference_layer_by_layer(
        tmp_path, monkeypatch):
    from benchmark.drivers import serve_decode_arch as drv
    from benchmark.reference import olmoe_1b_7b as reference
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             save_decode_model)
    cfg = bench_run.load_json(os.path.join(
        bench_run.ROOT, "benchmark", "configs", CONFIG + ".json"))
    rehearse.TINY[CONFIG](cfg)
    meta = dict(cfg["model"])
    # fp32 on the CPU: both sides agree to rounding, no near-tie allowance
    cfg["tolerances"] = {"logits": 1e-4, "top1_gap": 2e-4}
    ctx = _Ctx(seed=2 ** 31 + 9, reference=reference, config=cfg)
    art = save_decode_model(str(tmp_path / "lm"),
                            drv.state_to_host(ctx, meta), meta)
    pred = GenerativePredictor(art)
    assert drv.check_against_reference(ctx, pred, meta)
    facts = ctx.logged[-1]
    assert facts["buckets"] == [16, 32, 64] and facts["near_ties"] == 0
    assert facts["positions"] == 3 * 5 and facts["max_logit_diff"] < 1e-4
    # the reference's weights come from the seed, not from the predictor:
    # another seed is another model, and the check must fail
    other = _Ctx(seed=ctx.seed + 1, reference=reference, config=cfg)
    assert not drv.check_against_reference(other, pred, meta)
    assert facts["over_the_bounds"] == 0
    # fp32 against fp32 rounds far less than the bf16 reference does
    assert facts["precision_positions"] == 3 * 4
    assert facts["precision_ratio"] < 0.01
    assert facts["logit_diff_median_lower_precision"] > 1e-3
    # a program that COMPUTES in bf16 (here: the bf16 reference's logits
    # handed in as the program's) reads a ratio of 1 and is refused by the
    # precision limit alone, the logit bounds being held wide open
    lens, seqs, got = drv.program_logits(ctx, pred, meta)
    rows = [slice(n - 1, n + len(got)) for n in lens]
    low, _ = drv.reference_rows(ctx, meta, seqs, rows,
                                drv.check_pad(ctx, pred), "bfloat16")
    as_bf16 = [np.stack([low[i][t + 1] for i in range(len(lens))])
               for t in range(len(got))]
    monkeypatch.setattr(drv, "program_logits",
                        lambda *a: (lens, seqs, as_bf16))
    cfg["tolerances"] = {"logits": 10.0, "top1_gap": 10.0,
                         "precision_ratio": 0.93}
    assert not drv.check_against_reference(ctx, pred, meta)
    assert ctx.logged[-1]["precision_ratio"] == 1.0
    assert ctx.logged[-1]["over_the_bounds"] == 0


def test_near_ties_are_excused_counted_and_bounded():
    from benchmark.drivers.serve_decode_arch import TOL_DEFAULTS, _judge
    tol = dict(TOL_DEFAULTS, logits=0.08, top1_gap=0.16, router_gap=1e-3,
               near_tie_share=0.2, logits_near_tie=1.5)
    fine = [(5e-3, 0.04, 0.0)] * 8 + [(1e-5, 0.04, 0.0)]
    ok, facts = _judge(tol, fine)
    assert ok and facts["near_ties"] == 1 and facts["excused"] == 0
    # one near-tie flips: excused, counted, its difference logged apart
    ok, facts = _judge(tol, fine + [(2e-4, 1.0, 0.5)])
    assert ok and facts["excused"] == 1 and facts["excused_share"] == 0.1
    assert facts["max_logit_diff"] == 0.04
    assert facts["max_logit_diff_excused"] == 1.0
    # the same difference where the router was decided is a fault
    assert not _judge(tol, fine + [(5e-3, 1.0, 0.0)])[0]
    # so is a near-tie past the loose bound, or a token far from the top-1
    assert not _judge(tol, fine + [(2e-4, 2.0, 0.0)])[0]
    assert not _judge(tol, fine + [(2e-4, None, 3.5)])[0]
    # and too many excused: what moves every position is no near-tie
    # (a lower precision, a fault in the semantics)
    assert not _judge(tol, [(2e-4, 0.3, 0.0)] * 9 + fine)[0]
    # with no allowance (router_gap 0) nothing is excused
    assert not _judge(dict(tol, router_gap=0.0),
                      fine + [(2e-4, 1.0, 0.5)])[0]
    # a flip at a wider gap is a stray: tolerated one in twenty, counted
    # apart; a fault that takes no notice of the router makes too many
    tol = dict(tol, router_gap_stray=3e-3, stray_share=0.05)
    many = fine * 4 + [(2e-4, 1.0, 0.5)] * 2
    ok, facts = _judge(tol, many + [(2e-3, 1.0, 0.0)] * 2)
    assert ok and (facts["excused"], facts["strays"]) == (2, 2)
    assert facts["stray_share"] == 0.05
    assert not _judge(tol, many + [(2e-3, 1.0, 0.0)] * 3)[0]
    assert not _judge(tol, many + [(4e-3, 1.0, 0.0)])[0]
    assert not _judge(tol, many + [(2e-3, 2.0, 0.0)])[0]


def test_precision_is_held_against_the_lower_precision_pair_by_pair():
    from benchmark.drivers.serve_decode_arch import TOL_DEFAULTS, _precision
    tol = dict(TOL_DEFAULTS, precision_ratio=0.93)
    # the program rounds 0.85 of what bf16 does, on easy and hard
    # sequences alike; a flip on either side of a pair is not seen
    pairs = [(0.85 * l, l) for l in (0.03, 0.04, 0.05, 0.06, 0.07)]
    ok, facts = _precision(tol, pairs + [(0.2, 0.04), (0.03, 0.2)])
    assert ok and facts["precision_ratio"] == pytest.approx(0.85)
    assert facts["precision_positions"] == 7
    # bf16 itself reads 1, whatever the sequences' own level
    ok, facts = _precision(tol, [(l, l) for _, l in pairs])
    assert not ok and facts["precision_ratio"] == 1.0
    # no limit in the configuration: only worse than bf16 is refused
    assert _precision(TOL_DEFAULTS, [(l, l) for _, l in pairs])[0]
    assert not _precision(TOL_DEFAULTS, [(1.1 * l, l) for _, l in pairs])[0]
    assert not _precision(tol, [])[0]


@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
def test_olmoe_cell_rehearsal(manifest, trace, monkeypatch):
    # the roofline reader refuses a device with no recorded peaks, as it
    # must; a rehearsal walks it with the v5e's row standing in (nothing a
    # CPU run prints is a device number)
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
        optional = {"decode_attention_roofline", "moe_ffn_ms_per_round",
                    "moe_ffn_roofline"}
        assert names - optional <= set(last["metrics"]) <= names
        fetch = [json.loads(ln) for ln in lines if '"served_check"' in ln]
        assert fetch and fetch[0]["ok"]
    else:
        assert set(last["metrics"]) == names
