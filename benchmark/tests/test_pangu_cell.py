"""Tests of what the `openpangu_ultra_moe_718b` configuration and its cell add
to the benchmark, on the CPU: the configuration file against the catalog's
numbers and the cut's arithmetic, the latent kernel's and the held experts'
cost arithmetic, how the new readers find their operations, the driver's
layer-at-a-time comparison with the plain reference (and that it can fail),
and the cell's whole rehearsal (slow).

`rehearse.TINY` / `rehearse.TINY_TRAFFIC`: as
benchmark/tests/test_olmoe_cell.py says, both entries are made HERE, at
import.
"""

import json
import os

import numpy as np
import pytest

from benchmark import costs_mla, costs_moe, xplane
from benchmark import run as bench_run
from benchmark.tests import rehearse
from benchmark.tests.test_olmoe_cell import _Ctx

CELL, CONFIG, MIX = ("pangu_decode_saturated", "openpangu_ultra_moe_718b",
                     "pangu_decode_saturated")

rehearse.TINY.setdefault(CONFIG, lambda c: (
    c["model"].update(vocab_size=97, d_model=60, n_heads=4, n_layers=3,
                      layer_types=["mla"] * 3, max_seq_len=128,
                      prefill_buckets=[16, 32, 64, 128], q_lora_rank=24,
                      kv_lora_rank=16, qk_nope_head_dim=8,
                      qk_rope_head_dim=4, v_head_dim=8, dense_width=96,
                      n_experts=16, experts_per_token=4, expert_width=32,
                      experts_held=[4, 4]),
    c["deployment"].update(decode_slots=4),
    c.update(reference_check={"prompt_tokens": [5, 20, 40], "steps": 4})))
rehearse.TINY_TRAFFIC.setdefault(MIX, lambda m: (
    m.update(requests=32),
    m["prompt_tokens"].update(min=8, max=30),
    m["output_tokens"].update(value=24)))

# The catalog's entry (model-configs guide, architectures.jsonl,
# openPangu-Ultra-MoE-718B, `config`), number for number.
CATALOG = {"attention_bias": False, "first_k_dense_replace": 3,
           "hidden_act": "silu", "hidden_size": 7680,
           "intermediate_size": 18432, "kv_lora_rank": 512,
           "max_position_embeddings": 131072,
           "model_type": "pangu_ultra_moe", "moe_intermediate_size": 2048,
           "n_routed_experts": 256, "n_shared_experts": 1,
           "norm_topk_prob": True, "num_attention_heads": 128,
           "num_experts_per_tok": 8, "num_hidden_layers": 61,
           "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
           "q_lora_rank": 1536, "qk_nope_head_dim": 128,
           "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
           "rope_theta": 25600000, "routed_scaling_factor": 2.5,
           "sandwich_norm": True, "tie_word_embeddings": False,
           "v_head_dim": 128, "vocab_size": 153600}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "num_nextn_predict_layers"]


@pytest.fixture(scope="module")
def manifest():
    return bench_run.load_json(bench_run.MANIFEST)


@pytest.fixture(scope="module")
def config(manifest):
    return bench_run.resolve_cell(manifest, CELL)[1]


def test_configuration_keeps_every_published_width(manifest, config):
    entry = [c for c in manifest["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == config["reduced"] == REDUCED
    assert entry["source"] == config["source"]
    for key, value in CATALOG.items():
        if key in REDUCED:
            assert config[key] < value and config["published"][key] == value
            assert key in config["reduced_detail"]
        else:
            assert config[key] == value, key
    m = config["model"]       # what the program is given says the same
    assert (m["d_model"], m["n_heads"], m["n_layers"], m["vocab_size"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_hidden_layers"], config["vocab_size"])
    assert [m[k] for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                           "qk_rope_head_dim", "v_head_dim")] == [
        config[k] for k in ("q_lora_rank", "kv_lora_rank",
                            "qk_nope_head_dim", "qk_rope_head_dim",
                            "v_head_dim")] == [1536, 512, 128, 64, 128]
    # the router keeps its published width and its experts per token; the
    # experts HELD are the chip's share, the floor of the guide
    assert (m["n_experts"], m["experts_per_token"], m["expert_width"],
            m["dense_width"], m["n_shared_experts"], m["routed_scaling"],
            m["norm_topk_prob"], m["norm_eps"], m["rope_theta"],
            m["sandwich_norm"]) == (
        256, config["num_experts_per_tok"], config["moe_intermediate_size"],
        config["intermediate_size"], config["n_shared_experts"],
        config["routed_scaling_factor"], config["norm_topk_prob"],
        config["rms_norm_eps"], config["rope_theta"],
        config["sandwich_norm"])
    assert m["experts_held"][1] == config["n_routed_experts"] == 8
    assert m["vocab_size"] * 8 == 153600 and m["n_layers"] >= 1 + 4
    assert (m["n_dense_layers"], m["layer_types"]) == (1, ["mla"] * 5)
    assert (m["router"], m["weight_dtype"], m["ffn"], m["norm"]) == (
        "sigmoid", "bfloat16", "moe_swiglu", "rmsnorm")
    assert set(config["assumed"]) >= {
        "norm_placement", "router", "rope_layout", "dtype", "sampling",
        "eos_id", "max_seq_len", "prefill_buckets", "decode_slots",
        "weights"}
    assert "32" in config["deployment"]["stands_for"]
    assert config["deployment"]["decode_slots"] in (32, 48, 64)


def test_the_cut_is_the_arithmetic_the_file_states(config):
    """3.41 B parameters, 6.82 GB in bfloat16: the reference's shapes add up
    to what `reduced_detail` says, and the slots' rows to the deployment's."""
    from benchmark.reference import openpangu_ultra_moe_718b as reference
    m = config["model"]
    shapes = reference.tensor_shapes(m)
    params = sum(int(np.prod(s)) for s in shapes.values())
    rest = sum(int(np.prod(s)) * (2 if reference.at_rest(n, s).dtype.itemsize
                                  == 2 else 4) for n, s in shapes.items())
    assert round(params / 1e9, 2) == 3.41 and round(rest / 1e9, 2) == 6.83
    attention = sum(int(np.prod(shapes["l1_" + n])) for n in (
        "wq_a", "wq_b", "wkv_a", "wkv_b", "wo"))
    assert round(attention / 1e6, 1) == 196.6
    assert int(np.prod(shapes["l1_w_gate"])) * 3 // 8 == 3 * 7680 * 2048
    slots = config["deployment"]["decode_slots"]
    row = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    assert row == 576 and -(-row // 128) * 128 == 640
    assert slots * m["max_seq_len"] * m["n_layers"] * 640 * 4 \
        == config["deployment"]["latent_table_bytes"]


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
    names = {m["name"] for m in per_layer}
    assert names >= {"mla_attention_roofline", "mla_attention_ms_per_trip",
                     "mla_proj_ms_per_trip", "latent_cache_bytes_per_slot",
                     "held_experts_ffn_roofline", "prefill_share_of_lane",
                     "decode_round_ms.saturated", "moe_ffn_ms_per_round",
                     "slots_busy_share", "decode_kv_stream_share"}
    # their readers charge 4 bytes a weight and per-head K and V rows
    assert not names & {"moe_ffn_roofline", "decode_attention_roofline",
                        "gqa_attention_roofline"}
    buckets = config["model"]["prefill_buckets"]
    from benchmark import loadgen
    lens = loadgen.quantile_values(mix["prompt_tokens"], mix["requests"])
    assert {min(b for b in buckets if n <= b) for n in lens} == {512, 1024}


@pytest.mark.parametrize("seconds,want", [(45.0, 6.0), (5.0, 2.5)])
def test_the_profiled_sub_window_holds_a_whole_wave(config, monkeypatch,
                                                    seconds, want):
    """The cell's 64 streams end in one dispatch, so 64 prefills run in a
    row (~3 s with no decode dispatch) before ~2 s of dispatches: the driver
    profiles the configuration's `trace_seconds`, capped at half the window
    as run.py caps its own 3 s, and hands the rest to `serve_decode_arch`
    with every scope of `trace_scopes` named."""
    import types
    from benchmark.drivers import serve_decode_arch as arch
    from benchmark.drivers import serve_decode_hybrid, serve_decode_latent
    seen = []
    theirs = arch.step_scope_ops
    monkeypatch.setattr(arch, "run", lambda ctx: seen.append(
        (ctx.trace_seconds, arch.step_scope_ops)))
    ctx = types.SimpleNamespace(config=config, seconds=seconds,
                                trace_seconds=min(3.0, seconds / 2.0))
    serve_decode_latent.run(ctx)
    assert seen == [(want, serve_decode_hybrid.step_scope_ops)]
    assert arch.step_scope_ops is theirs
    # a wave: 64 prefills at 31-56 ms and 127 trips at ~16 ms
    assert config["trace_seconds"] > 64 * 0.056 + 127 * 0.0165


def test_latent_and_held_expert_costs_by_hand():
    flops, bytes_ = costs_mla.latent_attention_cost(
        [700, 300], n_heads=128, row_lanes=576, value_lanes=512)
    assert flops == 1000 * 128 * 2 * (576 + 512) == 1000 * 278528
    assert bytes_ == 1000 * 576 * 4 + 2 * 128 * (576 + 512) * 4
    # a row is read once for all heads: per-head K and V rows of the same
    # positions would be 71 times the bytes
    assert 128 * (192 + 128) * 4 / (576 * 4.0) > 71
    f, b = costs_mla.held_experts_ffn_cost(
        tokens=64, held_touched=7, d_model=7680, expert_width=2048,
        n_experts=256, weight_bytes=2)
    assert b == (7 * 3 * 7680 * 2048 * 2 + 7680 * 256 * 4
                 + 64 * 7680 * 2 * 4)
    assert f == 7 * 3 * 2 * 7680 * 2048 + 64 * 2 * 7680 * 256
    # at 4 bytes a weight it is costs_moe's own count
    f4, b4 = costs_mla.held_experts_ffn_cost(64, 7, 7680, 2048, 256, 4)
    assert (f4, b4) == costs_moe.moe_ffn_cost(64, 7, 7680, 2048, 256,
                                              7 / 64.0)


class _Rec(object):
    def __init__(self, prompt_len, times, max_new=128):
        self.prompt_len, self.token_times, self.max_new = (prompt_len, times,
                                                           max_new)
        self.done = None


def test_the_new_readers_find_and_time_their_operations():
    # two dispatches of 2 trips, 10 ms each on the device: the kernel 2 ms a
    # trip, the projections 1 ms a trip, the routed FFN 1.5 ms a dispatch
    ops = []
    for r in (0.0, 0.010):
        ops += [("%fusion.7 = f32[2,128,576] fusion(...)", r, r + 0.001),
                ("%_step_math.4 = f32[2,128,512] custom-call(...), custom_"
                 "call_target=\"tpu_custom_call\", frontend_attributes={"
                 "kernel_metadata={}}", r + 0.001, r + 0.003),
                ("%fusion.8 = f32[2,7680] fusion(...)", r + 0.003,
                 r + 0.004),
                ("%_step_math.4 = f32[2,128,512] custom-call(...), custom_"
                 "call_target=\"tpu_custom_call\", frontend_attributes={"
                 "kernel_metadata={}}", r + 0.004, r + 0.006),
                ("%ragged-dot-none.2 = f32[16,2048] custom-call(...)",
                 r + 0.006, r + 0.0075),
                ("%fusion.9 = f32[2,7680] fusion(...)", r + 0.0075,
                 r + 0.010)]
    trace = xplane.Trace({0: ops})
    trace.anchor = (0.0, 0.0, 100.0)           # monotonic 100 s = trace 0 s
    steps = [{"name": "serving/decode_step", "t0": 100.0 + r,
              "t1": 100.0 + r + 0.010, "attrs": {"tokens": 4, "trips": 2}}
             for r in (0.0, 0.010)]
    spans = steps + [
        {"name": "decode/fetch", "t0": s["t0"] + 0.001, "t1": s["t1"],
         "attrs": {"phase": "step", "moe_experts_touched": 6,
                   "latent_cache_bytes": 2 * 4096 * 640 * 4 * 2,
                   "moe_experts_held": 8}} for s in steps]
    meta = {"n_layers": 2, "n_dense_layers": 1, "d_model": 7680,
            "n_heads": 128, "kv_lora_rank": 512, "qk_rope_head_dim": 64,
            "expert_width": 2048, "n_experts": 256, "experts_held": [96, 8],
            "weight_dtype": "bfloat16"}
    recs = [_Rec(300, [99.0]), _Rec(700, [99.5])]
    run = {"trace_window_monotonic": (100.0, 100.021),
           "trace_window": (0.0, 0.021), "window": (100.0, 100.021),
           "slots": 2, "records": recs, "device_kind": "TPU v5 lite",
           "kernel_match": {"mla_attention": "kernel_metadata={}"},
           "scope_ops": {"mla_proj": ["fusion.7", "fusion.8"],
                         "moe_ffn": ["ragged-dot-none.2"]},
           "meta": meta}
    read = bench_run.load_reader
    assert read("mla_attention_ms_per_trip")(spans, trace, run) \
        == pytest.approx(2.0)
    assert read("mla_proj_ms_per_trip")(spans, trace, run) \
        == pytest.approx(1.0)
    assert read("latent_cache_bytes_per_slot")(spans, trace, run) \
        == 2 * 4096 * 640 * 4
    # the kernel: 2 dispatches x 2 trips x 2 layers over streams of 301 and
    # 701 positions, a token longer at the second trip
    flops = bytes_ = 0.0
    for trip in (0, 1):
        f, b = costs_mla.latent_attention_cost(
            [301 + trip, 701 + trip], 128, 576, 512)
        flops, bytes_ = flops + 2 * 2 * f, bytes_ + 2 * 2 * b
    least = max(flops / 197e12, bytes_ / 819e9)
    assert read("mla_attention_roofline")(spans, trace, run) \
        == pytest.approx(100 * least / 0.008)
    # the held experts: per dispatch 1 routed layer x 2 trips = 2 calls of
    # 2 tokens and 3 held experts touched each
    f, b = costs_mla.held_experts_ffn_cost(2, 3, 7680, 2048, 256, 2)
    assert read("held_experts_ffn_roofline")(spans, trace, run) \
        == pytest.approx(100 * (2 * 2 * b / 819e9) / 0.003)
    # a program without the scopes, the kernel or the meta (the parent, or
    # another configuration): nothing to read, no raise
    bare = dict(run, scope_ops={}, kernel_match={},
                meta={"n_layers": 2, "d_model": 64, "n_heads": 4})
    quiet = [dict(s, attrs={"phase": "step"}) if s["name"] == "decode/fetch"
             else s for s in spans]
    for name in ("mla_attention_roofline", "mla_attention_ms_per_trip",
                 "mla_proj_ms_per_trip", "held_experts_ffn_roofline",
                 "latent_cache_bytes_per_slot"):
        assert read(name)(quiet, trace, bare) is None, name


def _tiny(seed, tolerances):
    """(ctx, meta) of the configuration at its tiny size, as the driver
    would see them."""
    from benchmark.reference import openpangu_ultra_moe_718b as reference
    cfg = bench_run.load_json(os.path.join(
        bench_run.ROOT, "benchmark", "configs", CONFIG + ".json"))
    rehearse.TINY[CONFIG](cfg)
    cfg["tolerances"] = tolerances
    return (_Ctx(seed=seed, reference=reference, config=cfg),
            dict(cfg["model"]))


def test_driver_holds_the_program_to_the_reference_layer_by_layer(
        tmp_path, monkeypatch):
    from benchmark.drivers import serve_decode_arch as drv
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             save_decode_model)
    # fp32 on the CPU: both sides agree to rounding, no near-tie allowance
    ctx, meta = _tiny(2 ** 31 + 9, {"logits": 1e-4, "top1_gap": 2e-4})
    state = drv.state_to_host(ctx, meta)
    # the driver's draw is the artifact's own dtypes: bf16 matmul weights
    assert state["l1_w_gate"].dtype.itemsize == 2
    assert state["l1_router"].dtype == np.float32
    art = save_decode_model(str(tmp_path / "lm"), state, meta)
    pred = GenerativePredictor(art)
    assert drv.check_against_reference(ctx, pred, meta)
    facts = ctx.logged[-1]
    assert facts["buckets"] == [16, 32, 64]
    assert facts["positions"] == 3 * 5 and facts["max_logit_diff"] < 1e-4
    assert facts["over_the_bounds"] == 0
    # another seed is another model, and the check must fail
    other = _Ctx(seed=ctx.seed + 1, reference=ctx.reference,
                 config=ctx.config)
    assert not drv.check_against_reference(other, pred, meta)
    # fp32 against fp32 rounds far less than the bf16 reference does
    assert facts["precision_positions"] == 3 * 4
    assert facts["precision_ratio"] < 0.01
    assert facts["logit_diff_median_lower_precision"] > 1e-3


PLANTED = {
    # rope lanes left unrotated in the cached row
    "unrotated_rope_in_the_row": lambda dec, mp: mp.setattr(
        dec, "_rope", _unrotated_rows(dec._rope)),
    # the shared expert left out
    "no_shared_expert": lambda dec, mp: mp.setattr(
        dec, "_swiglu", _no_shared(dec._swiglu)),
    # scaling 1 for 2.5
    "scaling_1": lambda dec, mp: mp.setattr(
        dec, "moe_ffn", _scaling_one(dec.moe_ffn)),
    # the post-sublayer norms left out
    "no_sandwich": lambda dec, mp: mp.setattr(
        dec.GenerativePredictor, "_norm", _no_post_norm(
            dec.GenerativePredictor._norm)),
}


def _unrotated_rows(rope):
    def f(x, positions, theta):
        # the shared key is the one input with a single "head"
        return x if x.shape[-2] == 1 else rope(x, positions, theta)
    return f


def _no_shared(swiglu):
    def f(h, gate, up, down):
        out = swiglu(h, gate, up, down)
        return out * 0.0 if gate.shape[-1] == 32 else out    # expert_width
    return f


def _scaling_one(moe_ffn):
    def f(*a, **kw):
        return moe_ffn(*a, **dict(kw, scaling=1.0))
    return f


def _no_post_norm(norm):
    def f(self, x, state, name):
        return x if name.endswith(("ln1p", "ln2p")) else norm(self, x, state,
                                                              name)
    return f


@pytest.mark.parametrize("fault", sorted(PLANTED))
def test_a_planted_fault_is_refused_at_the_tiny_size(tmp_path, monkeypatch,
                                                     fault):
    """The comparison that decides `correct`, at a tiny size with the chip's
    own tolerances' ORDER (logits 0.08): each fault the issue names moves
    the logits by far more."""
    from benchmark.drivers import serve_decode_arch as drv
    from paddle_tpu.inference import decode as dec
    ctx, meta = _tiny(2 ** 31 + 21, {"logits": 0.08, "top1_gap": 0.16})
    art = dec.save_decode_model(str(tmp_path / "lm"),
                                drv.state_to_host(ctx, meta), meta)
    PLANTED[fault](dec, monkeypatch)
    pred = dec.GenerativePredictor(art)
    assert not drv.check_against_reference(ctx, pred, meta)
    assert ctx.logged[-1]["over_the_bounds"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
def test_pangu_cell_rehearsal(manifest, trace, monkeypatch):
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
        optional = {"mla_attention_roofline", "mla_attention_ms_per_trip",
                    "mla_proj_ms_per_trip", "moe_ffn_ms_per_round",
                    "held_experts_ffn_roofline"}
        assert names - optional <= set(last["metrics"]) <= names
        fetch = [json.loads(ln) for ln in lines if '"served_check"' in ln]
        assert fetch and fetch[0]["ok"]
    else:
        assert set(last["metrics"]) == names
