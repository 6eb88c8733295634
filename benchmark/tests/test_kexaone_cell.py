"""Tests of what the `k_exaone_236b_a23b` configuration and its cell add to
the benchmark, on the CPU: the configuration file against the catalog's
numbers and the cut's arithmetic, the two kinds of K/V table's cost
arithmetic, how the new readers find their operations, the driver's
layer-at-a-time comparison with the plain reference (and that it refuses
each planted fault the issue names), and the cell's whole rehearsal (slow).

`rehearse.TINY` / `rehearse.TINY_TRAFFIC`: as
benchmark/tests/test_olmoe_cell.py says, both entries are made HERE, at
import.

`PLANTED` is also what the chip's calibration plants at the published widths
(PERF.md section 6, PR 44): each entry edits the PROGRAM (`paddle_tpu.
inference.decode`) through a monkeypatch and is undone by it.
"""

import inspect
import json
import os

import numpy as np
import pytest

from benchmark import costs_hybrid, costs_window, xplane
from benchmark import run as bench_run
from benchmark.tests import rehearse
from benchmark.tests.test_olmoe_cell import _Ctx

CELL, CONFIG, MIX = ("kexaone_decode_mixed_len", "k_exaone_236b_a23b",
                     "kexaone_decode_mixed_len")
TINY_KINDS = ["window_attention", "window_attention", "window_attention",
              "attention", "window_attention"]

rehearse.TINY.setdefault(CONFIG, lambda c: (
    c["model"].update(vocab_size=97, d_model=48, n_heads=4, n_kv_heads=2,
                      head_dim=8, n_layers=5, layer_types=list(TINY_KINDS),
                      sliding_window=8, max_seq_len=128,
                      prefill_buckets=[16, 32, 64, 128], dense_width=96,
                      n_experts=16, experts_per_token=4, expert_width=32,
                      experts_held=[4, 4]),
    c["deployment"].update(decode_slots=4),
    c.update(reference_check={"prompt_tokens": [5, 20, 40], "steps": 4})))
rehearse.TINY_TRAFFIC.setdefault(MIX, lambda m: (
    m.update(requests=32),
    m["prompt_tokens"].update(median=14, min=4, max=60),
    m["output_tokens"].update(min=12, max=24)))

# The catalog's entry (model-configs guide, architectures.jsonl,
# K-EXAONE-236B-A23B, `config`), number for number.
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 6144, "intermediate_size": 18432,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 12,
    "max_position_embeddings": 262144,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "model_type": "exaone_moe", "moe_intermediate_size": 2048,
    "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0],
    "n_group": 1, "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 8, "num_nextn_predict_layers": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": 128, "sliding_window_pattern": "LLLG",
    "sliding_windows": [128, 128, 128, 0] * 12,
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "num_nextn_predict_layers"]
NEW_READERS = ("mixed_attention_roofline", "window_attention_ms_per_trip",
               "full_attention_ms_per_trip",
               "prefill_attention_ms_per_prefill",
               "window_kv_bytes_per_slot")


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
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
            m["n_layers"], m["vocab_size"], m["dense_width"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        config["num_hidden_layers"], config["vocab_size"],
        config["intermediate_size"]) == (6144, 64, 8, 128, 5, 19200, 18432)
    # the published pattern's first five layers, kind for kind
    names = {"sliding_attention": "window_attention",
             "full_attention": "attention"}
    assert m["layer_types"] == [names[k] for k in config["layer_types"][:5]]
    assert m["layer_types"].count("window_attention") == 4
    assert [m["sliding_window"] if k == "window_attention" else 0
            for k in m["layer_types"]] == config["sliding_windows"][:5]
    assert config["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert m["n_dense_layers"] == config["first_k_dense_replace"] == 1
    # the router keeps its published width and its experts per token; the
    # experts HELD are the chip's share, the floor of the guide
    assert (m["n_experts"], m["experts_per_token"], m["expert_width"],
            m["n_shared_experts"], m["routed_scaling"], m["norm_topk_prob"],
            m["norm_eps"], m["rope_theta"]) == (
        128, config["num_experts_per_tok"], config["moe_intermediate_size"],
        config["num_shared_experts"], config["routed_scaling_factor"],
        config["norm_topk_prob"], config["rms_norm_eps"],
        config["rope_parameters"]["rope_theta"])
    assert m["experts_held"][1] == config["num_experts"] == 8
    assert m["vocab_size"] * 8 == 153600
    assert (m["router"], m["weight_dtype"], m["ffn"], m["norm"], m["head"],
            m["qk_norm"], m["position"], m["rope_layers"]) == (
        "sigmoid_bias", "bfloat16", "moe_swiglu", "rmsnorm", "untied",
        "head", "rope", "window")
    assert set(config["assumed"]) >= {
        "norm_placement", "qk_norm", "rope_layers", "selection_bias",
        "window_edge", "dtype", "weights", "sampling", "eos_id",
        "max_seq_len", "prefill_buckets", "decode_slots", "max_new_tokens"}
    assert "16 chips" in config["deployment"]["stands_for"]
    assert "4:1" in config["reduced_detail"]["num_hidden_layers"]
    assert config["deployment"]["decode_slots"] in (64, 96)
    assert config["driver"] == "serve_decode_window"


def test_the_cut_is_the_arithmetic_the_file_states(config):
    """2.504 B parameters, 5.01 GB at rest: the reference's shapes add up to
    what `reduced_detail` says, and a slot's two kinds of K/V state to the
    deployment's."""
    from benchmark.reference import k_exaone_236b_a23b as reference
    from paddle_tpu.inference import decode as dec
    m = config["model"]
    shapes = reference.tensor_shapes(m)
    assert shapes == dec.decode_state_shapes(m)
    params = sum(int(np.prod(s)) for s in shapes.values())
    rest = sum(int(np.prod(s)) * reference.at_rest(n, s).dtype.itemsize
               for n, s in shapes.items())
    assert (params, rest) == (2504068864, 5014567936)
    for n, s in shapes.items():
        assert dec._bf16_at_rest(n, np.zeros((1,) * len(s))) \
            == (reference.at_rest(n, s).dtype.itemsize == 2), n
    attention = sum(int(np.prod(shapes["l1_" + n]))
                    for n in ("wq", "wk", "wv", "wo"))
    assert round(attention / 1e6, 2) == 113.25
    assert int(np.prod(shapes["l1_w_gate"])) * 3 // 8 == 3 * 6144 * 2048
    d = config["deployment"]
    kv, conv, ssm = dec.slot_state_shapes(m, d["decode_slots"], None)
    ring = dec.window_state_shape(m, d["decode_slots"])
    assert (conv, ssm) == (None, None)
    assert kv == (1, d["decode_slots"], 4096, 8 * 128)
    assert ring == (4, d["decode_slots"], 128, 8 * 128)
    assert 2 * 4 * int(np.prod(kv)) == d["kv_table_bytes"]
    assert 2 * 4 * int(np.prod(ring)) == d["window_kv_table_bytes"]
    per_slot = (d["kv_table_bytes"] + d["window_kv_table_bytes"]) \
        / d["decode_slots"]
    assert round(per_slot / 1e6, 2) == 37.75
    # what a uniform table would reserve: five layers of max_seq_len rows
    assert round(5 * 2 * 4096 * 1024 * 4 / 1e6, 1) == 167.8


def test_the_cell_is_the_issues(manifest):
    cell, config, mix, e2e, per_layer = bench_run.resolve_cell(manifest,
                                                               CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert (mix["loop"], mix["clients_per_slot"], mix["requests"]) == (
        "closed", 2, 512)
    assert mix["prompt_tokens"] == {"kind": "lognormal", "median": 512,
                                    "sigma": 1.0, "min": 64, "max": 3968}
    assert mix["output_tokens"] == {"kind": "uniform", "min": 64,
                                    "max": 128}
    assert {m["name"] for m in e2e} == {"tokens_per_s", "setup_s"}
    names = {m["name"] for m in per_layer}
    assert set(NEW_READERS) <= names
    assert names >= {"decode_round_ms.saturated", "moe_ffn_ms_per_round",
                     "held_experts_ffn_roofline", "slots_busy_share",
                     "prefill_share_of_lane", "decode_kv_stream_share"}
    # their readers charge 4 bytes a weight, or know one kind of K/V table
    assert not names & {"moe_ffn_roofline", "decode_attention_roofline",
                        "gqa_attention_roofline",
                        "hybrid_attention_roofline",
                        "mla_attention_roofline"}
    for m in per_layer:
        assert os.path.exists(os.path.join(bench_run.LAYERS_DIR,
                                           m["name"] + ".py")), m["name"]
        assert m["moves"] == "tokens_per_s"
    # every metric the six older decode cells share is reported here too
    six = {"gpt2s_decode_saturated", "gpt2s_decode_deep",
           "olmoe_decode_saturated", "lfm2_decode_saturated",
           "pangu_decode_saturated", "falconh1_decode_saturated"}
    for m in manifest["per_layer"]:
        if six <= set(m.get("workloads", ())):
            assert CELL in m["workloads"], m["name"]
    # short and long in one queue: half under 512, a quarter over 1,024, a
    # twelfth over 2,048, all four buckets
    from benchmark import loadgen
    lens = loadgen.quantile_values(mix["prompt_tokens"], mix["requests"])
    buckets = config["model"]["prefill_buckets"]
    assert {min(b for b in buckets if n <= b) for n in lens} == set(buckets)
    share = [sum(n > edge for n in lens) / 512.0
             for edge in (512, 1024, 2048)]
    assert abs(share[0] - 0.5) < 0.01 and abs(share[1] - 0.25) < 0.01
    assert abs(share[2] - 1 / 12.0) < 0.01
    assert (min(lens), max(lens)) == (64, 3968)
    # the check's prompts: under the window, across it while decoding,
    # several wraps, the largest bucket
    chk = config["reference_check"]
    assert chk == {"prompt_tokens": [40, 100, 400, 700, 1500, 3000],
                   "steps": 32}
    assert min(b for b in buckets if 3000 <= b) == 4096


@pytest.mark.parametrize("seconds", [45.0, 5.0])
def test_the_driver_names_the_steps_and_the_prefills_scopes(config,
                                                            monkeypatch,
                                                            seconds):
    """`serve_decode_window.run` hands everything to `serve_decode_arch.run`
    with `serve_decode_ssm.step_scope_ops` in `step_scope_ops`'s place (the
    step's scopes and each bucket's prefill's), and takes it out again."""
    import types
    from benchmark.drivers import (serve_decode_arch as arch,
                                   serve_decode_ssm, serve_decode_window)
    seen = []
    theirs = arch.step_scope_ops
    monkeypatch.setattr(arch, "run", lambda ctx: seen.append(
        (ctx.trace_seconds, arch.step_scope_ops)))
    ctx = types.SimpleNamespace(config=config, seconds=seconds,
                                trace_seconds=min(3.0, seconds / 2.0))
    serve_decode_window.run(ctx)
    want = min(float(config.get("trace_seconds", 3.0)), seconds / 2.0)
    assert seen == [(want, serve_decode_ssm.step_scope_ops)]
    assert arch.step_scope_ops is theirs
    assert set(config["prefill_trace_scopes"]) == {"window_attention",
                                                   "full_attention"}
    assert set(config["trace_scopes"]) >= {"window_attention",
                                           "full_attention", "moe_ffn"}


def test_mixed_attention_cost_by_hand():
    """One trip over streams of 40, 128, 700 and 3,000 rows: the full layer
    reads them all, each of four window layers min(rows, 128)."""
    lengths = [40, 128, 700, 3000]
    flops, bytes_ = costs_window.mixed_attention_cost(
        lengths, 1, 4, 128, n_heads=64, n_kv_heads=8, head_dim=128)
    full, ring = sum(lengths), 40 + 128 + 128 + 128
    q_io = 4 * 64 * 128 * (4 + 4)
    assert bytes_ == (2.0 * full * 1024 * 4 + q_io) \
        + 4 * (2.0 * ring * 1024 * 4 + q_io)
    assert flops == 4.0 * 64 * 128 * (full + 4 * ring)
    f1, b1 = costs_hybrid.gqa_attention_cost(lengths, 64, 8, 128)
    assert costs_window.mixed_attention_cost(lengths, 1, 0, 128, 64, 8,
                                             128) == (f1, b1)
    # memory binds, and a window layer's call costs what 128 rows cost
    # however long the stream
    assert bytes_ / 819e9 > flops / 197e12
    assert costs_window.mixed_attention_cost([3000], 0, 1, 128, 64, 8, 128) \
        == costs_window.mixed_attention_cost([128], 0, 1, 128, 64, 8, 128)


class _Rec(object):
    def __init__(self, prompt_len, token_times, max_new=128, done=None):
        self.prompt_len, self.token_times = prompt_len, token_times
        self.max_new, self.done = max_new, done


def test_the_new_readers_find_and_time_their_operations():
    """Synthetic spans and a synthetic device plane: two dispatches of two
    trips over two live streams (one under the window, one far past it),
    and two prefills (buckets 512 and 4096) whose executables both have a
    `fusion.3`, under `full_attention` in one only."""
    kernel = ("%%custom-call.%d = f32[2,64,1024] custom-call(...), "
              "custom_call_target=\"tpu_custom_call\", frontend_attributes={"
              "kernel_metadata={}}")
    ops = []
    for r in (0.0, 0.010):
        ops += [("%fusion.7 = f32[2,64,1024] fusion(...)", r, r + 0.001),
                (kernel % 3, r + 0.001, r + 0.003),          # window layers
                ("%fusion.8 = f32[2,64,1024] fusion(...)", r + 0.003,
                 r + 0.004),
                (kernel % 4, r + 0.004, r + 0.007),          # the full layer
                ("%fusion.9 = f32[2,6144] fusion(...)", r + 0.007,
                 r + 0.010)]
    ops += [("%fusion.3 = f32[8,8,512,640] fusion(...)", 0.021, 0.024),
            ("%fusion.3 = f32[4096,6144] fusion(...)", 0.031, 0.033),
            ("%fusion.4 = f32[8,8,512,4096] fusion(...)", 0.033, 0.038),
            ("%fusion.5 = f32[8,8,512,640] fusion(...)", 0.038, 0.039)]
    trace = xplane.Trace({0: ops})
    trace.anchor = (0.0, 0.0, 100.0)           # monotonic 100 s = trace 0 s
    steps = [{"name": "serving/decode_step", "t0": 100.0 + r,
              "t1": 100.0 + r + 0.010, "attrs": {"tokens": 4, "trips": 2}}
             for r in (0.0, 0.010)]
    prefills = [{"name": "serving/prefill_compute", "t0": 100.020,
                 "t1": 100.030, "attrs": {"prompt": 300}},
                {"name": "serving/prefill_compute", "t0": 100.030,
                 "t1": 100.040, "attrs": {"prompt": 2049}}]
    ring_bytes = 2 * 4 * 2 * 128 * 1024 * 4
    spans = steps + prefills + [
        {"name": "decode/fetch", "t0": s["t0"] + 0.001, "t1": s["t1"],
         "attrs": {"phase": "step", "window_kv_bytes": ring_bytes,
                   "full_kv_bytes": 2 * 2 * 4096 * 1024 * 4,
                   "window_layers": 4, "full_layers": 1}} for s in steps]
    meta = {"n_layers": 5, "d_model": 6144, "n_heads": 64, "n_kv_heads": 8,
            "head_dim": 128, "sliding_window": 128,
            "layer_types": ["window_attention"] * 3 + ["attention",
                                                       "window_attention"],
            "prefill_buckets": [512, 1024, 2048, 4096]}
    recs = [_Rec(3000, [99.0]), _Rec(40, [99.5])]
    run = {"trace_window_monotonic": (100.0, 100.041),
           "trace_window": (0.0, 0.041), "window": (100.0, 100.041),
           "slots": 2, "records": recs, "device_kind": "TPU v5 lite",
           "kernel_match": {"mixed_attention": "kernel_metadata={}"},
           "scope_ops": {"window_attention": ["fusion.7", "custom-call.3"],
                         "full_attention": ["fusion.8", "custom-call.4"],
                         "window_attention@512": ["fusion.3"],
                         "full_attention@512": [],
                         "window_attention@4096": ["fusion.5"],
                         "full_attention@4096": ["fusion.4"]},
           "meta": meta}
    read = bench_run.load_reader
    assert read("window_attention_ms_per_trip")(spans, trace, run) \
        == pytest.approx(1.5)
    assert read("full_attention_ms_per_trip")(spans, trace, run) \
        == pytest.approx(2.0)
    assert read("window_kv_bytes_per_slot")(spans, trace, run) \
        == ring_bytes / 2 == 4 * 2 * 128 * 1024 * 4
    # 3 ms of bucket 512's fusion.3; 5 + 1 ms of bucket 4096's fusion.4 and
    # fusion.5; bucket 4096's own fusion.3 is no attention
    assert read("prefill_attention_ms_per_prefill")(spans, trace, run) \
        == pytest.approx((3.0 + 6.0) / 2)
    # the kernel: 2 dispatches x 2 trips over streams of 3,001 and 41 rows,
    # a token longer at the second trip; 10 ms of kernel events
    flops = bytes_ = 0.0
    for trip in (0, 1):
        f, b = costs_window.mixed_attention_cost(
            [3001 + trip, 41 + trip], 1, 4, 128, 64, 8, 128)
        flops, bytes_ = flops + 2 * f, bytes_ + 2 * b
    least = max(flops / 197e12, bytes_ / 819e9)
    got = read("mixed_attention_roofline")(spans, trace, run)
    assert got == pytest.approx(100 * least / 0.010) and got < 100.0
    # a program without the scopes, the kernel or the meta (the parent, or
    # another configuration): nothing to read, no raise
    bare = dict(run, scope_ops={}, kernel_match={},
                meta={"n_layers": 2, "d_model": 64, "n_heads": 4})
    quiet = [dict(s, attrs={"phase": "step"}) if s["name"] == "decode/fetch"
             else s for s in spans]
    for name in NEW_READERS:
        assert read(name)(quiet, trace, bare) is None, name
    # ... and a stack of one kind of attention under this cell's match
    one_kind = dict(run, meta=dict(meta, layer_types=["attention"] * 5,
                                   sliding_window=0))
    assert read("mixed_attention_roofline")(spans, trace, one_kind) is None


def _tiny(seed, tolerances):
    """(ctx, meta) of the configuration at its tiny size, as the driver
    would see them."""
    from benchmark.reference import k_exaone_236b_a23b as reference
    cfg = bench_run.load_json(os.path.join(
        bench_run.ROOT, "benchmark", "configs", CONFIG + ".json"))
    rehearse.TINY[CONFIG](cfg)
    cfg["tolerances"] = tolerances
    return (_Ctx(seed=seed, reference=reference, config=cfg),
            dict(cfg["model"]))


def test_driver_holds_the_program_to_the_reference_layer_by_layer(tmp_path):
    """`serve_decode_arch.check_against_reference` as it is (this driver
    replaces `step_scope_ops` alone), fp32 on the CPU: both sides agree to
    rounding; and the names of the step's and of each prefill's
    instructions under the two kinds' scopes."""
    from benchmark.drivers import serve_decode_arch as drv
    from benchmark.drivers import serve_decode_window
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             save_decode_model)
    ctx, meta = _tiny(2 ** 31 + 9, {"logits": 1e-4, "top1_gap": 2e-4})
    state = drv.state_to_host(ctx, meta)
    # the driver's draw is the artifact's own dtypes: bf16 matmul weights
    assert state["l1_w_gate"].dtype.itemsize == 2
    assert state["l1_router"].dtype == np.float32
    assert state["l1_expert_bias"].dtype == np.float32
    assert 0.02 < float(np.std(state["l1_expert_bias"])) < 0.1
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
    ops = serve_decode_window.step_scope_ops(pred, 4, ctx.config)
    assert ops["window_attention"] and ops["full_attention"]
    assert set(ops["window_attention"]).isdisjoint(ops["full_attention"])
    for bucket in meta["prefill_buckets"]:
        assert ops["window_attention@%d" % bucket], bucket
        assert ops["full_attention@%d" % bucket], bucket


# --- the faults the issue names, planted in the PROGRAM -------------------

def _meta_edit(**keys):
    """The stack described with `keys` changed (whoever asks `block_of`)."""
    def plant(dec, mp):
        block_of = dec.block_of
        mp.setattr(dec, "block_of",
                   lambda meta: dict(block_of(meta), **keys))
    return plant


def _window_off_by(d):
    def plant(dec, mp):
        block_of = dec.block_of

        def f(meta):
            blk = block_of(meta)
            return dict(blk, sliding_window=blk["sliding_window"] + d)
        mp.setattr(dec, "block_of", f)
    return plant


def _window_layer_attends_the_whole_prefix(dec, mp):
    # in a prefill (a ring holds no more than the window)
    real = dec._blocked_attention
    mp.setattr(dec, "_blocked_attention",
               lambda q, k, v, scale, window=0: real(q, k, v, scale))


def _full_layer_held_to_the_window(dec, mp):
    # in a prefill: layer 0 is a window layer, so the window is known by
    # the time the full layer is traced
    real, seen = dec._blocked_attention, {}

    def f(q, k, v, scale, window=0):
        seen["window"] = window or seen["window"]
        return real(q, k, v, scale, window=seen["window"])
    mp.setattr(dec, "_blocked_attention", f)


def _row_lands_at_the_clamped_length(dec, mp):
    import jax.numpy as jnp
    P = dec.GenerativePredictor
    core, write = P._step_core, P._write
    seen = {}

    def step_core(self, state, tables, lengths, *a, **kw):
        seen["lengths"] = lengths
        return core(self, state, tables, lengths, *a, **kw)

    def wr(self, kc, vc, i, where, k_new, v_new, tp):
        W = self._block_meta["sliding_window"]
        if kc.shape[2] == W:                # a ring
            where = (where[0], jnp.where(
                where[1] < W, jnp.minimum(seen["lengths"], W - 1), W))
        return write(self, kc, vc, i, where, k_new, v_new, tp)
    mp.setattr(P, "_step_core", step_core)
    mp.setattr(P, "_write", wr)


def _prefill_lands_from_row_0(dec, mp):
    import jax.numpy as jnp

    def f(rows, true_len, window):
        # the prompt's last rows, oldest first, from ring row 0 on
        r = jnp.arange(window)
        have = r < true_len
        p = jnp.where(have, jnp.maximum(true_len - window, 0) + r, 0)
        return jnp.where(have[None, None, :, None, None],
                         jnp.take(rows, p, axis=2), 0.0)
    mp.setattr(dec, "_ring_rows", f)


def _attend(edit):
    """`_attend_table` over a window layer's rings with `edit(kc, vc,
    lengths, window) -> (kc, vc, lengths, window)` applied first."""
    def plant(dec, mp):
        P = dec.GenerativePredictor
        real = P._attend_table

        def f(self, q, kc, vc, lengths, ahead, i, tp, window=0):
            if window:
                kc, vc, lengths, window = edit(kc, vc, lengths, window)
            return real(self, q, kc, vc, lengths, ahead, i, tp,
                        window=window)
        mp.setattr(P, "_attend_table", f)
    return plant


def _neighbours_ring(kc, vc, lengths, window):
    import jax.numpy as jnp
    return jnp.roll(kc, 1, axis=1), jnp.roll(vc, 1, axis=1), lengths, window


def _no_clamp(kc, vc, lengths, window):
    # every row of the ring attended, whatever the slot's length: the rows a
    # short stream has not written (its last owner's, had they been kept)
    import jax.numpy as jnp
    return kc, vc, jnp.maximum(lengths, window), window


def _no_qk_norm(dec, mp):
    rms = dec._rms
    block_of = dec.block_of
    head = {}

    def remember(meta):
        blk = block_of(meta)
        head["dim"] = dec._head_dim(meta, blk)
        return blk

    def f(x, g, eps):
        return x if x.shape[-1] == head["dim"] else rms(x, g, eps)
    mp.setattr(dec, "block_of", remember)
    mp.setattr(dec, "_rms", f)


def _bias_in_the_weights(dec, mp):
    src = inspect.getsource(dec.moe_ffn)
    line = "w = jnp.take_along_axis(p, idx, axis=-1)"
    assert src.count(line) == 1
    scope = dict(vars(dec))
    exec(src.replace(line, "w = jnp.take_along_axis(p + expert_bias, idx, "
                           "axis=-1)"), scope)
    mp.setattr(dec, "moe_ffn", scope["moe_ffn"])


def _scaling_one(dec, mp):
    real = dec.moe_ffn
    mp.setattr(dec, "moe_ffn", lambda *a, **kw: real(*a, **dict(
        kw, scaling=1.0)))


def _no_shared_expert(dec, mp):
    P = dec.GenerativePredictor
    real, swiglu = P._block, dec._swiglu

    def block(self, state, i, *a, **kw):
        shared = state["l%d_shared_gate" % i] \
            if "l%d_shared_gate" % i in state else None

        def f(h, gate, up, down, *m):
            out = swiglu(h, gate, up, down, *m)
            return out * 0.0 if gate is shared else out
        dec._swiglu = f
        try:
            return real(self, state, i, *a, **kw)
        finally:
            dec._swiglu = swiglu
    mp.setattr(P, "_block", block)


def _activations_in_bfloat16(dec, mp):
    """Every matmul's result, every norm's and the residual stream kept as
    bfloat16 numbers (softmax and the norms' sums still float32)."""
    import jax.numpy as jnp
    P = dec.GenerativePredictor

    def low(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    contract, rms, block = dec._contract, dec._rms, P._block
    mp.setattr(dec, "_contract", lambda x, w, c: low(contract(x, w, c)))
    mp.setattr(dec, "_rms", lambda x, g, eps: low(rms(x, g, eps)))

    def f(self, *a, **kw):
        x, facts = block(self, *a, **kw)
        return low(x), facts
    mp.setattr(P, "_block", f)


PLANTED = {
    "window_127": _window_off_by(-1),
    "window_129": _window_off_by(+1),
    "window_layer_attends_the_whole_prefix":
        _window_layer_attends_the_whole_prefix,
    "full_layer_held_to_the_window": _full_layer_held_to_the_window,
    "row_lands_at_the_clamped_length": _row_lands_at_the_clamped_length,
    "prefill_lands_the_last_rows_from_row_0": _prefill_lands_from_row_0,
    "full_layer_rotated": _meta_edit(rope_layers="all"),
    "window_layers_unrotated": lambda dec, mp: mp.setattr(
        dec, "_rope", lambda x, positions, theta: x),
    "a_neighbours_ring": _attend(_neighbours_ring),
    "rows_past_the_length_attended": _attend(_no_clamp),
    "no_qk_norm": _no_qk_norm,
    "bias_in_the_weights": _bias_in_the_weights,
    "scaling_1": _scaling_one,
    "no_shared_expert": _no_shared_expert,
}
# refused by `precision_ratio`, which needs the chip's rounding: planted
# there (PERF.md section 6, PR 44), walked here
PLANTED_ON_THE_CHIP = {"activations_in_bfloat16": _activations_in_bfloat16}


@pytest.mark.parametrize("fault", sorted(PLANTED))
def test_a_planted_fault_is_refused_at_the_tiny_size(tmp_path, monkeypatch,
                                                     fault):
    """The comparison that decides `correct`, at a tiny size with the chip's
    own tolerances' ORDER (logits 0.08): each fault the issue names moves
    the logits by far more."""
    from benchmark.drivers import serve_decode_arch as drv
    from paddle_tpu.flags import FLAGS, set_flags
    from paddle_tpu.inference import decode as dec
    ctx, meta = _tiny(2 ** 31 + 21, {"logits": 0.08, "top1_gap": 0.16})
    art = dec.save_decode_model(str(tmp_path / "lm"),
                                drv.state_to_host(ctx, meta), meta)
    PLANTED[fault](dec, monkeypatch)
    # the executable store keys a phase by the artifact and the meta, not by
    # the code: with it on, a plant would load whatever phase of these
    # weights an earlier test left there
    was = FLAGS.compile_cache
    set_flags({"compile_cache": False})
    try:
        pred = dec.GenerativePredictor(art)
        assert not drv.check_against_reference(ctx, pred, meta)
    finally:
        set_flags({"compile_cache": was})
    assert ctx.logged[-1]["over_the_bounds"] > 0


def test_the_bfloat16_plant_runs_and_moves_every_position(tmp_path,
                                                          monkeypatch):
    from benchmark.drivers import serve_decode_arch as drv
    from paddle_tpu.flags import FLAGS, set_flags
    from paddle_tpu.inference import decode as dec
    ctx, meta = _tiny(2 ** 31 + 21, {"logits": 1e-4, "top1_gap": 2e-4,
                                     "precision_ratio": 0.5})
    art = dec.save_decode_model(str(tmp_path / "lm"),
                                drv.state_to_host(ctx, meta), meta)
    _activations_in_bfloat16(dec, monkeypatch)
    was = FLAGS.compile_cache
    set_flags({"compile_cache": False})
    try:
        assert not drv.check_against_reference(
            ctx, dec.GenerativePredictor(art), meta)
    finally:
        set_flags({"compile_cache": was})
    assert ctx.logged[-1]["precision_ratio"] > 0.5


@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
def test_kexaone_cell_rehearsal(manifest, trace, monkeypatch):
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
        # the executables' instruction names
        optional = {"mixed_attention_roofline",
                    "window_attention_ms_per_trip",
                    "full_attention_ms_per_trip",
                    "prefill_attention_ms_per_prefill",
                    "moe_ffn_ms_per_round", "held_experts_ffn_roofline",
                    "decode_kv_stream_share"}
        assert names - optional <= set(last["metrics"]) <= names
        assert last["metrics"]["window_kv_bytes_per_slot"]["value"] \
            == 2 * 4 * 8 * 16 * 4
        fetch = [json.loads(ln) for ln in lines if '"served_check"' in ln]
        assert fetch and fetch[0]["ok"]
    else:
        assert set(last["metrics"]) == names
