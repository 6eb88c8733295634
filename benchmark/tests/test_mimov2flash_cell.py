"""Tests of what the `mimo_v2_flash` configuration and its cell add to the
benchmark, on the CPU: the configuration file against the catalog's numbers
and the cut's arithmetic, the cell against the issue's traffic, the cost
arithmetic of a kernel over tables of two geometries, the new readers on
made-up spans, the driver's layer-at-a-time comparison with the plain
reference (and that it refuses each planted fault the issue names), and the
cell's whole rehearsal (slow).

`rehearse.TINY` / `rehearse.TINY_TRAFFIC`: as
benchmark/tests/test_olmoe_cell.py says, both entries are made HERE, at
import.

`PLANTED` is also what the chip's calibration plants at the published widths
(PERF.md section 6, PR 51): each entry edits the PROGRAM (`paddle_tpu.
inference.decode`) through a monkeypatch and is undone by it.
"""

import json
import os
import time
import types

import numpy as np
import pytest

from benchmark import costs_kinds, costs_window, xplane
from benchmark import run as bench_run
from benchmark.tests import rehearse
from benchmark.tests.test_kexaone_cell import (_activations_in_bfloat16,
                                               _bias_in_the_weights,
                                               _meta_edit, _Rec,
                                               _window_off_by)
from benchmark.tests.test_olmoe_cell import _Ctx

CELL = MIX = "mimov2flash_reasoning_decode"
CONFIG = "mimo_v2_flash"
TINY_KINDS = ["attention", "window_attention", "window_attention",
              "window_attention", "window_attention", "attention",
              "window_attention"]

rehearse.TINY.setdefault(CONFIG, lambda c: (
    c["model"].update(vocab_size=97, d_model=48, n_heads=4, n_kv_heads=1,
                      window_kv_heads=2, head_dim=12, v_head_dim=8,
                      rotary_dim=4, n_layers=7, layer_types=list(TINY_KINDS),
                      sliding_window=8, max_seq_len=128,
                      prefill_buckets=[16, 32, 64], dense_width=96,
                      n_experts=16, experts_per_token=4, expert_width=32,
                      experts_held=[4, 4]),
    c["deployment"].update(decode_slots=4, max_new_tokens_cap=24),
    c.update(reference_check={"prompt_tokens": [5, 20, 40], "steps": 4,
                              "pad": 128})))
rehearse.TINY_TRAFFIC.setdefault(MIX, lambda m: (
    m.update(requests=32),
    m["prompt_tokens"].update(median=14, min=4, max=60),
    m["output_tokens"].update(min=12, max=24)))

# The catalog's entry (model-configs guide, architectures.jsonl,
# MiMo-V2-Flash, `config`), number for number.
CATALOG = {
    "attention_value_scale": 0.707, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384,
    "max_position_embeddings": 262144, "model_type": "mimo_v2_flash",
    "num_attention_heads": 64, "head_dim": 192, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "layernorm_epsilon": 1e-05,
    "rope_theta": 5000000, "tie_word_embeddings": False,
    "vocab_size": 152576, "partial_rotary_factor": 0.334,
    "sliding_window": 128, "swa_rope_theta": 10000,
    "attention_bias": False, "v_head_dim": 128,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0],
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "sliding_window_size": 128,
    "attention_chunk_size": 128, "moe_layer_freq": [0] + [1] * 47,
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": None, "num_experts_per_tok": 8,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc",
    "routed_scaling_factor": None, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 8, "swa_head_dim": 192,
    "swa_v_head_dim": 128}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
NEW_READERS = ("kinds_attention_roofline", "attention_share_of_trip",
               "full_kv_bytes_per_slot")


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
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(guide):       # where the guide is at hand: its row
        with open(guide) as f:
            row = [r for r in map(json.loads, filter(str.strip, f))
                   if r["name"] == "MiMo-V2-Flash"][0]
        assert row["config"] == CATALOG
        assert row["source_url"] == config["source"]
    assert len(CATALOG["hybrid_layer_pattern"]) == 48
    for key, value in CATALOG.items():
        if key in REDUCED:
            assert config[key] < value and config["published"][key] == value
            assert key in config["reduced_detail"]
        else:
            assert config[key] == value, key
    m = config["model"]       # what the program is given says the same
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"],
            m["window_kv_heads"], m["head_dim"], m["v_head_dim"],
            m["n_layers"], m["vocab_size"], m["dense_width"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["swa_num_key_value_heads"],
        config["head_dim"], config["v_head_dim"],
        config["num_hidden_layers"], config["vocab_size"],
        config["intermediate_size"]) == (4096, 64, 4, 8, 192, 128, 7, 19072,
                                         16384)
    assert (config["swa_head_dim"], config["swa_v_head_dim"],
            config["swa_num_attention_heads"]) == (192, 128, 64)
    # the published pattern's first seven layers, kind for kind
    names = {0: "attention", 1: "window_attention"}
    assert m["layer_types"] == [names[k] for k in
                                config["hybrid_layer_pattern"][:7]]
    assert m["layer_types"] == TINY_KINDS
    assert config["moe_layer_freq"][:7] == [0] + [1] * 6
    assert m["n_dense_layers"] == 1
    assert (m["sliding_window"], m["rotary_dim"], m["rope_theta"],
            m["window_rope_theta"], m["value_scale"], m["window_sink"],
            m["norm_eps"]) == (
        config["sliding_window"],
        int(config["head_dim"] * config["partial_rotary_factor"]),
        config["rope_theta"], config["swa_rope_theta"],
        config["attention_value_scale"],
        config["add_swa_attention_sink_bias"],
        config["layernorm_epsilon"]) == (128, 64, 5e6, 1e4, 0.707, True,
                                         1e-5)
    assert config["add_full_attention_sink_bias"] is False
    # the router keeps its published width and its experts per token; the
    # experts HELD are the chip's share, the floor of the guide
    assert (m["n_experts"], m["experts_per_token"], m["expert_width"],
            m["n_shared_experts"], m["routed_scaling"],
            m["norm_topk_prob"]) == (
        256, config["num_experts_per_tok"], config["moe_intermediate_size"],
        0, 1.0, config["norm_topk_prob"])
    assert m["experts_held"][1] == config["n_routed_experts"] == 8
    assert m["vocab_size"] * 8 == 152576
    assert (m["router"], m["weight_dtype"], m["ffn"], m["norm"], m["head"],
            m["position"], m["rope_layers"]) == (
        "sigmoid_bias", "bfloat16", "moe_swiglu", "rmsnorm", "untied",
        "rope", "all")
    assert "qk_norm" not in m
    assert set(config["assumed"]) >= {
        "norm_placement", "qk_norm", "rotated_lanes", "window_edge", "sink",
        "value_scale", "selection_bias", "mtp", "dtype", "weights",
        "sampling", "eos_id", "max_seq_len", "prefill_buckets",
        "decode_slots", "max_new_tokens_cap", "experts_held"}
    assert "32 chips" in config["deployment"]["stands_for"]
    assert "2 of the 7 layers are full" in config["reduced_detail"][
        "num_hidden_layers"]
    assert config["driver"] == "serve_decode_kinds"
    assert (m["max_seq_len"], m["prefill_buckets"], m["eos_id"]) == (
        4096, [512, 1024], 0)
    assert config["deployment"]["max_new_tokens_cap"] == 2560


@pytest.mark.parametrize("name,shape", [
    ("l1_w_gate", (3, 700, 1000)),          # three draws and a part of one
    ("l1_wk", (48, 24)), ("l1_router", (48, 16)), ("l1_sink", (64,))])
def test_a_weight_is_a_function_of_seed_and_name_at_rest(name, shape):
    """ONE compiled draw of `DRAW` elements serves every shape: a tensor is
    its chunks in order, cut to size; the float32 form the reference
    computes on is THE NUMBER the artifact holds at rest (the rounding is
    the compiled program's result, the widening a step of its own: in one
    program the chip's compiler may skip the rounding), and another seed or
    name is another tensor."""
    import jax.numpy as jnp
    from benchmark.reference import mimo_v2_flash as reference
    rest = reference.at_rest(name, shape)
    w = reference.draw_tensor(name, shape, 2 ** 31 + 5)
    assert w.dtype == rest and w.shape == shape
    wide = np.asarray(reference.draw_tensor(name, shape, 2 ** 31 + 5,
                                            "float32"))
    np.testing.assert_array_equal(np.asarray(w.astype(jnp.float32)), wide)
    n = int(np.prod(shape))
    chunks = [reference._normal(np.uint32(2 ** 31 + 5), np.uint32(
        reference.zlib.crc32(name.encode())), np.uint32(j))
        for j in range(-(-n // reference.DRAW))]
    assert reference._shaped(chunks, shape, 0.5, jnp.dtype(rest)).dtype \
        == rest
    flat = np.concatenate([np.asarray(c) for c in chunks])[:n]
    std = 1.0 if len(shape) == 1 else 1.0 / np.sqrt(shape[-2])
    np.testing.assert_array_equal(
        np.asarray((jnp.asarray(flat.reshape(shape)) * std).astype(
            rest).astype(jnp.float32)), wide)
    assert abs(float(flat.std()) - 1.0) < 0.05 and abs(flat.mean()) < 0.2
    for other in (reference.draw_tensor(name, shape, 2 ** 31 + 6),
                  reference.draw_tensor("l2" + name[2:], shape, 2 ** 31 + 5)):
        assert abs(np.corrcoef(np.asarray(other, np.float32).ravel(),
                               wide.ravel())[0, 1]) < 0.3


def test_the_cut_is_the_arithmetic_the_file_states(config):
    """2.222 B parameters, 4.46 GB at rest: the reference's shapes add up to
    what `reduced_detail` says, and a slot's two kinds of K/V state, each
    with a K and a V row of its own width, to the deployment's."""
    from benchmark.reference import mimo_v2_flash as reference
    from paddle_tpu.inference import decode as dec
    from paddle_tpu.inference import slot_state
    m = config["model"]
    shapes = reference.tensor_shapes(m)
    assert shapes == dec.decode_state_shapes(m)
    params = sum(int(np.prod(s)) for s in shapes.values())
    rest = sum(int(np.prod(s)) * reference.at_rest(n, s).dtype.itemsize
               for n, s in shapes.items())
    assert (params, rest) == (2221995840, 4456701184)
    for n, s in shapes.items():
        assert dec._bf16_at_rest(n, np.zeros((1,) * len(s))) \
            == (reference.at_rest(n, s).dtype.itemsize == 2), n

    def attention(i):
        return sum(int(np.prod(shapes["l%d_%s" % (i, n)]))
                   for n in ("wq", "wk", "wv", "wo"))
    assert round(attention(0) / 1e6, 2) == round(attention(5) / 1e6, 2) \
        == 89.13
    assert round(attention(1) / 1e6, 2) == 94.37
    assert shapes["l1_sink"] == (64,) and "l0_sink" not in shapes \
        and "l5_sink" not in shapes
    assert int(np.prod(shapes["l1_w_gate"])) * 3 // 8 == 3 * 4096 * 2048
    d = config["deployment"]
    n = d["decode_slots"]
    held = slot_state.kind_shapes(m, dec.block_of(m), n, None)
    assert held == {"kv": ((2, n, 4096, 4 * 192), (2, n, 4096, 4 * 128)),
                    "ring": ((5, n, 128, 8 * 192), (5, n, 128, 8 * 128))}
    kinds, totals = slot_state.state_bytes(m, dec.block_of(m), n, None)
    assert kinds == {"kv": d["kv_table_bytes"],
                     "ring": d["window_kv_table_bytes"]}
    assert totals["kv_cache_bytes"] == sum(kinds.values())
    per_slot = totals["kv_cache_bytes"] / n
    assert round(per_slot / 1e6, 1) == 48.5
    assert round(kinds["kv"] / n / 1e6, 1) == 41.9
    assert round(kinds["ring"] / n / 1e6, 2) == 6.55
    # what a uniform table at the window layers' geometry would reserve
    assert round(7 * 4096 * 2560 * 4 / 1e6, 1) == 293.6
    # at rest: over a quarter of the chip's 16.91 GB
    assert 0.5 < (rest + totals["kv_cache_bytes"]) / 16.91e9 < 0.6


def test_the_cell_is_the_issues(manifest):
    cell, config, mix, e2e, per_layer = bench_run.resolve_cell(manifest,
                                                               CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert (mix["loop"], mix["clients_per_slot"], mix["requests"]) == (
        "closed", 2, 512)
    assert mix["prompt_tokens"] == {"kind": "lognormal", "median": 384,
                                    "sigma": 0.8, "min": 64, "max": 1024}
    assert mix["output_tokens"] == {"kind": "uniform", "min": 1024,
                                    "max": 2560}
    assert {m["name"] for m in e2e} == {"tokens_per_s", "setup_s"}
    names = {m["name"] for m in per_layer}
    assert set(NEW_READERS) <= names
    assert names >= {"decode_round_ms.saturated", "moe_ffn_ms_per_round",
                     "held_experts_ffn_roofline", "slots_busy_share",
                     "prefill_share_of_lane", "decode_kv_stream_share",
                     "window_attention_ms_per_trip",
                     "full_attention_ms_per_trip",
                     "prefill_attention_ms_per_prefill",
                     "window_kv_bytes_per_slot",
                     "decode_trips_per_dispatch",
                     "decode_early_launch_share", "finish_ms_per_ender",
                     "slot_free_ms_per_ender"}
    # their readers charge 4 bytes a weight, or know one geometry of table
    assert not names & {"moe_ffn_roofline", "decode_attention_roofline",
                        "gqa_attention_roofline",
                        "hybrid_attention_roofline",
                        "mla_attention_roofline",
                        "mixed_attention_roofline"}
    for m in per_layer:
        assert os.path.exists(os.path.join(bench_run.LAYERS_DIR,
                                           m["name"] + ".py")), m["name"]
        assert m["moves"] == "tokens_per_s"
    for m in manifest["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
    # every metric the six older decode cells share is reported here too
    six = {"gpt2s_decode_saturated", "gpt2s_decode_deep",
           "olmoe_decode_saturated", "lfm2_decode_saturated",
           "pangu_decode_saturated", "falconh1_decode_saturated"}
    for m in manifest["per_layer"]:
        if six <= set(m.get("workloads", ())):
            assert CELL in m["workloads"], m["name"]
    # both buckets, and answers far longer than prompts
    from benchmark import loadgen
    lens = loadgen.quantile_values(mix["prompt_tokens"], mix["requests"])
    buckets = config["model"]["prefill_buckets"]
    assert {min(b for b in buckets if n <= b) for n in lens} == set(buckets)
    assert (min(lens), max(lens)) == (64, 1024)
    assert abs(sum(n > 384 for n in lens) / 512.0 - 0.5) < 0.01
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= config["model"]["max_seq_len"]
    assert mix["output_tokens"]["max"] \
        == config["deployment"]["max_new_tokens_cap"]
    # the check's prompts: under the window, across it while decoding,
    # several wraps, both buckets, none a multiple of 128
    chk = config["reference_check"]
    assert chk["steps"] >= 32 and not [n for n in chk["prompt_tokens"]
                                       if n % 128 == 0]
    assert min(chk["prompt_tokens"]) + chk["steps"] < 128 \
        < max(chk["prompt_tokens"]) // 4
    assert any(n < 128 < n + chk["steps"] for n in chk["prompt_tokens"])
    assert {min(b for b in buckets if n <= b)
            for n in chk["prompt_tokens"]} == set(buckets)
    # the replay after the window can read over half of the served streams
    assert chk["pad"] % 128 == 0 and chk["pad"] >= 384 + 1792


@pytest.mark.parametrize("seconds", [45.0, 5.0])
def test_the_driver_takes_its_functions_out_again(config, monkeypatch,
                                                  seconds):
    """`serve_decode_kinds.run` hands everything to `serve_decode_arch.run`
    with the step's and the prefills' scopes named, the configuration's one
    padded length (asked for the first time, it waits for the reference's
    compiles, which were started before anything else) and the server's
    ceiling raised, and puts all three back."""
    from benchmark.drivers import (serve_decode_arch as arch,
                                   serve_decode_kinds, serve_decode_ssm)
    from paddle_tpu.flags import FLAGS
    seen, ahead = [], []
    theirs = arch.step_scope_ops, arch.check_pad
    cap = FLAGS.serving_max_new_tokens
    pred = types.SimpleNamespace(max_seq_len=4096)
    monkeypatch.setattr(
        serve_decode_kinds, "compile_reference_ahead",
        lambda ctx, meta: ahead.append(("started", meta["n_layers"]))
        or (lambda: ahead.append("waited")))
    monkeypatch.setattr(arch, "run", lambda ctx: seen.append(
        (ctx.trace_seconds, arch.step_scope_ops, list(ahead),
         arch.check_pad(ctx, pred), arch.check_pad(ctx, pred), list(ahead),
         FLAGS.serving_max_new_tokens)))
    ctx = types.SimpleNamespace(config=config, seconds=seconds,
                                trace_seconds=min(3.0, seconds / 2.0))
    serve_decode_kinds.run(ctx)
    want = min(float(config.get("trace_seconds", 3.0)), seconds / 2.0)
    assert seen == [(want, serve_decode_ssm.step_scope_ops, [("started", 7)],
                     2304, 2304, [("started", 7), "waited"], 2560)]
    assert ahead == [("started", 7), "waited"]
    assert (arch.step_scope_ops, arch.check_pad) == theirs
    assert FLAGS.serving_max_new_tokens == cap
    assert serve_decode_kinds.check_pad(ctx, pred) == 2304
    assert arch.check_pad(ctx, pred) == 1152
    assert set(config["prefill_trace_scopes"]) == {"window_attention",
                                                   "full_attention"}
    assert set(config["trace_scopes"]) >= {"window_attention",
                                           "full_attention", "moe_ffn"}


def test_a_program_without_the_keys_is_refused_at_once(config, monkeypatch):
    """The parent: its `BLOCK_DEFAULTS` lacks the keys, and `block_of` would
    pass over them."""
    from benchmark.drivers import serve_decode_arch as arch
    from benchmark.drivers import serve_decode_kinds
    from paddle_tpu.inference import decode as dec
    monkeypatch.setattr(dec, "BLOCK_DEFAULTS", tuple(
        kv for kv in dec.BLOCK_DEFAULTS
        if kv[0] not in ("window_kv_heads", "rotary_dim", "window_sink")))
    monkeypatch.setattr(arch, "run", lambda ctx: pytest.fail("it ran"))
    monkeypatch.setattr(serve_decode_kinds, "compile_reference_ahead",
                        lambda ctx, meta: pytest.fail("it compiled"))
    with pytest.raises(SystemExit) as e:
        serve_decode_kinds.run(types.SimpleNamespace(
            config=config, seconds=45.0, trace_seconds=3.0))
    assert "rotary_dim, window_kv_heads, window_sink" in str(e.value)


def test_kinds_attention_cost_by_hand():
    """One trip over streams of 40, 128, 700 and 3,000 rows: each of two full
    layers reads them all at 4 x (192 + 128) lanes a row, each of five
    window layers min(rows, 128) at 8 x (192 + 128), with 64 sinks."""
    lengths = [40, 128, 700, 3000]
    full, ring = (2, 4, 192, 128), (5, 8, 192, 128)
    flops, bytes_ = costs_kinds.kinds_attention_cost(
        lengths, 64, 128, full, ring, sink=True)
    rows, ringed = sum(lengths), 40 + 128 + 128 + 128
    q_io = 4 * 64 * (192 + 128) * 4
    assert bytes_ == 2 * (rows * 4 * 320 * 4.0 + q_io) \
        + 5 * (ringed * 8 * 320 * 4.0 + q_io + 4 * 64)
    assert flops == 2.0 * 64 * 320 * (2 * rows + 5 * ringed)
    # with one geometry and K as wide as V it is costs_window's count
    same = (1, 8, 128, 128)
    assert costs_kinds.kinds_attention_cost(
        lengths, 64, 128, same, (4, 8, 128, 128)) \
        == costs_window.mixed_attention_cost(lengths, 1, 4, 128, 64, 8, 128)
    # memory binds, and a window layer's call costs what 128 rows cost
    # however long the stream
    assert bytes_ / 819e9 > flops / 197e12
    assert costs_kinds.kind_attention_cost([128], 1, 64, 8, 192, 128) \
        == costs_kinds.kinds_attention_cost([3000], 64, 128,
                                            (0, 4, 192, 128),
                                            (1, 8, 192, 128))
    # a uniform geometry would read 2.0 x the full layers' bytes
    assert costs_kinds.kind_attention_cost([1000], 1, 64, 8, 192, 192)[1] \
        > 2.0 * costs_kinds.kind_attention_cost([1000], 1, 64, 4, 192,
                                                128)[1]


def test_the_new_readers_find_and_time_their_operations():
    """Synthetic spans and a synthetic device plane: two dispatches of two
    trips over two live streams (one under the window, one far past it)."""
    kernel = ("%%custom-call.%d = f32[2,64,1024] custom-call(...), "
              "custom_call_target=\"tpu_custom_call\", frontend_attributes={"
              "kernel_metadata={}}")
    ops = []
    for r in (0.0, 0.010):
        ops += [("%fusion.7 = f32[2,64,1536] fusion(...)", r, r + 0.001),
                (kernel % 3, r + 0.001, r + 0.003),          # window layers
                ("%fusion.8 = f32[2,64,768] fusion(...)", r + 0.003,
                 r + 0.004),
                (kernel % 4, r + 0.004, r + 0.007),          # full layers
                ("%fusion.9 = f32[2,4096] fusion(...)", r + 0.007,
                 r + 0.009)]                    # 1 ms of the round idle
    trace = xplane.Trace({0: ops})
    trace.anchor = (0.0, 0.0, 100.0)           # monotonic 100 s = trace 0 s
    steps = [{"name": "serving/decode_step", "t0": 100.0 + r,
              "t1": 100.0 + r + 0.010, "attrs": {"tokens": 4, "trips": 2}}
             for r in (0.0, 0.010)]
    full_bytes = 2 * 2 * 4096 * (768 + 512) * 4
    spans = steps + [
        {"name": "decode/fetch", "t0": s["t0"] + 0.001, "t1": s["t1"],
         "attrs": {"phase": "step", "full_kv_bytes": full_bytes,
                   "window_kv_bytes": 5 * 2 * 128 * 2560 * 4,
                   "full_k_lanes": 768, "full_v_lanes": 512,
                   "window_k_lanes": 1536, "window_v_lanes": 1024,
                   "window_layers": 5, "full_layers": 2}} for s in steps]
    meta = {"n_layers": 7, "d_model": 4096, "n_heads": 64, "n_kv_heads": 4,
            "window_kv_heads": 8, "head_dim": 192, "v_head_dim": 128,
            "window_sink": True, "sliding_window": 128,
            "layer_types": list(TINY_KINDS), "prefill_buckets": [512, 1024]}
    recs = [_Rec(3000, [99.0], max_new=2560), _Rec(40, [99.5], max_new=2560)]
    run = {"trace_window_monotonic": (100.0, 100.021),
           "trace_window": (0.0, 0.021), "window": (100.0, 100.021),
           "slots": 2, "records": recs, "device_kind": "TPU v5 lite",
           "kernel_match": {"kinds_attention": "kernel_metadata={}"},
           "scope_ops": {"window_attention": ["fusion.7", "custom-call.3"],
                         "full_attention": ["fusion.8", "custom-call.4"]},
           "meta": meta}
    read = bench_run.load_reader
    assert read("full_kv_bytes_per_slot")(spans, trace, run) \
        == full_bytes / 2 == 2 * 4096 * 1280 * 4
    # 3 + 4 ms of a round's 9 busy ms are under the two scopes
    assert read("attention_share_of_trip")(spans, trace, run) \
        == pytest.approx(100.0 * 7 / 9)
    # the kernel: 2 dispatches x 2 trips over streams of 3,001 and 41 rows,
    # a token longer at the second trip; 10 ms of kernel events
    flops = bytes_ = 0.0
    for trip in (0, 1):
        f, b = costs_kinds.kinds_attention_cost(
            [3001 + trip, 41 + trip], 64, 128, (2, 4, 192, 128),
            (5, 8, 192, 128), sink=True)
        flops, bytes_ = flops + 2 * f, bytes_ + 2 * b
    least = max(flops / 197e12, bytes_ / 819e9)
    got = read("kinds_attention_roofline")(spans, trace, run)
    assert got == pytest.approx(100 * least / 0.010) and got < 100.0
    # `mixed_attention_roofline` would have counted one geometry
    one = dict(run, kernel_match={"mixed_attention": "kernel_metadata={}"})
    assert read("mixed_attention_roofline")(spans, trace, one) != got
    # a program without the scopes, the kernel or the meta (the parent, or
    # another configuration): nothing to read, no raise
    bare = dict(run, scope_ops={}, kernel_match={},
                meta={"n_layers": 2, "d_model": 64, "n_heads": 4})
    quiet = [dict(s, attrs={"phase": "step"}) if s["name"] == "decode/fetch"
             else s for s in spans]
    for name in NEW_READERS:
        assert read(name)(quiet, trace, bare) is None, name
    # ... and a stack of two kinds with ONE geometry under this cell's match
    kex = dict(run, meta={k: v for k, v in meta.items() if k not in (
        "window_kv_heads", "v_head_dim", "window_sink")})
    assert read("kinds_attention_roofline")(spans, trace, kex) is None


def _tiny(seed, tolerances):
    """(ctx, meta) of the configuration at its tiny size, as the driver
    would see them."""
    from benchmark.reference import mimo_v2_flash as reference
    cfg = bench_run.load_json(os.path.join(
        bench_run.ROOT, "benchmark", "configs", CONFIG + ".json"))
    rehearse.TINY[CONFIG](cfg)
    cfg["tolerances"] = tolerances
    return (_Ctx(seed=seed, reference=reference, config=cfg),
            dict(cfg["model"]))


def test_driver_holds_the_program_to_the_reference_layer_by_layer(tmp_path):
    """`serve_decode_arch.check_against_reference` as it is, fp32 on the
    CPU: both sides agree to rounding through both kinds of table, each
    with its own rows; and the names of the step's and of each prefill's
    instructions under the two kinds' scopes."""
    from benchmark.drivers import serve_decode_arch as drv
    from benchmark.drivers import serve_decode_kinds
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             save_decode_model)
    ctx, meta = _tiny(2 ** 31 + 9, {"logits": 1e-4, "top1_gap": 2e-4})
    state = drv.state_to_host(ctx, meta)
    # the driver's draw is the artifact's own dtypes: bf16 matmul weights
    assert state["l1_w_gate"].dtype.itemsize == 2
    assert state["l1_router"].dtype == state["l1_sink"].dtype == np.float32
    assert 0.3 < float(np.std(np.concatenate(
        [state["l%d_sink" % i] for i in (1, 2, 3, 4, 6)]))) < 2.0
    assert (state["l0_wk"].shape, state["l0_wv"].shape) == ((48, 12),
                                                            (48, 8))
    assert (state["l1_wk"].shape, state["l1_wv"].shape) == ((48, 24),
                                                            (48, 16))
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
    ops = serve_decode_kinds.step_scope_ops(pred, 4, ctx.config)
    assert ops["window_attention"] and ops["full_attention"]
    assert set(ops["window_attention"]).isdisjoint(ops["full_attention"])
    for bucket in meta["prefill_buckets"]:
        assert ops["window_attention@%d" % bucket], bucket
        assert ops["full_attention@%d" % bucket], bucket


def test_the_reference_compiled_ahead_is_what_the_check_calls():
    """`serve_decode_kinds.compile_reference_ahead` lowers and compiles
    `serve_decode_arch.reference_rows`'s two functions for the very shapes
    that function hands them, in both precisions: once it has been waited
    for, the comparison's reference compiles NOTHING (a first pass has paid
    the draws' and the eager operations' compiles), where a context without
    it compiles a program a kind of layer and the head, a precision."""
    from benchmark.drivers import serve_decode_arch as drv
    from benchmark.drivers import serve_decode_kinds
    watch = bench_run.CompileWatch()

    def rows(ctx, meta):
        chk = ctx.config["reference_check"]
        steps = int(chk["steps"])
        seqs = [list(range(1, n + steps + 1)) for n in chk["prompt_tokens"]]
        at = [slice(n - 1, n + steps) for n in chk["prompt_tokens"]]
        t0 = time.monotonic()
        got = [drv.reference_rows(ctx, meta, seqs, at, chk["pad"], dtype)[0]
               for dtype in ("float32", "bfloat16")]
        return got, [n for n, _ in watch.between(t0, time.monotonic())
                     if n.endswith("backend_compile_duration")]
    first, compiled = rows(*_tiny(2 ** 31 + 11, {}))
    kinds = len({(op, i < 1) for i, op in enumerate(TINY_KINDS)})
    assert len(compiled) >= 2 * (kinds + 1)
    ctx, meta = _tiny(2 ** 31 + 11, {})
    wait = serve_decode_kinds.compile_reference_ahead(ctx, meta)
    wait()
    facts = ctx.logged[-1]
    assert facts["phase"] == "reference_compiled_ahead"
    assert len(facts["each"]) == 2 * (kinds + 1)
    assert all(isinstance(s, float) for s in facts["each"]), facts
    again, compiled = rows(ctx, meta)
    assert compiled == []
    for a, b in zip(first, again):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    # the control: the same context without what was compiled ahead
    del ctx._arch_reference_fns
    assert len(rows(ctx, meta)[1]) == 2 * (kinds + 1)


# --- the faults the issue names, planted in the PROGRAM -------------------

def _a_sink_on_the_full_layers(dec, mp):
    """The first window layer's sinks, given to the full layers too: in the
    step (the kernel) and in a prefill (the blocked scores)."""
    P = dec.GenerativePredictor
    block, attend, blocked = P._block, P._attend_table, dec._blocked_attention
    now = {"sink": None}

    def layer(self, state, i, *a, **kw):
        ops = [op for op, _ in self.layer_kinds]
        now["sink"] = state["l%d_sink" % ops.index("window_attention")] \
            if ops[i] == "attention" else None
        try:
            return block(self, state, i, *a, **kw)
        finally:
            now["sink"] = None

    def table(self, q, kc, vc, lengths, ahead, i, tp, window=0, sinks=None):
        return attend(self, q, kc, vc, lengths, ahead, i, tp, window=window,
                      sinks=now["sink"] if sinks is None else sinks)

    def scores(q, k, v, scale, window=0, sink=None):
        return blocked(q, k, v, scale, window=window,
                       sink=now["sink"] if sink is None else sink)
    mp.setattr(P, "_block", layer)
    mp.setattr(P, "_attend_table", table)
    mp.setattr(dec, "_blocked_attention", scores)


def _ring(edit):
    """`_attend_table` over a window layer's rings with `edit(self, q, kc,
    vc) -> (kc, vc, lanes of the result to keep)` applied first."""
    def plant(dec, mp):
        P = dec.GenerativePredictor
        real = P._attend_table

        def f(self, q, kc, vc, lengths, ahead, i, tp, window=0, **kw):
            keep = None
            if window:
                kc, vc, keep = edit(self, q, kc, vc)
            out = real(self, q, kc, vc, lengths, ahead, i, tp, window=window,
                       **kw)
            return out if keep is None else out[..., :keep]
        mp.setattr(P, "_attend_table", f)
    return plant


def _the_full_layers_heads(self, q, kc, vc):
    # the rings read as rows of the FULL layers' K/V heads: the first
    # n_kv_heads heads' lanes, so a query head reads another head's rows
    heads = self._kv_heads("attention")
    dk, dv = q.shape[-1], self._v_head_dim
    return kc[..., :heads * dk], vc[..., :heads * dv], None


def _v_at_the_key_heads_size(self, q, kc, vc):
    # a V row read at 192 lanes a head: head h's values taken from lanes
    # 192 h .. 192 h + 127 of the row (zeros past its end)
    import jax.numpy as jnp
    dk, dv = q.shape[-1], self._v_head_dim
    heads = kc.shape[-1] // dk
    return kc, jnp.pad(vc, [(0, 0)] * (vc.ndim - 1)
                       + [(0, heads * (dk - dv))]), dv


def _thetas_swapped(dec, mp):
    block_of = dec.block_of

    def f(meta):
        blk = block_of(meta)
        return dict(blk, rope_theta=blk["window_rope_theta"],
                    window_rope_theta=blk["rope_theta"])
    mp.setattr(dec, "block_of", f)


PLANTED = {
    "the_sink_left_out": _meta_edit(window_sink=False),
    "a_sink_on_the_full_layers": _a_sink_on_the_full_layers,
    "value_scale_1": _meta_edit(value_scale=1.0),
    "all_lanes_rotated": _meta_edit(rotary_dim=0),
    "the_two_thetas_swapped": _thetas_swapped,
    "window_layers_read_with_the_full_layers_heads":
        _ring(_the_full_layers_heads),
    "v_read_at_the_key_heads_size": _ring(_v_at_the_key_heads_size),
    "window_127": _window_off_by(-1),
    "window_129": _window_off_by(+1),
    "bias_in_the_weights": _bias_in_the_weights,
}
# refused by `precision_ratio`, which needs the chip's rounding: planted
# there (PERF.md section 6, PR 51), walked here
PLANTED_ON_THE_CHIP = {"activations_in_bfloat16": _activations_in_bfloat16}


def refuses(fault, tmp_path, monkeypatch, tolerances):
    """Whether the driver's comparison refuses the program with `fault`
    planted, at the tiny size; (refused, the facts it logged)."""
    from benchmark.drivers import serve_decode_arch as drv
    from paddle_tpu.flags import FLAGS, set_flags
    from paddle_tpu.inference import decode as dec
    ctx, meta = _tiny(2 ** 31 + 21, tolerances)
    art = dec.save_decode_model(str(tmp_path / "lm"),
                                drv.state_to_host(ctx, meta), meta)
    dict(PLANTED, **PLANTED_ON_THE_CHIP)[fault](dec, monkeypatch)
    # the executable store keys a phase by the artifact and the meta, not by
    # the code: with it on, a plant would load whatever phase of these
    # weights an earlier test left there
    was = FLAGS.compile_cache
    set_flags({"compile_cache": False})
    try:
        ok = drv.check_against_reference(ctx, dec.GenerativePredictor(art),
                                         meta)
    finally:
        set_flags({"compile_cache": was})
    return not ok, ctx.logged[-1]


@pytest.mark.parametrize("fault", sorted(PLANTED))
def test_a_planted_fault_is_refused_at_the_tiny_size(tmp_path, monkeypatch,
                                                     fault):
    """The comparison that decides `correct`, at a tiny size with the chip's
    own tolerances' ORDER (logits 0.08): each fault the issue names moves
    the logits by far more."""
    refused, facts = refuses(fault, tmp_path, monkeypatch,
                             {"logits": 0.08, "top1_gap": 0.16})
    assert refused and facts["over_the_bounds"] > 0


def test_the_bfloat16_plant_runs_and_moves_every_position(tmp_path,
                                                          monkeypatch):
    refused, facts = refuses(
        "activations_in_bfloat16", tmp_path, monkeypatch,
        {"logits": 1e-4, "top1_gap": 2e-4, "precision_ratio": 0.5})
    assert refused and facts["precision_ratio"] > 0.5


@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
def test_mimov2flash_cell_rehearsal(manifest, trace, monkeypatch):
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
        optional = {"kinds_attention_roofline", "attention_share_of_trip",
                    "window_attention_ms_per_trip",
                    "full_attention_ms_per_trip",
                    "prefill_attention_ms_per_prefill",
                    "moe_ffn_ms_per_round", "held_experts_ffn_roofline",
                    "decode_kv_stream_share"}
        assert names - optional <= set(last["metrics"]) <= names
        # two full layers' K rows of 12 and V rows of 8 lanes, 128 positions;
        # five rings of 8 rows of 24 and 16 lanes
        assert last["metrics"]["full_kv_bytes_per_slot"]["value"] \
            == 2 * 128 * (12 + 8) * 4
        assert last["metrics"]["window_kv_bytes_per_slot"]["value"] \
            == 5 * 8 * (24 + 16) * 4
        served = [json.loads(ln) for ln in lines if '"served_check"' in ln]
        assert served and served[0]["ok"] and served[0]["streams"] > 0
    else:
        assert set(last["metrics"]) == names
