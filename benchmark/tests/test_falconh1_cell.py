"""Tests of what the `falcon_h1_34b` configuration and its cell add to the
benchmark, on the CPU: the configuration file against the catalog's numbers
and the cut's arithmetic, the reference's blocked vocabulary against the
table drawn whole, the state-space update's cost arithmetic, how the new
readers find their operations (in the step AND in a prefill), the driver's
comparison with the plain reference (and that it can fail, by each planted
fault), and the cell's whole rehearsal (slow).

`rehearse.TINY` / `rehearse.TINY_TRAFFIC`: as
benchmark/tests/test_olmoe_cell.py says, both entries are made HERE, at
import.
"""

import json
import os

import numpy as np
import pytest

from benchmark import costs_hybrid, costs_ssm, xplane
from benchmark import run as bench_run
from benchmark.tests import rehearse
from benchmark.tests.test_olmoe_cell import _Ctx

CELL, CONFIG, MIX = ("falconh1_decode_saturated", "falcon_h1_34b",
                     "falconh1_decode_saturated")

rehearse.TINY.setdefault(CONFIG, lambda c: (
    c["model"].update(vocab_size=97, d_model=48, n_heads=4, n_kv_heads=2,
                      head_dim=8, n_layers=2,
                      layer_types=["attention+ssm"] * 2, max_seq_len=128,
                      prefill_buckets=[16, 64], dense_width=96,
                      ssm_heads=4, ssm_head_dim=8, ssm_state=16,
                      ssm_groups=2, ssm_chunk=8),
    c["deployment"].update(decode_slots=4),
    c.update(reference_check={"prompt_tokens": [5, 20, 40], "steps": 4})))
rehearse.TINY_TRAFFIC.setdefault(MIX, lambda m: (
    m.update(requests=32),
    m["prompt_tokens"].update(min=8, max=30),
    m["output_tokens"].update(min=12, max=24)))

# The catalog's entry (model-configs guide, architectures.jsonl,
# Falcon-H1-34B-Instruct, `config`), number for number.
CATALOG = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120}
REDUCED = ["num_hidden_layers"]
NEW_READERS = ("ssm_update_ms_per_trip", "ssm_update_roofline",
               "ssm_proj_ms_per_trip", "ssm_scan_ms_per_prefill",
               "ssm_state_bytes_per_slot", "hybrid_attention_roofline")


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
        config["intermediate_size"]) == (5120, 20, 4, 128, 4, 261120, 21504)
    assert (m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"],
            m["ssm_groups"], m["ssm_conv_kernel"], m["ssm_chunk"]) == (
        config["mamba_n_heads"], config["mamba_d_head"],
        config["mamba_d_state"], config["mamba_n_groups"],
        config["mamba_d_conv"], config["mamba_chunk_size"])
    assert m["ssm_heads"] * m["ssm_head_dim"] == config["mamba_d_ssm"]
    # all ten multipliers, as published
    for key in ("embedding_multiplier", "lm_head_multiplier",
                "attention_in_multiplier", "key_multiplier",
                "attention_out_multiplier", "ssm_in_multiplier",
                "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers"):
        assert m[key] == config[key], key
    assert (m["norm"], m["norm_eps"], m["position"], m["rope_theta"],
            m["ffn"], m["head"], m["weight_dtype"]) == (
        "rmsnorm", config["rms_norm_eps"], "rope", config["rope_theta"],
        "swiglu", "untied", "bfloat16")
    assert m["layer_types"] == ["attention+ssm"] * 4
    assert set(config["assumed"]) >= {
        "dt_limits", "ssm_parameters", "gated_norm", "conv_state",
        "mup_vector", "rope_layout", "dtype", "weight_scales", "sampling",
        "eos_id", "max_seq_len", "prefill_buckets", "decode_slots"}
    assert "18" in config["deployment"]["stands_for"]
    assert config["deployment"]["decode_slots"] in (64, 96)
    assert config["driver"] == "serve_decode_ssm"


def test_the_cut_is_the_arithmetic_the_file_states(config):
    """4.39 B parameters, 8.79 GB at rest: the reference's shapes add up to
    what `reduced_detail` says, and a slot's state to the deployment's."""
    from benchmark.reference import falcon_h1_34b as reference
    from paddle_tpu.inference import decode as dec
    m = config["model"]
    shapes = reference.tensor_shapes(m)
    assert shapes == dec.decode_state_shapes(m)
    params = sum(int(np.prod(s)) for s in shapes.values())
    rest = sum(int(np.prod(s)) * reference.at_rest(n, s).dtype.itemsize
               for n, s in shapes.items())
    assert (params, rest) == (4394354048, 8789038592)
    layer = sum(int(np.prod(s)) for n, s in shapes.items()
                if n.startswith("l0_"))
    assert round(layer / 1e6, 2) == 430.12
    assert int(np.prod(shapes["l0_ssm_in"])) == 5120 * 9248
    # at rest by the program's own rule
    for n, s in shapes.items():
        assert dec._bf16_at_rest(n, np.zeros((1,) * len(s))) \
            == (reference.at_rest(n, s).dtype.itemsize == 2), n
    d = config["deployment"]
    kv, conv, ssm = dec.slot_state_shapes(m, d["decode_slots"], None)
    assert ssm == (4, d["decode_slots"], 32, 128, 256)
    assert conv == (4, d["decode_slots"], 3, 5120)
    assert kv == (4, d["decode_slots"], 1024, 4 * 128)
    assert 4 * int(np.prod(ssm)) == d["ssm_state_table_bytes"]
    assert 2 * 4 * int(np.prod(kv)) == d["kv_table_bytes"]
    assert 4 * int(np.prod(conv)) == d["conv_state_table_bytes"]
    per_slot = (d["ssm_state_table_bytes"] + d["kv_table_bytes"]
                + d["conv_state_table_bytes"]) / d["decode_slots"]
    assert round(per_slot / 1e6, 1) == 33.8


def test_the_cell_is_the_issues(manifest):
    cell, config, mix, e2e, per_layer = bench_run.resolve_cell(manifest,
                                                               CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert (mix["loop"], mix["clients_per_slot"], mix["requests"]) == (
        "closed", 2, 512)
    assert mix["prompt_tokens"] == {"kind": "uniform", "min": 64,
                                    "max": 512}
    assert mix["output_tokens"] == {"kind": "uniform", "min": 64,
                                    "max": 128}
    assert {m["name"] for m in e2e} == {"tokens_per_s", "setup_s"}
    names = {m["name"] for m in per_layer}
    assert set(NEW_READERS) <= names
    assert "decode_round_ms.saturated" in names
    assert not names & {"decode_attention_roofline",
                        "gqa_attention_roofline", "moe_ffn_roofline",
                        "mla_attention_roofline",
                        "held_experts_ffn_roofline", "moe_ffn_ms_per_round",
                        "conv_state_bytes_per_slot"}
    for m in per_layer:
        assert os.path.exists(os.path.join(bench_run.LAYERS_DIR,
                                           m["name"] + ".py")), m["name"]
        assert m["moves"] == "tokens_per_s"
    # every metric the five older decode cells share is reported here too
    five = {"gpt2s_decode_saturated", "gpt2s_decode_deep",
            "olmoe_decode_saturated", "lfm2_decode_saturated",
            "pangu_decode_saturated"}
    for m in manifest["per_layer"]:
        if five <= set(m.get("workloads", ())):
            assert CELL in m["workloads"], m["name"]
    # the prompts reach both buckets, none a multiple of the chunk
    chk = config["reference_check"]
    buckets = config["model"]["prefill_buckets"]
    assert {min(b for b in buckets if n <= b)
            for n in chk["prompt_tokens"]} == set(buckets)
    assert all(n % config["model"]["ssm_chunk"] for n in
               chk["prompt_tokens"])


def test_the_vocabulary_in_blocks_is_the_table_whole(monkeypatch):
    """`embed_tokens` and `head_blocked` (a block drawn, used, dropped)
    against `embed` and `head` on the tables `draw_tensor` gives whole, at a
    vocabulary of several ragged blocks."""
    import jax.numpy as jnp
    from benchmark.reference import falcon_h1_34b as reference
    monkeypatch.setattr(reference, "VOCAB_BLOCK", 40)
    model = {"vocab_size": 97, "d_model": 16, "n_heads": 2, "n_layers": 0,
             "dense_width": 8, "ssm_heads": 2, "ssm_head_dim": 4,
             "ssm_state": 4, "ssm_groups": 1, "ssm_conv_kernel": 2,
             "norm_eps": 1e-5, "embedding_multiplier": 3.0,
             "lm_head_multiplier": 0.25}
    shapes = reference.tensor_shapes(model)
    assert reference.vocab_blocks(97) == [(0, 40), (40, 40), (80, 17)]
    table = reference.draw_tensor("embed", shapes["embed"], 7, jnp.float32,
                                  model)
    tokens = np.array([0, 39, 40, 96, 5, 80, 41])
    np.testing.assert_array_equal(
        np.asarray(reference.embed_tokens(model, 7, tokens)),
        np.asarray(reference.embed(table, jnp.asarray(tokens), model)))
    x = jnp.asarray(np.random.RandomState(0).randn(5, 16), jnp.float32)
    lm_head = reference.draw_tensor("lm_head", shapes["lm_head"], 7,
                                    jnp.float32, model)
    np.testing.assert_allclose(
        reference.head_blocked(model, 7, x),
        np.asarray(reference.head(x, jnp.ones(16), lm_head, model)),
        atol=1e-6)
    # the scales undo the multipliers: x_0 and the logits have std ~1
    assert abs(float(jnp.std(table)) * 3.0 - 1.0) < 0.1
    assert abs(float(jnp.std(lm_head)) * 4.0 * 0.25 - 1.0) < 0.15


def test_ssm_update_cost_by_hand():
    """96 live slots of 32 x 128 x 256 fp32 state: read once, written once;
    the conv window of 3 x 5120 rolled; memory binds."""
    flops, bytes_ = costs_ssm.ssm_update_cost(96, 32, 128, 256, 2, 4)
    values = 32 * 128 * 256
    assert values == 1048576
    assert flops == 96 * (6.0 * values + 2.0 * 4 * 5120)
    assert bytes_ == 96 * (2.0 * values * 4 + 2.0 * 3 * 5120 * 4
                           + (5120 + 4096) * 4)
    assert round(bytes_ / 1e9, 3) == 0.821          # a layer, a trip
    assert bytes_ / 819e9 > flops / 197e12
    assert costs_ssm.ssm_update_cost(0, 32, 128, 256, 2, 4) == (0.0, 0.0)


class _Rec(object):
    def __init__(self, prompt_len, token_times, max_new=128, done=None):
        self.prompt_len, self.token_times = prompt_len, token_times
        self.max_new, self.done = max_new, done


def test_the_new_readers_find_and_time_their_operations():
    """Synthetic spans and a synthetic device plane: two dispatches of two
    trips over two live streams, and two prefills (buckets 256 and 512)
    whose executables both have a `fusion.3`, under `ssm_scan` in one
    only."""
    ops = []
    for r in (0.0, 0.010):
        ops += [("%fusion.7 = f32[2,9248] fusion(...)", r, r + 0.001),
                ("%fusion.8 = f32[2,32,128,256] fusion(...)", r + 0.001,
                 r + 0.005),
                ("%custom-call.3 = f32[2,20,128] custom-call(...), "
                 "call_target=\"tpu_custom_call\", frontend_attributes={"
                 "kernel_metadata={}}", r + 0.005, r + 0.007),
                ("%fusion.9 = f32[2,5120] fusion(...)", r + 0.007,
                 r + 0.010)]
    # a prefill of bucket 256 (fusion.3 is its scan), one of bucket 512
    # (fusion.3 is something else there, fusion.4 its scan)
    ops += [("%fusion.3 = f32[256,32,128] fusion(...)", 0.021, 0.024),
            ("%fusion.3 = f32[512,5120] fusion(...)", 0.031, 0.033),
            ("%fusion.4 = f32[512,32,128] fusion(...)", 0.033, 0.038)]
    trace = xplane.Trace({0: ops})
    trace.anchor = (0.0, 0.0, 100.0)           # monotonic 100 s = trace 0 s
    steps = [{"name": "serving/decode_step", "t0": 100.0 + r,
              "t1": 100.0 + r + 0.010, "attrs": {"tokens": 4, "trips": 2}}
             for r in (0.0, 0.010)]
    prefills = [{"name": "serving/prefill_compute", "t0": 100.020,
                 "t1": 100.030, "attrs": {"prompt": 200}},
                {"name": "serving/prefill_compute", "t0": 100.030,
                 "t1": 100.040, "attrs": {"prompt": 257}}]
    state_bytes = 4 * 2 * 32 * 128 * 256 * 4
    spans = steps + prefills + [
        {"name": "decode/fetch", "t0": s["t0"] + 0.001, "t1": s["t1"],
         "attrs": {"phase": "step", "ssm_state_bytes": state_bytes,
                   "ssm_layers": 4}} for s in steps]
    meta = {"n_layers": 4, "d_model": 5120, "n_heads": 20, "n_kv_heads": 4,
            "head_dim": 128, "layer_types": ["attention+ssm"] * 4,
            "ssm_heads": 32, "ssm_head_dim": 128, "ssm_state": 256,
            "ssm_groups": 2, "ssm_conv_kernel": 4,
            "prefill_buckets": [256, 512]}
    recs = [_Rec(300, [99.0]), _Rec(100, [99.5])]
    run = {"trace_window_monotonic": (100.0, 100.041),
           "trace_window": (0.0, 0.041), "window": (100.0, 100.041),
           "slots": 2, "records": recs, "device_kind": "TPU v5 lite",
           "kernel_match": {"hybrid_attention": "kernel_metadata={}"},
           "scope_ops": {"ssm_proj": ["fusion.7"],
                         "ssm_update": ["fusion.8"],
                         "ssm_scan@256": ["fusion.3"],
                         "ssm_scan@512": ["fusion.4"]},
           "meta": meta}
    read = bench_run.load_reader
    assert read("ssm_update_ms_per_trip")(spans, trace, run) \
        == pytest.approx(2.0)
    assert read("ssm_proj_ms_per_trip")(spans, trace, run) \
        == pytest.approx(0.5)
    assert read("ssm_state_bytes_per_slot")(spans, trace, run) \
        == state_bytes / 2
    # 3 ms of bucket 256's fusion.3, 5 ms of bucket 512's fusion.4; bucket
    # 512's own fusion.3 is no scan
    assert read("ssm_scan_ms_per_prefill")(spans, trace, run) \
        == pytest.approx((3.0 + 5.0) / 2)
    # the update: 2 dispatches x 2 trips x 4 layers over 2 live slots
    f, b = costs_ssm.ssm_update_cost(2, 32, 128, 256, 2, 4)
    assert read("ssm_update_roofline")(spans, trace, run) \
        == pytest.approx(100 * (2 * 2 * 4 * b / 819e9) / 0.008)
    # the kernel: the same calls over streams of 301 and 101 positions, a
    # token longer at the second trip, heads of 128 from the meta
    flops = bytes_ = 0.0
    for trip in (0, 1):
        f, b = costs_hybrid.gqa_attention_cost(
            [301 + trip, 101 + trip], 20, 4, 128)
        flops, bytes_ = flops + 2 * 4 * f, bytes_ + 2 * 4 * b
    least = max(flops / 197e12, bytes_ / 819e9)
    assert read("hybrid_attention_roofline")(spans, trace, run) \
        == pytest.approx(100 * least / 0.004)
    # a program without the scopes, the kernel or the meta (the parent, or
    # another configuration): nothing to read, no raise
    bare = dict(run, scope_ops={}, kernel_match={},
                meta={"n_layers": 2, "d_model": 64, "n_heads": 4})
    quiet = [dict(s, attrs={"phase": "step"}) if s["name"] == "decode/fetch"
             else s for s in spans]
    for name in NEW_READERS:
        assert read(name)(quiet, trace, bare) is None, name


def _tiny(seed, tolerances):
    """(ctx, meta) of the configuration at its tiny size, as the driver
    would see them."""
    from benchmark.reference import falcon_h1_34b as reference
    cfg = bench_run.load_json(os.path.join(
        bench_run.ROOT, "benchmark", "configs", CONFIG + ".json"))
    rehearse.TINY[CONFIG](cfg)
    cfg["tolerances"] = tolerances
    return (_Ctx(seed=seed, reference=reference, config=cfg),
            dict(cfg["model"]))


def _with_the_drivers_functions(monkeypatch):
    from benchmark.drivers import serve_decode_arch as arch
    from benchmark.drivers import serve_decode_ssm as drv
    for name in ("state_to_host", "reference_rows", "step_scope_ops"):
        monkeypatch.setattr(arch, name, getattr(drv, name))
    return arch, drv


def test_driver_holds_the_program_to_the_reference(tmp_path, monkeypatch):
    """`serve_decode_arch.check_against_reference` with this driver's
    `reference_rows` (rows gathered, head in blocks), fp32 on the CPU: both
    sides agree to rounding; and the names of the step's and of each
    prefill's instructions under the scopes."""
    from benchmark.reference import falcon_h1_34b as reference
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             save_decode_model)
    arch, drv = _with_the_drivers_functions(monkeypatch)
    monkeypatch.setattr(reference, "VOCAB_BLOCK", 40)
    ctx, meta = _tiny(2 ** 31 + 9, {"logits": 1e-4, "top1_gap": 2e-4,
                                    "precision_ratio": 0.5})
    state = arch.state_to_host(ctx, meta)
    assert state["l0_wq"].dtype.name == "bfloat16"
    assert state["l0_ssm_conv_w"].dtype == np.float32
    art = save_decode_model(str(tmp_path / "lm"), state, meta)
    pred = GenerativePredictor(art)
    assert arch.check_against_reference(ctx, pred, meta)
    log = ctx.logged[-1]
    assert log["positions"] == 3 * 5 and log["near_ties"] == 0
    assert log["max_logit_diff"] < 1e-4 and log["precision_ratio"] < 0.1
    ops = arch.step_scope_ops(pred, 4, ctx.config)
    assert ops["ssm_update"] and ops["ssm_proj"]
    for bucket in meta["prefill_buckets"]:
        assert ops["ssm_scan@%d" % bucket], bucket
    assert set(ops["ssm_update"]).isdisjoint(ops["ssm_proj"])


def _zero_state_from_prefill(dec, monkeypatch):
    import jax.numpy as jnp
    core = dec.GenerativePredictor._prefill_core

    def f(self, *a, **kw):
        out = core(self, *a, **kw)
        return out[:-1] + (jnp.zeros_like(out[-1]),)
    monkeypatch.setattr(dec.GenerativePredictor, "_prefill_core", f)


def _state_at_the_buckets_end(dec, monkeypatch):
    import jax.numpy as jnp
    scan = dec.ssd_chunked_scan

    def f(xs, Bm, Cm, dt, A, chunk, state=None):
        # the pads' dt is no longer 0: they decay and add to the state
        return scan(xs, Bm, Cm, jnp.where(dt == 0.0, 0.05, dt), A, chunk,
                    state)
    monkeypatch.setattr(dec, "ssd_chunked_scan", f)


def _ssm_fault(edit):
    """A fault planted in `_ssm`'s inputs: `edit(name, value)` rewrites a
    weight of the mixer as the layer reads it."""
    def plant(dec, monkeypatch):
        ssm = dec.GenerativePredictor._ssm

        def f(self, state, p, h, convolve, scope, scan):
            state = {n: edit(n[len(p):], v) if n.startswith(p + "ssm_")
                     else v for n, v in state.items()}
            return ssm(self, state, p, h, convolve, scope, scan)
        monkeypatch.setattr(dec.GenerativePredictor, "_ssm", f)
    return plant


def _swap_groups(dec, monkeypatch):
    import jax.numpy as jnp
    ssm = dec.GenerativePredictor._ssm

    def f(self, state, p, h, convolve, scope, scan):
        def swapped(xs, Bm, Cm, dt, A):
            return scan(xs, jnp.flip(Bm, axis=-2), jnp.flip(Cm, axis=-2),
                        dt, A)
        return ssm(self, state, p, h, convolve, scope, swapped)
    monkeypatch.setattr(dec.GenerativePredictor, "_ssm", f)


def _window_never_rolls(dec, monkeypatch):
    core = dec.GenerativePredictor._step_core

    def f(self, state, tables, *a, **kw):
        logits, new, facts = core(self, state, tables, *a, **kw)
        return logits, new[:2] + (tables[2],) + new[3:], facts
    monkeypatch.setattr(dec.GenerativePredictor, "_step_core", f)


def _kv_prefix_zeroed(dec, monkeypatch):
    core = dec.GenerativePredictor._prefill_core

    def f(self, *a, **kw):
        out = core(self, *a, **kw)
        return (out[0],) + tuple(t.at[:, :, :4].set(0.0)
                                 for t in out[1:3]) + out[3:]
    monkeypatch.setattr(dec.GenerativePredictor, "_prefill_core", f)


def _no_key_multiplier(dec, monkeypatch):
    block_of = dec.block_of
    monkeypatch.setattr(dec, "block_of", lambda meta: dict(
        block_of(meta), key_multiplier=1.0))


def _gate_after_the_norm(dec, monkeypatch):
    import jax
    import jax.numpy as jnp

    def f(y, z, g, groups, eps):
        y = y.reshape(y.shape[:-1] + (groups, -1))
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1,
                                       keepdims=True) + eps)
        return y.reshape(z.shape) * g * jax.nn.silu(z)
    monkeypatch.setattr(dec, "_gated_group_norm", f)


PLANTED = {
    "state_not_carried_from_prefill": _zero_state_from_prefill,
    "state_at_the_buckets_end": _state_at_the_buckets_end,
    "decay_without_dt_bias": _ssm_fault(
        lambda n, v: v * 0.0 if n == "ssm_dt_bias" else v),
    "b_and_c_swapped_between_groups": _swap_groups,
    "gate_after_the_norm": _gate_after_the_norm,
    "conv_window_never_rolls": _window_never_rolls,
    "kv_prefix_zeroed": _kv_prefix_zeroed,
    "key_multiplier_dropped": _no_key_multiplier,
}


@pytest.mark.parametrize("fault", sorted(PLANTED))
def test_a_planted_fault_is_refused_at_the_tiny_size(tmp_path, monkeypatch,
                                                     fault):
    """The comparison that decides `correct`, at a tiny size with the chip's
    own tolerances' ORDER (logits 0.08): each fault the issue names moves
    the logits by far more.  (A neighbouring slot's state and the whole
    forward in bfloat16 are planted on the chip: PERF.md section 6.)"""
    from paddle_tpu.inference import decode as dec
    arch, drv = _with_the_drivers_functions(monkeypatch)
    ctx, meta = _tiny(2 ** 31 + 21, {"logits": 0.08, "top1_gap": 0.16})
    art = dec.save_decode_model(str(tmp_path / "lm"),
                                arch.state_to_host(ctx, meta), meta)
    PLANTED[fault](dec, monkeypatch)
    pred = dec.GenerativePredictor(art)
    assert not arch.check_against_reference(ctx, pred, meta)
    assert ctx.logged[-1]["over_the_bounds"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
def test_falconh1_cell_rehearsal(manifest, trace, monkeypatch):
    from benchmark import peaks
    from benchmark.reference import falcon_h1_34b as reference
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(reference, "VOCAB_BLOCK", 40)
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
        optional = {"ssm_update_ms_per_trip", "ssm_update_roofline",
                    "ssm_proj_ms_per_trip", "ssm_scan_ms_per_prefill",
                    "hybrid_attention_roofline", "decode_kv_stream_share"}
        assert names - optional <= set(last["metrics"]) <= names
        assert last["metrics"]["ssm_state_bytes_per_slot"]["value"] \
            == 2 * 4 * 8 * 16 * 4
        fetch = [json.loads(ln) for ln in lines if '"served_check"' in ln]
        assert fetch and fetch[0]["ok"]
    else:
        assert set(last["metrics"]) == names
