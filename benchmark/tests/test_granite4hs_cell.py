"""Tests of what the `granite_4_0_h_small` configuration and its cell add to
the benchmark, on the CPU: the configuration file against the catalog's
numbers and the cut's arithmetic, the reference's own properties (a member's
run of the experts is that run of the layer drawn whole; a hint is followed
through a near-tie and through nothing else), the two new readers on
synthetic spans (and that they read nothing from a program without the
counter or the scopes), the driver's comparison with the plain reference (and
that it can fail, by each planted fault), that a program which cannot
describe the stack is refused before anything is drawn, and the cell's whole
rehearsal (slow).

`rehearse.TINY` / `rehearse.TINY_TRAFFIC`: as
benchmark/tests/test_olmoe_cell.py says, both entries are made HERE, at
import.
"""

import json
import os

import numpy as np
import pytest

from benchmark import xplane
from benchmark import run as bench_run
from benchmark.tests import rehearse
from benchmark.tests.test_kexaone_cell import _activations_in_bfloat16
from benchmark.tests.test_olmoe_cell import _Ctx

CELL, CONFIG, MIX = ("granite4hs_decode_saturated", "granite_4_0_h_small",
                     "granite4hs_decode_saturated")

rehearse.TINY.setdefault(CONFIG, lambda c: (
    c["model"].update(vocab_size=97, d_model=48, n_heads=4, n_kv_heads=2,
                      head_dim=8, n_layers=3,
                      layer_types=["ssm", "attention", "ssm"],
                      max_seq_len=128, prefill_buckets=[16, 64],
                      ssm_heads=4, ssm_head_dim=8, ssm_state=16,
                      ssm_groups=1, ssm_chunk=8, n_experts=8,
                      experts_per_token=3, expert_width=16,
                      experts_held=[0, 2]),
    c["deployment"].update(decode_slots=4),
    c.update(reference_check={"prompt_tokens": [5, 20, 40], "steps": 4})))
rehearse.TINY_TRAFFIC.setdefault(MIX, lambda m: (
    m.update(requests=32),
    m["prompt_tokens"].update(min=8, max=30),
    m["output_tokens"].update(min=12, max=24)))

# The catalog's entry (model-configs guide, architectures.jsonl,
# granite-4.0-h-small, `config`), number for number.
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": PERIOD * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352}
REDUCED = ["num_hidden_layers", "num_local_experts", "vocab_size"]
NEW_READERS = ("ssm_share_of_trip", "held_pairs_per_expert")


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
            m["n_layers"], m["vocab_size"], m["expert_width"]) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"],
        config["hidden_size"] // config["num_attention_heads"],
        config["num_hidden_layers"], config["vocab_size"],
        config["intermediate_size"]) == (4096, 32, 8, 128, 10, 25088, 768)
    assert (m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"],
            m["ssm_groups"], m["ssm_conv_kernel"], m["ssm_chunk"]) == (
        config["mamba_n_heads"], config["mamba_d_head"],
        config["mamba_d_state"], config["mamba_n_groups"],
        config["mamba_d_conv"], config["mamba_chunk_size"])
    assert m["ssm_heads"] * m["ssm_head_dim"] \
        == config["mamba_expand"] * config["hidden_size"]
    # the router keeps all 72 outputs and its top-10; the member holds 18
    assert (m["n_experts"], m["experts_per_token"], m["experts_held"]) == (
        config["published"]["num_local_experts"],
        config["num_experts_per_tok"], [0, config["num_local_experts"]])
    assert m["n_shared_experts"] * m["expert_width"] \
        == config["shared_intermediate_size"]
    # the three multipliers and the logits' divisor, as published
    assert (m["embedding_multiplier"], m["attention_multiplier"],
            m["residual_multiplier"], 1.0 / m["lm_head_multiplier"]) == (
        config["embedding_multiplier"], config["attention_multiplier"],
        config["residual_multiplier"], config["logits_scaling"])
    assert (m["norm"], m["norm_eps"], m["position"], m["ffn"], m["router"],
            m["norm_topk_prob"], m["head"], m["weight_dtype"]) == (
        "rmsnorm", config["rms_norm_eps"], "none", "moe_swiglu", "softmax",
        True, "tied", "bfloat16")
    # the pattern's period, under the meta's names
    assert m["layer_types"] == ["ssm" if t == "mamba" else t
                                for t in config["layer_types"][:10]]
    assert set(config["assumed"]) >= {
        "dt_limits", "ssm_parameters", "gated_norm", "conv_state",
        "router_weights", "dtype", "weight_scales", "sampling", "eos_id",
        "max_seq_len", "prefill_buckets", "decode_slots"}
    assert "four chips" in config["deployment"]["stands_for"].lower() \
        or "FOUR chips" in config["deployment"]["stands_for"]
    assert config["deployment"]["decode_slots"] in (64, 80, 96)
    assert config["driver"] == "serve_decode_recurrent_moe"


def test_the_cut_is_the_arithmetic_the_file_states(config):
    """2.96 B parameters, 5.92 GB at rest; a Mamba layer 291.3 M, the
    attention layer 231.0 M; a slot 47.05 MB of which 37.75 MB scanned
    state: the reference's shapes add up to what the file says."""
    import jax.numpy as jnp
    from benchmark.reference import granite_4_0_h_small as reference
    from paddle_tpu.inference import decode as dec
    from paddle_tpu.inference import slot_state
    m = config["model"]
    shapes = reference.tensor_shapes(m)
    assert shapes == dec.decode_state_shapes(m)
    params = sum(int(np.prod(s)) for s in shapes.values())
    rest = sum(int(np.prod(s)) * jnp.dtype(reference.at_rest(n, s)).itemsize
               for n, s in shapes.items())
    assert (params, rest) == (2955758208, 5918501376)

    def layer(i, names=None):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.startswith("l%d_" % i)
                   and (names is None or n.split("_", 1)[1] in names))
    assert (layer(0), layer(5)) == (291333760, 230989824)
    assert layer(0, reference.SSM_WEIGHTS) == 102286976
    assert layer(5, reference.ATTENTION_WEIGHTS) == 41943040
    assert layer(0, ("w_gate", "w_up", "w_down")) == 18 * 9437184
    assert layer(0, ("shared_gate", "shared_up", "shared_down")) == 18874368
    assert int(np.prod(shapes["embed"])) == 25088 * 4096
    # whole: 36 Mamba layers and 4 attention layers with all 72 experts,
    # the whole vocabulary: the published 32 B
    whole = 36 * (layer(0) + 54 * 9437184) + 4 * (layer(5) + 54 * 9437184) \
        + 100352 * 4096
    assert round(whole / 1e9, 1) == 32.2
    kinds, totals = slot_state.state_bytes(m, dec.block_of(m), 1, None)
    assert kinds == {"kv": 8388608, "conv": 912384, "ssm": 37748736}
    assert kinds["ssm"] == 9 * 4194304
    d = config["deployment"]
    n = d["decode_slots"]
    assert (d["ssm_state_table_bytes"], d["kv_table_bytes"],
            d["conv_state_table_bytes"]) == (
        n * kinds["ssm"], n * kinds["kv"], n * kinds["conv"])


def test_a_hint_is_followed_through_a_near_tie_and_nothing_else():
    """`reference.routed_ffn`: the hinted experts replace the reference's
    own top k where every one of them lies within the margin of its k-th
    logit, and nowhere else; the weights are the softmax over the kept
    logits, which is the program's softmax over all of them, renormalised
    over the kept."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import granite_4_0_h_small as reference
    from paddle_tpu.inference import decode as dec
    model = dict(d_model=16, n_experts=12, experts_per_token=3,
                 expert_width=8, n_shared_experts=1, experts_held=[])
    rng = np.random.RandomState(0)
    w = {n: jnp.asarray(rng.randn(*s), jnp.float32) for n, s in (
        ("router", (16, 12)), ("w_gate", (12, 16, 8)), ("w_up", (12, 16, 8)),
        ("w_down", (12, 8, 16)))}
    g = jnp.asarray(rng.randn(6, 16), jnp.float32)
    with jax.default_matmul_precision("highest"):
        own, gap, used, short = reference.routed_ffn(g, w, model)
        assert np.isinf(np.asarray(short)).all()
        got, _ = dec.moe_ffn(g, w["router"], w["w_gate"], w["w_up"],
                             w["w_down"], 3, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(own),
                                   atol=1e-4)
        logits = np.asarray(g @ w["router"])
        order = np.argsort(-logits, axis=-1)
        # the hint: the 4th in place of the 3rd, at every position
        hint = np.sort(np.concatenate([order[:, :2], order[:, 3:4]], -1), -1)
        lag = logits[np.arange(6), order[:, 2]] \
            - logits[np.arange(6), order[:, 3]]
        np.testing.assert_allclose(np.asarray(gap), lag, atol=1e-6)
        margin = float(np.sort(lag)[2]) + 1e-6       # three of six within
        out, _, used_h, short_h = reference.routed_ffn(
            g, w, model, jnp.asarray(hint, jnp.int32), margin)
        np.testing.assert_allclose(np.asarray(short_h), lag, atol=1e-6)
        follows = lag <= margin
        assert follows.sum() == 3
        np.testing.assert_array_equal(
            np.asarray(used_h), np.where(follows[:, None], hint,
                                         np.asarray(used)))
        moved = np.abs(np.asarray(out) - np.asarray(own)).max(axis=-1)
        assert (moved[follows] > 1e-3).all() and (moved[~follows] == 0).all()
        # a row of -1 hints nothing
        none, _, used_n, _ = reference.routed_ffn(
            g, w, model, jnp.full((6, 3), -1, jnp.int32), 1e9)
        np.testing.assert_array_equal(np.asarray(used_n), np.asarray(used))


def test_the_new_readers_read_their_spans_and_nothing_from_a_parent():
    """Synthetic spans and a synthetic device plane: two dispatches of two
    trips; `ssm_share_of_trip` is the two scopes' seconds over the busy
    seconds inside the dispatches, `held_pairs_per_expert` the mean of
    pairs over experts a step fetch; a parent's spans (no counter) and a
    run that names no scope give nothing and do not raise."""
    ops = []
    for r in (0.0, 0.010):
        ops += [("%fusion.7 = f32[2,16768] fusion(...)", r, r + 0.001),
                ("%ssm_update.3 = f32[2,64,128] custom-call(...)",
                 r + 0.001, r + 0.005),
                ("%fusion.9 = f32[2,4096] fusion(...)", r + 0.005,
                 r + 0.008)]
    trace = xplane.Trace({0: ops})
    trace.anchor = (0.0, 0.0, 100.0)           # monotonic 100 s = trace 0 s
    steps = [{"name": "serving/decode_step", "t0": 100.0 + r,
              "t1": 100.0 + r + 0.010, "attrs": {"tokens": 4, "trips": 2}}
             for r in (0.0, 0.010)]
    fetch = [{"name": "decode/fetch", "t0": s["t0"] + 0.001, "t1": s["t1"],
              "attrs": {"phase": "step", "moe_experts_touched": touched,
                        "moe_pairs_held": pairs}}
             for s, touched, pairs in zip(steps, (8, 10), (20, 40))]
    fetch.append({"name": "decode/fetch", "t0": 100.015, "t1": 100.016,
                  "attrs": {"phase": "prefill", "moe_experts_touched": 18,
                            "moe_pairs_held": 700}})
    spans = steps + fetch
    meta = {"n_layers": 10, "experts_held": [0, 18], "n_experts": 72}
    run = {"trace_window_monotonic": (100.0, 100.021),
           "trace_window": (0.0, 0.021), "window": (100.0, 100.021),
           "slots": 2, "device_kind": "TPU v5 lite", "meta": meta,
           "scope_ops": {"ssm_proj": ["fusion.7"],
                         "ssm_update": ["ssm_update.3"]}}
    read = bench_run.load_reader
    # 1 + 4 ms of 8 busy ms a dispatch
    assert read("ssm_share_of_trip")(spans, trace, run) \
        == pytest.approx(100 * 5.0 / 8.0)
    assert read("held_pairs_per_expert")(spans, trace, run) \
        == pytest.approx((20 / 8 + 40 / 10) / 2)
    quiet = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                            if k != "moe_pairs_held"}) for s in spans]
    assert read("held_pairs_per_expert")(quiet, trace, run) is None
    assert read("held_pairs_per_expert")(
        spans, trace, dict(run, meta={"n_layers": 2})) is None
    assert read("ssm_share_of_trip")(
        spans, trace, dict(run, scope_ops={})) is None
    assert read("ssm_share_of_trip")(
        spans, trace, dict(run, scope_ops={"ssm_update": ["x"]})) is None


def test_the_manifest_enters_the_cell_where_its_readers_read(manifest):
    cell = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    mine = {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", ())}
    assert mine >= set(NEW_READERS) | {
        "moe_ffn_ms_per_round", "held_experts_ffn_roofline",
        "ssm_update_ms_per_trip", "ssm_update_roofline",
        "ssm_proj_ms_per_trip", "ssm_state_bytes_per_slot",
        "hybrid_attention_roofline", "slots_busy_share",
        "decode_round_ms.saturated", "prefill_prompts_per_call",
        "prefill_ahead_share"}
    # Y10: a grouped prefill is misread by this one until it is repaired
    assert "ssm_scan_ms_per_prefill" not in mine
    by = {m["name"]: m for m in manifest["per_layer"]}
    assert by["ssm_share_of_trip"]["workloads"] == [
        CELL, "falconh1_decode_saturated"]
    assert by["held_pairs_per_expert"]["workloads"] == [
        CELL, "pangu_decode_saturated", "kexaone_decode_mixed_len",
        "mimov2flash_reasoning_decode"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["tokens_per_s"]["workloads"]
    mix = bench_run.load_json(os.path.join(
        bench_run.TRAFFIC_DIR, MIX + ".json"))
    falcon = bench_run.load_json(os.path.join(
        bench_run.TRAFFIC_DIR, "falconh1_decode_saturated.json"))
    assert {k: v for k, v in mix.items() if k != "why"} \
        == {k: v for k, v in falcon.items() if k != "why"}


def _tiny(seed, tolerances):
    """(ctx, meta) of the configuration at its tiny size, as the driver
    would see them."""
    from benchmark.reference import granite_4_0_h_small as reference
    cfg = bench_run.load_json(os.path.join(
        bench_run.ROOT, "benchmark", "configs", CONFIG + ".json"))
    rehearse.TINY[CONFIG](cfg)
    cfg["tolerances"] = tolerances
    return (_Ctx(seed=seed, reference=reference, config=cfg),
            dict(cfg["model"]))


def _with_the_drivers_functions(monkeypatch):
    from benchmark.drivers import serve_decode_arch as arch
    from benchmark.drivers import serve_decode_recurrent_moe as drv
    for name in ("state_to_host", "reference_rows", "step_scope_ops",
                 "program_logits", "check_against_reference"):
        monkeypatch.setattr(arch, name, getattr(drv, name))
    return arch, drv


def _store_off():
    """The executable store keys a phase by the artifact and the meta, not
    by the code: with it on, a plant would load whatever phase of these
    weights an earlier test left there."""
    from paddle_tpu.flags import FLAGS, set_flags
    was = FLAGS.compile_cache
    set_flags({"compile_cache": False})
    return lambda: set_flags({"compile_cache": was})


def test_driver_holds_the_program_to_the_reference(tmp_path, monkeypatch):
    """`serve_decode_arch.check_against_reference` with this driver's
    functions, fp32 on the CPU: both sides agree to rounding, every compared
    position was hinted (none is excusable), the hints covered the prompts'
    positions from the first on, by the PREFILL's own picks; the program,
    which rounds nothing here, lies on the float32 forward and far from the
    pass below the stated precision; and the names of the step's and of
    each prefill's instructions under the scopes."""
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             save_decode_model)
    arch, drv = _with_the_drivers_functions(monkeypatch)
    ctx, meta = _tiny(2 ** 31 + 9, {"logits": 1e-4, "top1_gap": 2e-4,
                                    "precision_ratio": 0.5,
                                    "stated_precision_ratio": 0.5,
                                    "router_margin": 0.02})
    state = arch.state_to_host(ctx, meta)
    assert state["l0_ssm_in"].dtype.name == "bfloat16"
    assert state["l0_router"].dtype == np.float32
    assert state["l0_ssm_conv_w"].dtype == np.float32
    art = save_decode_model(str(tmp_path / "lm"), state, meta)
    pred = GenerativePredictor(art)
    assert arch.check_against_reference(ctx, pred, meta)
    log = [f for f in ctx.logged if f.get("phase") == "reference_check"][-1]
    assert log["positions"] == 3 * 5 and log["near_ties"] == 0
    assert log["max_logit_diff"] < 1e-4 and log["precision_ratio"] < 0.1
    stated = ctx.logged[-1]
    assert stated["phase"] == "stated_precision_check" and stated["ok"]
    assert stated["positions"] == 3 * 4
    assert stated["stated_precision_ratio"] < 0.01
    assert stated["logit_diff_median_float32"] < 1e-4 \
        < 1e-3 < stated["logit_diff_median_stated"] \
        < stated["logit_diff_median_below"]
    routing = [f for f in ctx.logged if f.get("phase") == "routing_check"]
    # three routed layers x (5 + 20 + 40 prompt positions + 3 x 4 steps),
    # in each of the four passes
    assert [r["decisions"] for r in routing] == [3 * (5 + 20 + 40 + 12)] * 4
    assert [(r["dtype"], r["precision"]) for r in routing] == [
        ("float32", None), ("bfloat16", None), ("float32", "stated"),
        ("float32", "below")]
    assert routing[0]["not_followed"] == 0
    picks = ctx._program_picks
    assert sorted(v[1].shape for v in picks.values()) == [
        (3, 9, 3), (3, 24, 3), (3, 44, 3)]
    assert all(v[0] == 0 for v in picks.values())
    ops = arch.step_scope_ops(pred, 4, ctx.config)
    for scope in ctx.config["trace_scopes"]:
        assert ops[scope], scope
    for bucket in meta["prefill_buckets"]:
        assert ops["ssm_scan@%d" % bucket], bucket
    assert set(ops["ssm_update"]).isdisjoint(ops["ssm_proj"])


def _zero_state_from_prefill(dec, mp):
    import jax.numpy as jnp
    core = dec.GenerativePredictor._prefill_core

    def f(self, *a, **kw):
        out = core(self, *a, **kw)
        return out[:-1] + (jnp.zeros_like(out[-1]),)
    mp.setattr(dec.GenerativePredictor, "_prefill_core", f)


def _a_neighbours_slot(dec, mp):
    """A step's recurrence reads, and writes back, the state of the slot
    before."""
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    update = pk.ssm_update

    def f(ss, *a, **kw):
        y, new = update(jnp.roll(ss, 1, axis=1), *a, **kw)
        return y, jnp.roll(new, -1, axis=1)
    mp.setattr(pk, "ssm_update", f)


def _state_at_the_buckets_end(dec, mp):
    import jax.numpy as jnp
    scan = dec.ssd_chunked_scan

    def f(xs, Bm, Cm, dt, A, chunk, state=None):
        # the pads' dt is no longer 0: they decay and add to the state
        return scan(xs, Bm, Cm, jnp.where(dt == 0.0, 0.05, dt), A, chunk,
                    state)
    mp.setattr(dec, "ssd_chunked_scan", f)


def _meta_edit(**edit):
    def plant(dec, mp):
        block_of = dec.block_of
        mp.setattr(dec, "block_of", lambda meta: dict(block_of(meta),
                                                      **edit))
    return plant


def _residual_multiplier_dropped_on_the_mixer(dec, mp):
    ssm = dec.GenerativePredictor._ssm

    def f(self, *a, **kw):
        return ssm(self, *a, **kw) / self._block_meta["residual_multiplier"]
    mp.setattr(dec.GenerativePredictor, "_ssm", f)


def _gate_after_the_norm(dec, mp):
    import jax
    import jax.numpy as jnp

    def f(y, z, g, groups, eps):
        y = y.reshape(y.shape[:-1] + (groups, -1))
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1,
                                       keepdims=True) + eps)
        return y.reshape(z.shape) * g * jax.nn.silu(z)
    mp.setattr(dec, "_gated_group_norm", f)


def _another_members_run(dec, mp):
    """The weights held are taken for the NEXT member's run of experts."""
    block_of = dec.block_of

    def f(meta):
        blk = block_of(meta)
        first, count = blk["experts_held"]
        return dict(blk, experts_held=(first + count, count))
    mp.setattr(dec, "block_of", f)


PLANTED = {
    "state_not_carried_from_prefill": _zero_state_from_prefill,
    "a_neighbours_slot": _a_neighbours_slot,
    "state_at_the_buckets_end": _state_at_the_buckets_end,
    "scale_one_over_sqrt_head_dim": _meta_edit(attention_multiplier=0.0),
    "residual_multiplier_dropped_on_the_mixer":
        _residual_multiplier_dropped_on_the_mixer,
    "rotation_applied": _meta_edit(position="rope"),
    "softmax_over_all_not_renormalised": _meta_edit(norm_topk_prob=False),
    "gate_after_the_norm": _gate_after_the_norm,
    "another_members_run_counted": _another_members_run,
}
def _activations_held_in_bfloat16(dec, mp):
    """`test_kexaone_cell._activations_in_bfloat16` (every matmul's result,
    every norm's and the residual stream a layer hands on kept as bfloat16
    numbers) by `reduce_precision`: a convert to bfloat16 and back inside
    one program is one the TPU's compiler may drop."""
    import jax
    P = dec.GenerativePredictor

    def low(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    contract, rms, block = dec._contract, dec._rms, P._block
    mp.setattr(dec, "_contract", lambda x, w, c: low(contract(x, w, c)))
    mp.setattr(dec, "_rms", lambda x, g, eps: low(rms(x, g, eps)))

    def f(self, *a, **kw):
        x, facts = block(self, *a, **kw)
        return low(x), facts
    mp.setattr(P, "_block", f)


def _router_off_by(std):
    """The router's logits moved by normal(0, std) noise (a fixed matrix
    added to each layer's router: the normed input has rms ~1)."""
    def plant(dec, mp):
        import jax.numpy as jnp
        moe = dec.moe_ffn

        def f(h, router, *a, **kw):
            rng = np.random.default_rng(router.shape[0] * 7 + 1)
            noise = rng.standard_normal(router.shape).astype(np.float32) \
                * np.float32(std / np.sqrt(router.shape[0]))
            return moe(h, router + jnp.asarray(noise), *a, **kw)
        mp.setattr(dec, "moe_ffn", f)
    return plant


# refused by `stated_precision_ratio` (and the convert-pair form, on one
# seed of six, by `precision_ratio`), which need the chip's rounding; and
# the router's logits moved by noise of half and of four times
# `router_margin` (below it the reference follows the program's picks, by
# design): planted there (PERF.md section 6, PR 56), walked here
PLANTED_ON_THE_CHIP = {
    "activations_in_bfloat16": _activations_in_bfloat16,
    "activations_held_in_bfloat16": _activations_held_in_bfloat16,
    "router_off_by_half_the_margin": _router_off_by(0.075),
    "router_off_by_four_margins": _router_off_by(0.6)}


@pytest.mark.parametrize("fault", sorted(PLANTED))
def test_a_planted_fault_is_refused_at_the_tiny_size(tmp_path, monkeypatch,
                                                     fault):
    """The comparison that decides `correct`, at a tiny size with the chip's
    own tolerances' ORDER (logits 0.1): each fault the issue names moves
    the logits by far more."""
    from paddle_tpu.inference import decode as dec
    arch, drv = _with_the_drivers_functions(monkeypatch)
    ctx, meta = _tiny(2 ** 31 + 21, {"logits": 0.1, "top1_gap": 0.2,
                                     "router_margin": 0.02})
    art = dec.save_decode_model(str(tmp_path / "lm"),
                                arch.state_to_host(ctx, meta), meta)
    PLANTED[fault](dec, monkeypatch)
    back = _store_off()
    try:
        pred = dec.GenerativePredictor(art)
        assert not arch.check_against_reference(ctx, pred, meta)
    finally:
        back()
    assert [f for f in ctx.logged if f.get("phase") == "reference_check"][
        -1]["over_the_bounds"] > 0


def _operands_in_bfloat16(dec, mp):
    """What `decode._contract` does where it lowers for the TPU, here on the
    CPU: a matmul against a weight takes its activation rounded to
    bfloat16 (the precision the configuration states)."""
    import jax
    contract = dec._contract
    mp.setattr(dec, "_contract", lambda x, w, c: contract(
        jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7), w, c))


@pytest.mark.parametrize("plant", [None, "activations_in_bfloat16",
                                   "activations_held_in_bfloat16"])
def test_the_program_stands_between_the_stated_precision_and_the_one_below(
        tmp_path, monkeypatch, plant):
    """The chip's rounding of a matmul's operands, planted on the CPU: the
    sound program lies nearer the reference's pass at the STATED precision
    than the pass below it, and passes.  A program that keeps its
    activations as bfloat16 numbers besides moves every position, lies
    nearer the pass BELOW, and `stated_precision_ratio` refuses it."""
    from paddle_tpu.inference import decode as dec
    arch, drv = _with_the_drivers_functions(monkeypatch)
    ctx, meta = _tiny(2 ** 31 + 21, {"logits": 1.0, "top1_gap": 2.0,
                                     "stated_precision_ratio": 1.0,
                                     "router_margin": 0.02})
    art = dec.save_decode_model(str(tmp_path / "lm"),
                                arch.state_to_host(ctx, meta), meta)
    _operands_in_bfloat16(dec, monkeypatch)
    if plant:
        PLANTED_ON_THE_CHIP[plant](dec, monkeypatch)
    back = _store_off()
    try:
        ok = arch.check_against_reference(
            ctx, dec.GenerativePredictor(art), meta)
    finally:
        back()
    log = [f for f in ctx.logged if f.get("phase") == "reference_check"][-1]
    assert log["over_the_bounds"] == 0 and log["logit_diff_median"] > 1e-3
    stated = ctx.logged[-1]
    assert ok == stated["ok"] == (plant is None)
    nearer, farther = ("stated", "below") if plant is None \
        else ("below", "stated")
    assert 2 * stated["logit_diff_median_" + nearer] \
        < stated["logit_diff_median_" + farther]
    if plant is None:
        assert stated["stated_precision_ratio"] < 0.5
    else:
        assert stated["stated_precision_ratio"] > 2.0


@pytest.mark.parametrize("std, followed", [(0.001, True), (0.5, False)])
def test_a_router_moved_within_the_margin_is_followed_and_past_it_refused(
        tmp_path, monkeypatch, std, followed):
    """The router's logits moved by noise well inside `router_margin`: the
    program's other picks are near-ties to the reference, which follows
    them, and the logits agree.  Moved by noise far past it: picks the
    reference does not follow, and `logits` refuses the run."""
    from paddle_tpu.inference import decode as dec
    arch, drv = _with_the_drivers_functions(monkeypatch)
    ctx, meta = _tiny(2 ** 31 + 21, {"logits": 0.02, "top1_gap": 0.04,
                                     "precision_ratio": 0.5,
                                     "stated_precision_ratio": 0.5,
                                     "router_margin": 0.02})
    art = dec.save_decode_model(str(tmp_path / "lm"),
                                arch.state_to_host(ctx, meta), meta)
    _router_off_by(std)(dec, monkeypatch)
    back = _store_off()
    try:
        ok = arch.check_against_reference(
            ctx, dec.GenerativePredictor(art), meta)
    finally:
        back()
    routing = [f for f in ctx.logged if f.get("phase") == "routing_check"][0]
    log = [f for f in ctx.logged if f.get("phase") == "reference_check"][-1]
    assert ok == followed
    assert (routing["not_followed"] == 0) == followed
    assert (log["over_the_bounds"] == 0) == followed


def test_a_program_that_cannot_describe_the_stack_fails_at_once(
        monkeypatch):
    """The parent's `block_of` knows no `position: "none"`: the driver's
    first act raises its typed error, which names the key, before the
    generator's process or a weight exists; a program that lacks a key of
    the configuration's `model` is refused by the key's name."""
    from benchmark import loadgen
    from benchmark.drivers import serve_decode_recurrent_moe as drv
    from paddle_tpu.inference import decode as dec
    ctx, _ = _tiny(1, {})
    monkeypatch.setattr(loadgen, "Generator", lambda: pytest.fail(
        "the generator's process was started"))
    monkeypatch.setitem(dec._BLOCK_CHOICES, "position", ("learned", "rope"))
    with pytest.raises(ValueError, match="position"):
        drv.run(ctx)
    monkeypatch.setitem(dec._BLOCK_CHOICES, "position",
                        ("learned", "rope", "none"))
    ctx.config["model"]["a_key_of_a_later_program"] = 1
    with pytest.raises(SystemExit, match="a_key_of_a_later_program"):
        drv.run(ctx)


@pytest.mark.slow
@pytest.mark.parametrize("trace", [0, 1])
def test_granite4hs_cell_rehearsal(manifest, trace, monkeypatch):
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
        optional = {"ssm_update_ms_per_trip", "ssm_update_roofline",
                    "ssm_proj_ms_per_trip", "ssm_share_of_trip",
                    "hybrid_attention_roofline", "decode_kv_stream_share",
                    "moe_ffn_ms_per_round", "held_experts_ffn_roofline"}
        assert names - optional <= set(last["metrics"]) <= names
        assert last["metrics"]["ssm_state_bytes_per_slot"]["value"] \
            == 2 * 4 * 8 * 16 * 4
        assert last["metrics"]["held_pairs_per_expert"]["value"] >= 1.0
        fetch = [json.loads(ln) for ln in lines if '"served_check"' in ln]
        assert fetch and fetch[0]["ok"]
    else:
        assert set(last["metrics"]) == names
