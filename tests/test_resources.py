"""Static resource & cost analysis (ANALYSIS.md "Resource analysis").

Pins the analyzer's contracts: liveness-based memory planning on a
hand-built program (exact bytes), golden ResourceReports across all 7
zoo models (deterministic — static shapes in, bytes out), dtype-honest
byte accounting (the int8 twin reads <= 0.5x its fp32 artifact
statically), decode KV-cache bytes scaling with the slot table, the
FLOP formula table on the contraction class, the serving admission fit
check (typed rejection BEFORE any build/warm work), and the
est_peak_mb / est_flops exposure through describe()/stats/Prometheus.
"""

import json
import math
import os

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.framework import Program
from paddle_tpu.analysis import (ResourceFitError, ResourceReport,
                                 analyze_artifact, analyze_program,
                                 check_fit, device_peaks)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _restore_mem_flag():
    yield
    fluid.set_flags({"serving_device_mem_mb": 0})


# ---------------------------------------------------------------------------
# byte accounting primitives
# ---------------------------------------------------------------------------

def test_var_nbytes_hint_dtypes():
    from paddle_tpu.fluid import core as fcore
    p = Program()
    blk = p.global_block()
    f32 = blk.create_var(name="f", shape=[-1, 8], dtype="float32")
    i8 = blk.create_var(name="q", shape=[16, 4], dtype="int8")
    assert f32.numel_hint(batch=4) == 32
    assert f32.nbytes_hint(batch=4) == 128
    assert i8.nbytes_hint() == 64            # one byte per int8 element
    assert fcore.dtype_size("bfloat16") == 2
    assert fcore.dtype_size(np.float64) == 8


def test_liveness_memory_plan_exact_bytes():
    # x[4,8] -> relu -> h -> relu -> y ; w persistable [4,8].
    # params pinned whole-program; at op 1 both h and y are live along
    # with the still-live feed x => peak = 3*128 activations + 128 param
    p = Program()
    blk = p.global_block()
    blk.create_var(name="x", shape=[4, 8], dtype="float32", is_data=True)
    blk.create_var(name="w", shape=[4, 8], dtype="float32",
                   persistable=True)
    blk.create_var(name="h", shape=[4, 8], dtype="float32")
    blk.create_var(name="y", shape=[4, 8], dtype="float32")
    blk.append_op(type="relu", inputs={"X": ["x"]},
                  outputs={"Out": ["h"]}, infer_shape=False)
    blk.append_op(type="elementwise_add",
                  inputs={"X": ["h"], "Y": ["x"]},
                  outputs={"Out": ["y"]}, infer_shape=False)
    rep = analyze_program(p, feeds=["x"], fetches=["y"])
    assert rep.param_bytes == 128
    assert rep.activation_peak_bytes == 3 * 128
    assert rep.peak_bytes == 4 * 128
    assert rep.n_ops == 2
    kinds = {r["var"]: r["kind"] for r in rep.top_contributors}
    assert kinds["w"] == "param" and kinds["x"] == "feed"
    assert kinds["h"] == "activation"
    # wire-encodable report
    json.dumps(rep.to_dict())


def test_cost_model_mul_exact_flops():
    # X [3, 16] x Y [16, 5] => 2*3*16*5 FLOPs
    p = Program()
    blk = p.global_block()
    blk.create_var(name="x", shape=[3, 16], dtype="float32",
                   is_data=True)
    blk.create_var(name="w", shape=[16, 5], dtype="float32",
                   persistable=True)
    blk.create_var(name="o", shape=[3, 5], dtype="float32")
    blk.append_op(type="mul", inputs={"X": ["x"], "Y": ["w"]},
                  outputs={"Out": ["o"]}, infer_shape=False)
    rep = analyze_program(p, feeds=["x"], fetches=["o"])
    assert rep.total_flops == 2 * 3 * 16 * 5
    # bytes: x + w + o, fp32
    assert rep.total_bytes == (3 * 16 + 16 * 5 + 3 * 5) * 4
    assert rep.arithmetic_intensity == pytest.approx(
        rep.total_flops / rep.total_bytes)


def test_loop_resident_sub_block_counts_at_owning_op():
    # a while body's locals are loop-resident: they appear in the
    # timeline at the owning op's index
    p = Program()
    blk = p.global_block()
    blk.create_var(name="cond", shape=[1], dtype="bool", is_data=True)
    sub = p._create_block()
    sub.create_var(name="body_tmp", shape=[256], dtype="float32")
    sub.append_op(type="relu", inputs={"X": ["body_tmp"]},
                  outputs={"Out": ["body_tmp"]}, infer_shape=False)
    p._rollback()
    blk.append_op(type="while", inputs={"Cond": ["cond"]}, outputs={},
                  attrs={"sub_block": sub}, infer_shape=False)
    rep = analyze_program(p, feeds=["cond"])
    assert rep.activation_peak_bytes >= 256 * 4
    assert any(r["var"] == "body_tmp" and r["kind"] == "loop"
               for r in rep.top_contributors)


# ---------------------------------------------------------------------------
# golden reports across the zoo (deterministic: static shapes in,
# bytes out — the pins survive anything but a real model/cost change)
# ---------------------------------------------------------------------------

_GOLDEN = {
    # name: (param_bytes, peak_bytes, total_flops) — deterministic:
    # static shapes in, bytes out; regenerate with the snippet in
    # ANALYSIS.md if the models or the cost table legitimately change
    "mnist": (403012, 2403868, 91758004),
    "vgg": (183093596, 260421164, 7609255116),
    "resnet": (2186068, 8511060, 502292496),
    "se_resnext": (204523988, 329752792, 4323793326),
    "transformer": (6927596, 14710896, 226760507),
    "stacked_dynamic_lstm": (2286500, 3049172, 738182),
    "machine_translation": (680756, 909736, 441195),
}


@pytest.mark.parametrize("name", sorted(_GOLDEN))
def test_zoo_golden_resource_reports(name):
    import importlib
    import sys
    sys.path.insert(0, REPO)
    from tools.lint_program import ZOO, _name
    spec = next(z for z in ZOO if z[0] == name)
    _, mod, kw = spec
    m = importlib.import_module(mod)
    main, _startup, feeds, loss, acc, predict = m.get_model(**kw)
    fetches = [_name(v) for v in (loss, acc, predict) if v is not None]
    rep = analyze_program(main, feeds=[_name(f) for f in feeds],
                          fetches=fetches,
                          batch=kw.get("batch_size", 1))
    want_params, want_peak, want_flops = _GOLDEN[name]
    assert math.isclose(rep.param_bytes, want_params, rel_tol=0.02), \
        (name, rep.param_bytes)
    assert math.isclose(rep.peak_bytes, want_peak, rel_tol=0.05), \
        (name, rep.peak_bytes)
    assert math.isclose(rep.total_flops, want_flops, rel_tol=0.05), \
        (name, rep.total_flops)
    assert rep.peak_bytes > rep.param_bytes       # activations exist
    assert rep.precision == "fp32"


# ---------------------------------------------------------------------------
# artifacts: est-vs-actual, the quantized twin, decode KV scaling
# ---------------------------------------------------------------------------

def _export_fc(tmp_path, name="m", in_dim=64, hid=64):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[in_dim], dtype="float32")
        h = fluid.layers.fc(input=x, size=hid, act="relu")
        pred = fluid.layers.fc(input=h, size=8, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        md = str(tmp_path / name)
        fluid.save_inference_model(md, ["x"], [pred], exe,
                                   main_program=main)
    return md


def test_artifact_est_matches_actual_bytes(tmp_path):
    md = _export_fc(tmp_path)
    rep = analyze_artifact(md, batch=4)
    assert rep.actual_param_bytes is not None
    assert math.isclose(rep.param_bytes, rep.actual_param_bytes,
                        rel_tol=0.10)          # the acceptance bound
    assert rep.what == md and rep.batch == 4


def test_quantized_twin_static_footprint(tmp_path):
    from paddle_tpu.inference.quantize import quantize_inference_model
    md = _export_fc(tmp_path, in_dim=64, hid=64)
    q = quantize_inference_model(md, str(tmp_path / "m_int8"),
                                 min_weight_elems=1024)
    fp = analyze_artifact(md)
    qr = analyze_artifact(q["dst"])
    assert qr.precision == "int8" and fp.precision == "fp32"
    # the int8 lane's weight footprint reads statically: the 64x64 and
    # 64x8 weights drop to 1 byte/elem (+ fp32 scale rows)
    assert qr.param_bytes <= 0.5 * fp.param_bytes
    # and the estimate still matches the actual committed payloads
    assert math.isclose(qr.param_bytes, qr.actual_param_bytes,
                        rel_tol=0.10)


def test_decode_kv_bytes_scale_with_slots(tmp_path):
    from paddle_tpu.inference.decode import (GenerativePredictor,
                                             build_tiny_decode_model)
    d = str(tmp_path / "dec")
    build_tiny_decode_model(d, vocab_size=32, d_model=16, n_heads=2,
                            n_layers=2, max_seq_len=64)
    r4 = analyze_artifact(d, decode_slots=4)
    r8 = analyze_artifact(d, decode_slots=8)
    # K and V, [L, slots, S, H, Dh] fp32
    assert r4.kv_cache_bytes == 2 * 2 * 4 * 64 * 2 * 8 * 4
    assert r8.kv_cache_bytes == 2 * r4.kv_cache_bytes
    assert r8.peak_bytes > r4.peak_bytes
    assert r4.param_bytes == r4.actual_param_bytes
    assert r4.param_bytes > 0
    # the predictor's own accounting hooks agree with the analyzer
    g = GenerativePredictor(d)
    assert g.kv_cache_bytes(4) == r4.kv_cache_bytes
    assert g.param_bytes() == r4.param_bytes


def test_predictor_resource_report_post_transpile(tmp_path):
    from paddle_tpu.inference import AnalysisConfig, Predictor
    md = _export_fc(tmp_path)
    cfg = AnalysisConfig(model_dir=md)
    cfg.batch_size_buckets = (2, 8)
    p = Predictor(cfg)
    rep = p.resource_report()
    assert rep.batch == 8            # defaults to the largest bucket
    assert rep.peak_bytes > rep.param_bytes > 0
    assert rep.precision == "fp32"


# ---------------------------------------------------------------------------
# serving admission
# ---------------------------------------------------------------------------

def _export_big_fc(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[256], dtype="float32")
        h = fluid.layers.fc(input=x, size=2048, act="relu")
        pred = fluid.layers.fc(input=h, size=64, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        md = str(tmp_path / "big")
        fluid.save_inference_model(md, ["x"], [pred], exe,
                                   main_program=main)
    return md


def test_load_model_rejects_unfittable_before_build(tmp_path):
    from paddle_tpu import compile_cache
    from paddle_tpu.serving import ModelRegistry
    md = _export_big_fc(tmp_path)        # ~2.2 MiB of weights
    reg = ModelRegistry()
    fluid.set_flags({"serving_device_mem_mb": 2})
    cc_before = compile_cache.stats()
    with pytest.raises(ResourceFitError) as ei:
        reg.load_model("big", md)
    e = ei.value
    # the typed error names both sides of the comparison
    assert e.estimated_bytes > e.available_bytes
    assert e.available_bytes == 2 << 20
    assert str(e.estimated_bytes) in str(e)
    assert str(e.available_bytes) in str(e)
    # rejected BEFORE any build/warm work: no model entry, no compile
    assert reg.model_names() == []
    assert compile_cache.stats() == cc_before


def test_load_model_fit_ok_exposes_estimates(tmp_path):
    from paddle_tpu.serving import ModelRegistry
    md = _export_big_fc(tmp_path)
    reg = ModelRegistry()
    fluid.set_flags({"serving_device_mem_mb": 64})
    try:
        entry = reg.load_model("big", md, warm=False)
        assert entry.resource is not None
        assert entry.resource.peak_bytes > 0
        info = reg.describe()["big"]
        assert info["est_peak_mb"] == pytest.approx(
            entry.resource.peak_mb, abs=1e-3)
        assert info["est_flops"] == entry.resource.total_flops
        snap = reg.metrics.model("big").snapshot()
        assert snap["est_peak_mb"] == pytest.approx(
            entry.resource.peak_mb, abs=1e-3)
        assert snap["est_flops"] == entry.resource.total_flops
        from paddle_tpu.obs.registry import MetricsRegistry
        mreg = MetricsRegistry()
        mreg.attach_serving(reg.metrics)
        text = mreg.prometheus_text()
        assert 'paddle_tpu_model_est_peak_mb{model="big"}' in text
        assert 'paddle_tpu_model_est_flops{model="big"}' in text
    finally:
        reg.close_all(drain=False)


def test_fit_check_emits_rejected_event(tmp_path):
    from paddle_tpu.obs import events as obs_events
    from paddle_tpu.serving import ModelRegistry
    md = _export_big_fc(tmp_path)
    reg = ModelRegistry()
    fluid.set_flags({"serving_device_mem_mb": 1})
    with pytest.raises(ResourceFitError):
        reg.load_model("nofit", md)
    evs = [e for e in obs_events.recent_events(kind="model_fit_rejected")
           if e.get("model") == "nofit"]
    assert evs and evs[-1]["est_bytes"] > evs[-1]["available_bytes"]


def test_check_fit_no_budget_passes(tmp_path):
    # CPU + flag 0: no known budget -> trivially fits (avail None)
    rep = ResourceReport(what="x")
    rep.param_bytes = 10 << 30
    est, avail = check_fit(rep)
    assert est == rep.peak_bytes
    assert avail is None or avail > 0   # TPU hosts resolve a real cap


def test_device_peaks_table():
    peaks = device_peaks(None)
    assert peaks["peak_flops"] > 0 and peaks["hbm_bytes_per_s"] > 0
    # the roofline denominator rides the report
    rep = ResourceReport()
    assert rep.est_step_ms >= 0.0 and 0.0 <= rep.mfu_cap() <= 1.0


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_device_peaks_v5e_is_the_published_row(kind):
    peaks = device_peaks(_FakeDevice("tpu", kind))
    assert (peaks["peak_flops"], peaks["hbm_bytes_per_s"],
            peaks["hbm_bytes"]) == (197e12, 819e9, 16 << 30)


def test_device_peaks_unknown_accelerator_raises():
    """The cpu placeholder is for platform == 'cpu' only: an accelerator
    kind the table does not hold is an error, never a made-up row."""
    assert device_peaks(_FakeDevice("cpu", "cpu"))["hbm_bytes"] == 0
    with pytest.raises(ValueError, match="TPU v9"):
        device_peaks(_FakeDevice("tpu", "TPU v9"))
    with pytest.raises(ValueError, match="gpu"):
        device_peaks(_FakeDevice("gpu", "cpu-like name"))


# ---------------------------------------------------------------------------
# debugger cost columns (satellite)
# ---------------------------------------------------------------------------

def test_debugger_renders_cost_columns(tmp_path):
    p = Program()
    blk = p.global_block()
    blk.create_var(name="x", shape=[3, 16], dtype="float32",
                   is_data=True)
    blk.create_var(name="w", shape=[16, 5], dtype="float32",
                   persistable=True)
    blk.create_var(name="o", shape=[3, 5], dtype="float32")
    blk.append_op(type="mul", inputs={"X": ["x"], "Y": ["w"]},
                  outputs={"Out": ["o"]}, infer_shape=False)
    rep = analyze_program(p, feeds=["x"], fetches=["o"])
    txt = fluid.debugger.pprint_program_codes(p, costs=rep)
    assert "est_flops=" in txt and "est_bytes=" in txt
    # report hook the columns ride
    assert rep.op_cost(0, 0) == (480, 572)    # 2*3*16*5 F, 143 elems
    dot = fluid.debugger.draw_block_graphviz(
        blk, path=str(tmp_path / "g.dot"), costs=rep)
    assert "480F" in dot and "572B" in dot
    # without costs the old contract holds
    bare = fluid.debugger.pprint_program_codes(p)
    assert "est_flops" not in bare
