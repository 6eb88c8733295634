"""`benchmark/layers/prefill_ahead_share.py` on a span list with and without
the attribute it reads, and its manifest entry (PR 53: the one file that PR
adds under benchmark/ is the reader; its test is here, where tier-1 runs)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run               # noqa: E402


def _prefill(t0, **attrs):
    return {"name": "serving/prefill_compute", "t0": t0, "t1": t0 + 0.016,
            "attrs": dict(attrs, prompt=700, replica=0)}


# a lane's pass that admits four prompts: the first launched with nothing in
# flight, the three behind it each while the one ahead was unfetched
_ADMISSION = [_prefill(1.0, ahead=0), _prefill(1.016, ahead=1),
              _prefill(1.032, ahead=1), _prefill(1.048, ahead=1)]
_OTHERS = [{"name": "serving/decode_step", "t0": 1.1, "t1": 1.2,
            "attrs": {"early": True, "ahead": 1}},
           {"name": "decode/launch", "t0": 1.0, "t1": 1.001,
            "attrs": {"phase": "prefill", "ahead": 1}}]


@pytest.mark.parametrize("case,spans,want", [
    ("an_admission_of_four", _ADMISSION, 75.0),
    ("one_prompt_a_pass", [_prefill(1.0, ahead=0), _prefill(2.0, ahead=0)],
     0.0),
    ("two_admissions", _ADMISSION + [_prefill(2.0, ahead=0),
                                     _prefill(2.016, ahead=1)],
     100.0 * 4 / 6),
    # prefills outside the window and other spans do not count
    ("only_the_windows_prefills",
     _ADMISSION + _OTHERS + [_prefill(9.0, ahead=1)], 75.0),
    # the parent's program stamps no such attribute: no reading, and a span
    # without it beside spans with it is left out
    ("the_parents_spans", [_prefill(1.0), _prefill(1.016)], None),
    ("mixed", [_prefill(1.0), _prefill(2.0, ahead=1),
               _prefill(3.0, ahead=0)], 50.0),
    ("no_prefill", _OTHERS, None)])
def test_prefill_ahead_share_reader(case, spans, want):
    read = bench_run.load_reader("prefill_ahead_share")
    got = read(spans, None, {"window": (0.5, 5.0)})
    assert got == (want if want is None else pytest.approx(want, rel=1e-12))


def test_prefill_ahead_share_is_declared_last_for_the_seven_cells():
    manifest = bench_run.load_json(bench_run.MANIFEST)
    # (last but for the three readers PR 54, the one PR 55 and the two
    # PR 56 appended behind it)
    assert manifest["per_layer"][-8] == {
        "name": "prefill_ahead_share", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "scheduler",
        "moves": "tokens_per_s",
        # the cells whose admissions hold several prompts (PR 56's, under
        # Falcon's traffic, the seventh); an admission of OLMoE's, MiMo's
        # or MiniCPM's cell is one prompt
        "workloads": ["gpt2s_decode_saturated", "gpt2s_decode_deep",
                      "lfm2_decode_saturated", "pangu_decode_saturated",
                      "falconh1_decode_saturated",
                      "kexaone_decode_mixed_len",
                      "granite4hs_decode_saturated"]}
    assert os.path.exists(os.path.join(bench_run.LAYERS_DIR,
                                       "prefill_ahead_share.py"))
    # each of them reports what it moves, and the layer is one the manifest
    # already names
    e2e, = [m for m in manifest["end_to_end"] if m["name"] == "tokens_per_s"]
    assert set(manifest["per_layer"][-8]["workloads"]) < set(e2e["workloads"])
    assert "scheduler" in {m["layer"] for m in manifest["per_layer"][:-7]}


@pytest.mark.parametrize("cell,listed", [
    ("falconh1_decode_saturated", True), ("gpt2s_decode_deep", True),
    ("granite4hs_decode_saturated", True),
    ("olmoe_decode_saturated", False), ("resnet50_feed_b256", False)])
def test_the_harness_finds_the_reader_in_the_cells_that_list_it(cell, listed):
    manifest = bench_run.load_json(bench_run.MANIFEST)
    per_layer = bench_run.resolve_cell(manifest, cell)[4]
    assert ("prefill_ahead_share" in [m["name"] for m in per_layer]) == listed
