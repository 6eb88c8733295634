"""`benchmark/layers/prefill_prompts_per_call.py` on span lists with and
without the attribute it reads, and its manifest entry (PR 55).  Fast: tier-1
runs it through `tests/test_benchmark_prefill_prompts.py`."""

import os

import pytest

from benchmark import run as bench_run


def _prefill(t0, **attrs):
    return {"name": "serving/prefill_compute", "t0": t0, "t1": t0 + 0.012,
            "attrs": dict(attrs, prompt=300, replica=0, ahead=0)}


def _call(t0, members):
    """The spans of ONE call of `members` prompts: one a request, tiling."""
    return [_prefill(t0 + 0.012 * j, prompts=members)
            for j in range(members)]


_OTHERS = [{"name": "serving/decode_step", "t0": 1.1, "t1": 1.2,
            "attrs": {"early": True, "prompts": 7}},
           {"name": "decode/launch", "t0": 1.0, "t1": 1.001,
            "attrs": {"phase": "prefill", "prompts": 4}}]


@pytest.mark.parametrize("case,spans,want", [
    # an admission of 8: a group of 4, a padded group of 3, one alone
    ("an_admission", _call(1.0, 4) + _call(1.1, 3) + _call(1.2, 1), 8 / 3.0),
    ("whole_groups", _call(1.0, 4) + _call(2.0, 4), 4.0),
    # a lane that groups nothing (a chunked stack): exactly one
    ("a_prompt_a_call", _call(1.0, 1) + _call(2.0, 1) + _call(3.0, 1), 1.0),
    # a call the window's edge cuts counts the part of it inside
    ("cut_by_the_window", _call(0.49, 4) + _call(2.0, 2), 5 / 1.75),
    # spans outside the window and other spans do not count
    ("only_the_windows_prefills",
     _call(1.0, 2) + _OTHERS + _call(9.0, 8), 2.0),
    # the parent's program stamps no such attribute: no reading
    ("the_parents_spans", [_prefill(1.0), _prefill(1.016)], None),
    ("no_prefill", _OTHERS, None)])
def test_prefill_prompts_per_call_reader(case, spans, want):
    read = bench_run.load_reader("prefill_prompts_per_call")
    got = read(spans, None, {"window": (0.5, 5.0)})
    assert got == (want if want is None else pytest.approx(want, rel=1e-12))


def test_prefill_prompts_per_call_is_declared_last_for_the_decode_cells():
    manifest = bench_run.load_json(bench_run.MANIFEST)
    e2e, = [m for m in manifest["end_to_end"] if m["name"] == "tokens_per_s"]
    assert manifest["per_layer"][-1] == {
        "name": "prefill_prompts_per_call", "unit": "prompts",
        "better": "higher", "source": "program_span", "layer": "scheduler",
        "moves": "tokens_per_s", "workloads": e2e["workloads"]}
    assert os.path.exists(os.path.join(bench_run.LAYERS_DIR,
                                       "prefill_prompts_per_call.py"))
    assert "scheduler" in {m["layer"] for m in manifest["per_layer"][:-1]}


@pytest.mark.parametrize("cell,listed", [
    ("falconh1_decode_saturated", True), ("minicpmsala_longdoc_mixed", True),
    ("olmoe_decode_saturated", True), ("resnet50_feed_b256", False),
    ("resnet50_dp4_loop_b1024", False)])
def test_the_harness_finds_the_reader_in_the_cells_that_list_it(cell, listed):
    manifest = bench_run.load_json(bench_run.MANIFEST)
    per_layer = bench_run.resolve_cell(manifest, cell)[4]
    assert ("prefill_prompts_per_call"
            in [m["name"] for m in per_layer]) == listed
