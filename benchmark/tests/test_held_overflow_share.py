"""`held_overflow_share` (benchmark/layers/held_overflow_share.py, PR 57):
the reader on synthetic spans, its manifest entry, and the four cells whose
member holds a run of the experts rehearsed on the CPU with the reader's
number on the line.  The cells' own rehearsals
(test_granite4hs_cell.py, test_kexaone_cell.py, test_pangu_cell.py,
test_mimov2flash_cell.py) take their metric names from the manifest, so they
hold the line to this reader too; the rehearsal here says what it reads."""

import pytest

from benchmark import run as bench_run
from benchmark.tests import rehearse
# every configuration's `rehearse.TINY` entry is made as its cell's test
# module is imported
from benchmark.tests import (test_falconh1_cell, test_granite4hs_cell,  # noqa: F401,E501
                             test_kexaone_cell, test_lfm2_cell,
                             test_mimov2flash_cell, test_minicpmsala_cell,
                             test_olmoe_cell, test_pangu_cell)

CELLS = ["granite4hs_decode_saturated", "pangu_decode_saturated",
         "kexaone_decode_mixed_len", "mimov2flash_reasoning_decode"]


def _fetch(t0, phase="step", **attrs):
    return {"name": "decode/fetch", "t0": t0, "t1": t0 + 0.01,
            "attrs": dict(attrs, phase=phase)}


def test_the_reader_counts_the_calls_that_ran_full_size():
    """Three dispatches of a stack with 10 routed layers (8, 8 and 1 trips:
    170 (layer, trip) calls), 3 + 0 + 1 of which overflowed; a prefill's
    fetch, a step fetch outside the window and a parent's spans (no
    counter) count for nothing; a stack that holds every expert, or a
    window without a counted fetch, gives no reading and does not raise."""
    read = bench_run.load_reader("held_overflow_share")
    spans = [_fetch(1.0, trips=8, moe_cap_overflows=3, moe_pairs_held=900),
             _fetch(2.0, trips=8, moe_cap_overflows=0, moe_pairs_held=880),
             _fetch(3.0, trips=1, moe_cap_overflows=1, moe_pairs_held=200),
             _fetch(3.5, phase="prefill", moe_cap_overflows=7),
             _fetch(9.0, trips=8, moe_cap_overflows=80),
             {"name": "decode/launch", "t0": 1.0, "t1": 1.1,
              "attrs": {"phase": "step", "trips": 8,
                        "moe_cap_overflows": 5}}]
    run = {"window": (0.5, 5.0),
           "meta": {"n_layers": 10, "experts_held": [0, 18]}}
    assert read(spans, None, run) == pytest.approx(100.0 * 4 / 170)
    # leading dense layers are not routed: 4 of 5 layers x 17 trips
    dense = dict(run, meta={"n_layers": 5, "n_dense_layers": 1,
                            "experts_held": [48, 8]})
    assert read(spans, None, dense) == pytest.approx(100.0 * 4 / 68)
    quiet = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                            if k != "moe_cap_overflows"}) for s in spans]
    assert read(quiet, None, run) is None
    assert read(spans, None, dict(run, meta={"n_layers": 10})) is None
    assert read(spans, None, dict(run, window=(20.0, 30.0))) is None
    calm = [_fetch(1.0, trips=8, moe_cap_overflows=0)]
    assert read(calm, None, run) == 0.0


def test_the_manifest_lists_the_reader_where_a_member_holds_experts():
    manifest = bench_run.load_json(bench_run.MANIFEST)
    by = {m["name"]: m for m in manifest["per_layer"]}
    mine = by["held_overflow_share"]
    assert mine == {"name": "held_overflow_share", "unit": "%",
                    "better": "lower", "source": "program_counter",
                    "layer": "kernels", "moves": "tokens_per_s",
                    "workloads": CELLS}
    assert mine["workloads"] == by["held_pairs_per_expert"]["workloads"]
    configs = {c["name"]: c for c in manifest["configs"]}
    for w in manifest["workloads"]:
        held = bench_run.load_json(configs[w["config"]]["file"]).get(
            "model", {}).get("experts_held")
        assert bool(held) == (w["name"] in CELLS), w["name"]


@pytest.mark.slow
@pytest.mark.parametrize("cell", CELLS)
def test_a_held_cell_reports_no_overflow_on_the_cpu(cell, monkeypatch):
    """A traced rehearsal of each held cell: `held_overflow_share` is on the
    line beside `held_pairs_per_expert`, and a seeded router over a tiny
    stack reads 0 or close to it (the call is exact either way)."""
    from benchmark import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    rc, last, lines = rehearse.rehearse(cell, 1, seconds=5.0)
    assert rc == 0, lines[-5:]
    assert last["correct"] is True and last["failed"] == 0
    assert "held_pairs_per_expert" in last["metrics"]
    assert 0.0 <= last["metrics"]["held_overflow_share"]["value"] <= 100.0
