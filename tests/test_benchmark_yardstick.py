"""Tier-1's way to the benchmark's own tests: `pytest tests/` does not
collect benchmark/tests/, so the yardstick (manifest rules, traffic
generator, percentile and interval arithmetic, the trace reduction on the
recorded v5e traces, the idle-time readers, the GPT-2 reference against the
program) had no gate.  This file imports the FAST tests of benchmark/tests/
so that they run here under their own names; the whole-cell rehearsals
(`slow`, and in need of benchmark/conftest.py) stay where they are.

Left out by name: `test_resnet_reference_matches_the_program_loss_and_update`
takes ~30 s on this machine (two ResNet-50 first steps at tiny size), over
the 20 s a tier-1 test may take; `python -m pytest benchmark/tests` runs it.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests.test_benchmark import *        # noqa: E402,F401,F403
from benchmark.tests.test_idle_readers import *     # noqa: E402,F401,F403

del test_resnet_reference_matches_the_program_loss_and_update  # noqa: F821
del test_cell_rehearsal, test_deep_cell_rehearsal              # noqa: F821


# ---------------------------------------------------------------------------
# benchmark/layers/decode_kv_stream_share.py on a recorded span list (here,
# not in benchmark/tests/: PR 32 may add one file under benchmark/, the reader)
# ---------------------------------------------------------------------------

import pytest                                        # noqa: E402


def _fetch(t0, phase="step", **attrs):
    return {"name": "decode/fetch", "t0": t0, "t1": t0 + 0.01,
            "attrs": dict(attrs, phase=phase)}


# three dispatches of a lane of 32 slots, 12 layers, 8 blocks a row: a
# window of 8 trips, one of 7, and an admission round's single trip
_STREAMED = [_fetch(1.0, trips=8, kv_blocks_live=4300, kv_blocks_total=24576),
             _fetch(2.0, trips=7, kv_blocks_live=4100, kv_blocks_total=21504),
             _fetch(3.0, trips=1, kv_blocks_live=3072, kv_blocks_total=3072)]
_OTHERS = [_fetch(1.5, phase="prefill", d2h_bytes=4),
           _fetch(9.0, trips=8, kv_blocks_live=1, kv_blocks_total=24576),
           {"name": "decode/launch", "t0": 1.0, "t1": 1.1,
            "attrs": {"phase": "step", "trips": 8}}]


@pytest.mark.parametrize("case,spans,want", [
    # blocks over blocks, whatever the dispatches' trips
    ("streamed", _STREAMED,
     100.0 * (4300 + 4100 + 3072) / (24576 + 21504 + 3072)),
    # a program that stamps neither attribute streams whole rows: 100,
    # with `trips` (PR 30's program) and without (a step a dispatch)
    ("whole_rows", [_fetch(1.0, trips=8), _fetch(2.0, trips=7)], 100.0),
    ("no_trips", [_fetch(1.0), _fetch(2.0)], 100.0),
    # a fetch without them weighs its trips: 8 trips at 25%, 8 at 100%
    ("mixed", [_fetch(1.0, trips=8, kv_blocks_live=25, kv_blocks_total=100),
               _fetch(2.0, trips=8)], 62.5),
    # other phases, other spans and fetches outside the window do not count
    ("only_step_fetches_of_the_window", _STREAMED + _OTHERS,
     100.0 * (4300 + 4100 + 3072) / (24576 + 21504 + 3072)),
    ("no_step_fetch", _OTHERS, None)])
def test_decode_kv_stream_share_reader(case, spans, want):
    read = bench_run.load_reader("decode_kv_stream_share")
    got = read(spans, None, {"window": (0.5, 5.0)})
    assert got == (want if want is None else pytest.approx(want, rel=1e-12))


def test_decode_kv_stream_share_is_declared_for_the_decode_cells():
    manifest = bench_run.load_json(bench_run.MANIFEST)
    entry, = [m for m in manifest["per_layer"]
              if m["name"] == "decode_kv_stream_share"]
    assert entry == {
        "name": "decode_kv_stream_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "tokens_per_s",
        "workloads": ["gpt2s_decode_saturated", "gpt2s_decode_deep",
                      "olmoe_decode_saturated", "lfm2_decode_saturated",
                      # PR 35's cell, whose latent kernel shares the stream's
                      # rule and counter (`kv_last_block`, `_kv_stream`)
                      "pangu_decode_saturated",
                      # PR 42's cell: its K/V rows alone are counted, its
                      # scanned state is no stream of rows
                      "falconh1_decode_saturated",
                      # PR 44's cell: its window layers' calls over their
                      # rings are counted beside the full layer's
                      "kexaone_decode_mixed_len",
                      # PR 48's cell: the blocks its sparse kernel STAGES
                      # (the selected ones, a K/V head) of every block of
                      # the slots' rows: the selection, not the length
                      "minicpmsala_longdoc_mixed",
                      # PR 51's cell: a kind's block counts at ITS rows'
                      # widths (a ring's weighs 2 beside a full table's 1)
                      "mimov2flash_reasoning_decode",
                      # PR 56's cell: its ONE attention layer's rows are
                      # counted, nine layers of ten keep none
                      "granite4hs_decode_saturated"]}
    # appended, not inserted: only PR 35's five readers, PR 38's one,
    # PR 39's nine, PR 42's six, PR 44's five, PR 45's one, PR 48's
    # eight, PR 49's one, PR 50's one, PR 51's three, PR 53's one,
    # PR 54's three, PR 55's one and PR 56's two stand behind it
    assert manifest["per_layer"].index(entry) == len(
        manifest["per_layer"]) - 49


# ---------------------------------------------------------------------------
# benchmark/layers/decode_early_launch_share.py on a recorded span list
# (PR 38: the one file it adds under benchmark/ is the reader)
# ---------------------------------------------------------------------------

def _step(t0, **attrs):
    return {"name": "serving/decode_step", "t0": t0, "t1": t0 + 0.02,
            "attrs": dict(attrs, trips=7, tokens=224)}


# a wave of a full lane: the dispatch after the admissions, three launched
# ahead of the delivery before them, and the one-trip dispatch of a lane
# with a slot free
_WAVE = [_step(1.0, early=False), _step(1.1, early=True),
         _step(1.2, early=True), _step(1.3, early=True),
         _step(1.4, early=False)]


@pytest.mark.parametrize("case,spans,want", [
    ("a_wave", _WAVE, 60.0),
    ("never", [_step(1.0, early=False), _step(2.0, early=False)], 0.0),
    ("always", [_step(1.0, early=True)], 100.0),
    # dispatches outside the window and other spans do not count
    ("only_the_windows_dispatches",
     _WAVE + [_step(9.0, early=True), _fetch(1.0, trips=7, early=True),
              {"name": "serving/emit", "t0": 1.1, "t1": 1.11,
               "attrs": {"early": True}}], 60.0),
    # the parent's program stamps no such attribute: no reading, and a span
    # without it beside spans with it is left out
    ("the_parents_spans", [_step(1.0), _step(2.0)], None),
    ("mixed", [_step(1.0), _step(2.0, early=True),
               _step(3.0, early=False)], 50.0),
    ("no_dispatch", _OTHERS, None)])
def test_decode_early_launch_share_reader(case, spans, want):
    read = bench_run.load_reader("decode_early_launch_share")
    got = read(spans, None, {"window": (0.5, 5.0)})
    assert got == (want if want is None else pytest.approx(want, rel=1e-12))


def test_decode_early_launch_share_is_declared_for_the_decode_cells():
    manifest = bench_run.load_json(bench_run.MANIFEST)
    # PR 39's nine readers, PR 42's six, PR 44's five, PR 45's one,
    # PR 48's eight, PR 49's one, PR 50's one, PR 51's three, PR 53's
    # one, PR 54's three, PR 55's one and PR 56's two stand behind it
    assert manifest["per_layer"][-43] == {
        "name": "decode_early_launch_share", "unit": "%",
        "better": "higher", "source": "program_span", "layer": "scheduler",
        "moves": "tokens_per_s",
        "workloads": ["gpt2s_decode_saturated", "gpt2s_decode_deep",
                      "olmoe_decode_saturated", "lfm2_decode_saturated",
                      "pangu_decode_saturated",
                      "falconh1_decode_saturated",
                      "kexaone_decode_mixed_len",
                      # PR 48's cell: a dispatch launched ahead of the
                      # delivery before it, as in every lane
                      "minicpmsala_longdoc_mixed",
                      "mimov2flash_reasoning_decode",
                      "granite4hs_decode_saturated"]}
    # the cells that report it are those that report what it moves
    e2e, = [m for m in manifest["end_to_end"] if m["name"] == "tokens_per_s"]
    assert manifest["per_layer"][-43]["workloads"] == e2e["workloads"]
