"""Tier-1's way to `benchmark/tests/test_prefill_prompts_per_call.py` (PR 55):
`pytest tests/` does not collect benchmark/tests/ (see
`tests/test_benchmark_yardstick.py`)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests.test_prefill_prompts_per_call import *  # noqa: E402,F401,F403


def test_prefill_prompts_per_call_is_declared_last_for_the_decode_cells():  # noqa: F811
    """benchmark/tests/ holds PR 55's reader to be the manifest's LAST
    entry, and only a `benchmark` PR may edit that file: behind it stand
    the two readers PR 56 appended; the rest is as it was, and PR 56's
    cell, which reports `tokens_per_s`, is in its list."""
    manifest = bench_run.load_json(bench_run.MANIFEST)          # noqa: F405
    e2e, = [m for m in manifest["end_to_end"] if m["name"] == "tokens_per_s"]
    assert [m["name"] for m in manifest["per_layer"][-3:-1]] == [
        "ssm_share_of_trip", "held_pairs_per_expert"]
    assert manifest["per_layer"][-4] == {
        "name": "prefill_prompts_per_call", "unit": "prompts",
        "better": "higher", "source": "program_span", "layer": "scheduler",
        "moves": "tokens_per_s", "workloads": e2e["workloads"]}
    assert os.path.exists(os.path.join(bench_run.LAYERS_DIR,    # noqa: F405
                                       "prefill_prompts_per_call.py"))
    assert "scheduler" in {m["layer"] for m in manifest["per_layer"][:-3]}
