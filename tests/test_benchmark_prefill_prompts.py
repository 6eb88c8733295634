"""Tier-1's way to `benchmark/tests/test_prefill_prompts_per_call.py` (PR 55):
`pytest tests/` does not collect benchmark/tests/ (see
`tests/test_benchmark_yardstick.py`)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests.test_prefill_prompts_per_call import *  # noqa: E402,F401,F403
