"""Tier-1's way to the fast tests of the `held_overflow_share` reader
(benchmark/tests/test_held_overflow_share.py), in the manner of
tests/test_benchmark_granite4hs.py: `pytest tests/` does not collect
benchmark/tests/.  The four cells' rehearsals (`slow`) stay where they
are."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests.test_held_overflow_share import (  # noqa: E402,F401
    test_the_manifest_lists_the_reader_where_a_member_holds_experts,
    test_the_reader_counts_the_calls_that_ran_full_size)
