"""Tier-1's way to the fast tests of the `granite_4_0_h_small` configuration
and its cell (benchmark/tests/test_granite4hs_cell.py), in the manner of
tests/test_benchmark_mimov2flash.py: `pytest tests/` does not collect
benchmark/tests/.  The cell's whole rehearsal (`slow`, and in need of
benchmark/conftest.py's four virtual devices) stays where it is."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests.test_granite4hs_cell import *  # noqa: E402,F401,F403

del test_granite4hs_cell_rehearsal                    # noqa: F821
