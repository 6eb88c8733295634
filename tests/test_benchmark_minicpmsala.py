"""Tier-1's way to the fast tests of the `minicpm_sala_9b` configuration and
its cell (benchmark/tests/test_minicpmsala_cell.py), in the manner of
tests/test_benchmark_kexaone.py: `pytest tests/` does not collect
benchmark/tests/.  The cell's whole rehearsal (`slow`, and in need of
benchmark/conftest.py's four virtual devices) stays where it is."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests.test_minicpmsala_cell import *   # noqa: E402,F401,F403

del test_minicpmsala_cell_rehearsal                    # noqa: F821


_the_cell_as_pr48_left_it = test_the_cell_is_the_issues          # noqa: F821


def test_the_cell_is_the_issues(manifest):                     # noqa: F811
    """benchmark/tests/ holds PR 48's eight readers to be the manifest's
    LAST entries, and only a `benchmark` PR may edit that file: behind them
    stands the one reader PR 49 appended, and the rest is as it was."""
    assert [m["name"] for m in manifest["per_layer"][-1:]] == [
        "sparse_tiles_per_grid_step"]
    _the_cell_as_pr48_left_it(dict(
        manifest, per_layer=manifest["per_layer"][:-1]))
