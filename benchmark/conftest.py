"""Tiny sizes of the traffic mixes added after benchmark/tests/rehearse.py
was written: its `TINY_TRAFFIC` table shrinks EVERY mix of the manifest
before a CPU rehearsal of any cell and knows only the mixes of its day, so a
mix added by a file alone (benchmark/README.md) has to be entered here, from
a file of its own, until a `benchmark` PR lets a mix carry its tiny size."""

import pytest


@pytest.fixture(autouse=True, scope="session")
def tiny_sizes_of_later_mixes():
    from benchmark.tests import rehearse
    rehearse.TINY_TRAFFIC.setdefault("decode_deep", lambda m: (
        m.update(requests=32),
        m["prompt_tokens"].update(min=64, max=100),
        m["output_tokens"].update(value=24)))
