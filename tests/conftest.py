"""Test config: force a virtual 8-device CPU platform so multi-chip sharding
paths run without TPU hardware (SURVEY.md §4 fixtures note — the analogue of
the reference's fake multi-device contexts in op-handle tests).

The suite never needs a chip: it is held to the CPU here whatever
JAX_PLATFORMS says (tier-1 runs it with JAX_PLATFORMS=cpu, which jax honours),
so a host that has a TPU does not hand it to a test process. What only the
chip can show is chip_smoke.py's job; what only the chip's COMPILER can show
is tests/test_tpu_compile.py's, which needs no chip either.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("CPU_NUM", "8")
# The suite (and the tool children it spawns, which inherit this) compiles
# the same tiny programs again and again, each well under jax's 1 s floor
# for its persistent cache: keep them all, so a repeat is a disk hit.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: the marker gates subprocess-heavy
    # bench smokes that have their own standalone entry points
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 sweep")
