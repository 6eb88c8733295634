"""The benchmark's own tests run on the CPU: four virtual devices for the
four-chip cell's rehearsal, jax held to the CPU.  Run them with
`python -m pytest benchmark/tests -q` from the checkout's root (they are
outside tests/, so tier-1 does not collect them; see PERF.md section 7)."""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: whole-cell rehearsals, tens of seconds each")
