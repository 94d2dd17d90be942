"""Test configuration.

Tests run on a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8``), standing in for the
reference's testcontainers-based multi-process broker tests (SURVEY.md §4).

The suite is CPU-by-contract: ``JAX_PLATFORMS=cpu`` (set here before the
first jax import, as the driver's command also does) keeps every test off
any accelerator, and ``XLA_FLAGS`` must be extended before that import too.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# TokenCounter(gpt2) must never reach for the network: look in the local hub
# cache only, then take the vendored stand-in (filters/token_counter.py).
os.environ.setdefault("HF_HUB_OFFLINE", "1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# Production CPU configuration (CLI --backend cpu): x64 on, so sort2 takes
# its packed-int64 path — the suite validates exactly what runs.
# test_pallas_sort pins the x64-off two-operand fallback's agreement
# separately (the config real-TPU lax fallbacks use).
jax.config.update("jax_enable_x64", True)

# Keep every document on the DEVICE path in tests: the runtime's host-oracle
# tail routing (ops/pipeline.py process_chunk) would otherwise hand small
# end-of-stream groups to the host executor, quietly turning parts of the
# parity suites into host-vs-host comparisons.  test_packing's dedicated
# tail-routing tests re-enable it locally.
os.environ.setdefault("TEXTBLAST_HOST_TAILS", "off")

# Persistent compilation cache: the filter-pipeline graphs are large, and the
# suite re-jits them every session without this.
from textblaster_tpu.utils.compile_cache import enable_compilation_cache  # noqa: E402

enable_compilation_cache()


# Fault-injection hygiene: FAULTS is process-global, so an armed fault leaking
# out of one test would poison every later one.  Reset around each test; the
# tier-1 guard test (test_fault_injection.py) separately asserts the injector
# is inert in production paths.
import pytest  # noqa: E402

from textblaster_tpu.resilience.faults import FAULTS  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()
