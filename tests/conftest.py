"""Global test config: hermetic 8-device CPU mesh (no TPU required).

Mirrors the reference's doctrine that all tests run without real
accelerators (reference tests use fake worker fixtures, no GPU —
SURVEY.md §4): we force the JAX CPU backend with 8 virtual devices so every
mesh/sharding path (tp/dp/sp/ep, multi-host placement logic) is exercised on
any machine.

``JAX_PLATFORMS=cpu`` in the environment is enough to keep JAX on the CPU
on this installation; the ``jax.config`` line below makes a bare
``pytest tests/`` hermetic as well.

Every in-process engine and every engine subprocess an e2e test starts
compiles the same tiny programs, so the session turns on the one
persistent compile cache (gpustack_tpu/utils/compile_cache.py); child
processes call the same helper and arrive at the same directory.
"""

import os
import sys

import pytest

# XLA reads this at backend init; conftest runs before any test imports jax.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Compile time is most of this suite and run time almost none of it (the
# models are two layers of 64): skip XLA's expensive optimization passes,
# here and in every engine subprocess a test starts.
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")

# hermetic: a hub lookup fails at once instead of retrying a network
# that is not there (read when huggingface_hub is first imported)
os.environ.setdefault("HF_HUB_OFFLINE", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gpustack_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()

# Clusters of different xdist workers must not probe one band of engine
# ports (gpustack_tpu/testing/ports.py): every Config an in-process
# server or a child process loads reads this. Assigned, not defaulted:
# an xdist worker inherits what the controller, which loads this file
# first, put there.
from gpustack_tpu.testing.ports import (  # noqa: E402
    coordinator_port_base,
    engine_port_base,
)

os.environ["GPUSTACK_TPU_ENGINE_PORT_BASE"] = str(engine_port_base())


@pytest.fixture
def own_coordinator_band(monkeypatch):
    """The scheduler of an in-process server hands multi-host replicas
    coordinator ports no other test process's scheduler hands out."""
    from gpustack_tpu.scheduler import scheduler

    monkeypatch.setattr(
        scheduler, "COORDINATOR_PORT_BASE", coordinator_port_base()
    )


# ---------------------------------------------------------------------------
# Test tiers (reference keeps pytest markers, pytest.ini:1-3; our split):
# directory => marker, so `make test-fast` gives a <2min signal while the
# full suite stays the merge gate.
# ---------------------------------------------------------------------------
_TIER_BY_DIR = {
    "e2e": "e2e",
    "engine": "engine",
    "models": "engine",
    "ops": "engine",
    "parallel": "engine",
    "benchmark": "engine",
}


def pytest_collection_modifyitems(config, items):
    tests_root = os.path.dirname(os.path.abspath(__file__))
    for item in items:
        try:
            rel = item.path.relative_to(tests_root)
            sub = rel.parts[0] if len(rel.parts) > 1 else ""
        except ValueError:
            sub = ""
        item.add_marker(
            getattr(pytest.mark, _TIER_BY_DIR.get(sub, "fast"))
        )
