"""Port bands of test clusters (gpustack_tpu/testing/ports.py): no two
test processes, and no two worker hosts of one, share a port."""

import itertools
import os

import pytest

from gpustack_tpu.config import Config
from gpustack_tpu.testing.ports import (
    COORDINATOR_PAIRS,
    ENGINE_BAND,
    HOSTS_PER_PROCESS,
    coordinator_port_base,
    engine_port_base,
)

# without xdist, and four times the driver's six workers
PROCESSES = [""] + [f"gw{n}" for n in range(24)]


def test_engine_bands_of_processes_and_hosts_are_disjoint():
    bands = [
        range(b, b + ENGINE_BAND)
        for b in (
            engine_port_base(h, w)
            for w in PROCESSES
            for h in range(HOSTS_PER_PROCESS)
        )
    ]
    for a, b in itertools.combinations(bands, 2):
        assert not set(a) & set(b), (a, b)
    # the product's default is not among them, and is what it was
    assert Config().engine_port_base == 40000
    assert all(40000 not in b for b in bands)


def test_coordinator_bands_are_disjoint_and_under_the_ephemeral_range():
    bands = [
        range(b, b + 2 * COORDINATOR_PAIRS)
        for b in map(coordinator_port_base, PROCESSES)
    ]
    for a, b in itertools.combinations(bands, 2):
        assert not set(a) & set(b), (a, b)
    engines_end = max(
        engine_port_base(HOSTS_PER_PROCESS - 1, w) for w in PROCESSES
    ) + ENGINE_BAND
    assert all(engines_end <= b.start and b.stop <= 32768 for b in bands)


@pytest.mark.parametrize(
    "call",
    [
        lambda: engine_port_base(0, "master"),
        lambda: engine_port_base(HOSTS_PER_PROCESS, "gw0"),
        lambda: engine_port_base(0, "gw24"),
        lambda: coordinator_port_base("gw200"),
    ],
    ids=["not-a-worker", "host-out-of-range", "engine-band-exhausted",
         "coordinator-band-exhausted"],
)
def test_a_band_that_does_not_exist_is_refused(call):
    with pytest.raises(ValueError):
        call()


def test_this_process_loads_its_own_band():
    """tests/conftest.py set the variable; a Config loaded here, and in
    any child process, starts its engines in this process's band."""
    want = engine_port_base(0, os.environ.get("PYTEST_XDIST_WORKER", ""))
    assert Config.load({}).engine_port_base == want
