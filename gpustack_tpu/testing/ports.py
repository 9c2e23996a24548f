"""Port bands for clusters that tests start beside one another.

A worker picks an engine port by bind-probing its band
(``Config.engine_port_base``, 40000 in the product) and the engine binds
it seconds later; the scheduler hands a multi-host replica the lowest
free pair of its coordinator band. Two clusters on one machine that
share a band can therefore pick the same port. pytest-xdist runs test
files in several processes at once, so every such process, and every
worker host a test starts, takes bands of its own: pure functions of
the process's xdist id (``gw0``, ``gw1``, ...; empty without xdist),
read from ``PYTEST_XDIST_WORKER`` where the caller gives none.

All bands lie under the kernel's ephemeral range (32768 up), where the
tests' ``bind(0)`` ports and every connection's local port come from:
the product's coordinator band (41000 up) lies inside it, so under six
busy test processes some other test's socket can hold the very port a
leader probes (PR 33: in a whole run both multi-host tests found their
pair's first port in use, four seconds apart, by no process of theirs).
"""

from __future__ import annotations

import os
from typing import Optional

from gpustack_tpu.config import Config

ENGINE_BAND = Config.model_fields["engine_port_range"].default
HOSTS_PER_PROCESS = 4    # the widest cluster a test starts (4 hosts)
FIRST_ENGINE_PORT = 11000   # room for 24 xdist workers under the next band
COORDINATOR_PAIRS = 8    # coordinator + command channel, per process
FIRST_COORDINATOR_PORT = 31000
EPHEMERAL_FROM = 32768


def _index(xdist_worker: Optional[str]) -> int:
    """0 without xdist, n + 1 for ``gw<n>``."""
    if xdist_worker is None:
        xdist_worker = os.environ.get("PYTEST_XDIST_WORKER", "")
    if not xdist_worker:
        return 0
    if not xdist_worker.startswith("gw"):
        raise ValueError(f"not an xdist worker id: {xdist_worker!r}")
    return int(xdist_worker[2:]) + 1


def engine_port_base(
    host: int = 0, xdist_worker: Optional[str] = None
) -> int:
    """First engine port of worker host ``host`` of a cluster started by
    test process ``xdist_worker`` (this one if not given)."""
    if not 0 <= host < HOSTS_PER_PROCESS:
        raise ValueError(f"host {host} outside 0..{HOSTS_PER_PROCESS - 1}")
    base = FIRST_ENGINE_PORT + ENGINE_BAND * (
        HOSTS_PER_PROCESS * _index(xdist_worker) + host
    )
    if base + ENGINE_BAND > FIRST_COORDINATOR_PORT:
        raise ValueError(f"no engine band left from {base}")
    return base


def coordinator_port_base(xdist_worker: Optional[str] = None) -> int:
    """First coordinator port for clusters whose scheduler runs in test
    process ``xdist_worker`` (this one if not given)."""
    base = FIRST_COORDINATOR_PORT + 2 * COORDINATOR_PAIRS * _index(
        xdist_worker
    )
    if base + 2 * COORDINATOR_PAIRS > EPHEMERAL_FROM:
        raise ValueError(f"no coordinator band left from {base}")
    return base
