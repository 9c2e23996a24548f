"""Process liveness from /proc, shared by the worker's orphan reapers."""

from __future__ import annotations

import asyncio
import os
import signal
import time
from typing import Iterable


def pid_running(pid: int) -> bool:
    """True while ``pid`` still runs. A zombie has exited — it holds no
    chip, port or file any more — and only waits for its parent."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            stat = f.read()
    except OSError:
        return False
    return stat[stat.rindex(")") + 2:].split()[0] != "Z"


def wait_exit_or_kill(pids: Iterable[int], timeout: float = 10.0) -> None:
    """Wait for signalled processes to exit (engines must release their
    TPU devices before any respawn); SIGKILL what is left at the
    deadline."""
    deadline = time.monotonic() + timeout
    for pid in pids:
        while time.monotonic() < deadline and pid_running(pid):
            time.sleep(0.05)
        if pid_running(pid):
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass


def stop_signal_event() -> asyncio.Event:
    """An event that SIGTERM or SIGINT sets, for a ``run_forever`` that
    must stop its engine processes before it exits: they run in their
    own sessions, outlive a parent that just dies, and would hold the
    chips."""
    event = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, event.set)
    return event


async def signalled_before(event: asyncio.Event, work) -> bool:
    """Await ``work`` until it ends or ``event`` (a stop signal) is set.
    True when the signal came first: ``work`` is left running for the
    caller's graceful stop. Otherwise ``work``'s own outcome is raised
    or returned through."""
    work = asyncio.ensure_future(work)
    signal_wait = asyncio.ensure_future(event.wait())
    done, _ = await asyncio.wait(
        {work, signal_wait}, return_when=asyncio.FIRST_COMPLETED
    )
    if work in done:
        signal_wait.cancel()
        work.result()
        return False
    return True
