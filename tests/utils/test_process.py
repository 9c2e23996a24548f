"""utils/process.py: liveness for the worker's orphan reapers."""

import os
import signal
import subprocess
import sys
import time

from gpustack_tpu.utils.process import pid_running, wait_exit_or_kill


def test_a_zombie_counts_as_exited():
    """A signalled child of ours stays in /proc as a zombie until we
    reap it; it holds nothing any more, so the reaper must not wait the
    whole deadline for it."""
    child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    try:
        assert pid_running(child.pid)
        os.kill(child.pid, signal.SIGTERM)
        t0 = time.monotonic()
        wait_exit_or_kill([child.pid], timeout=10.0)
        assert time.monotonic() - t0 < 5.0
        assert not pid_running(child.pid)
        assert os.path.exists(f"/proc/{child.pid}")   # zombie: still listed
    finally:
        child.kill()
        child.wait()
    assert not pid_running(child.pid)


def test_what_ignores_sigterm_is_killed_at_the_deadline():
    child = subprocess.Popen(
        [sys.executable, "-c",
         "import signal, time; signal.signal(signal.SIGTERM, signal.SIG_IGN);"
         "print('ready', flush=True); time.sleep(60)"],
        stdout=subprocess.PIPE,
    )
    try:
        assert child.stdout.readline().strip() == b"ready"
        os.kill(child.pid, signal.SIGTERM)
        wait_exit_or_kill([child.pid], timeout=0.5)
        assert child.wait(10) == -signal.SIGKILL
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
