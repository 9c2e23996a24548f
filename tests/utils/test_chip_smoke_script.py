"""chip_smoke.py off the chip: it has no CPU mode. Without a TPU chip it
fails in its first phases with ``"ok": false`` and a non-zero exit code,
inside a few seconds, and leaves no process behind."""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def test_chip_smoke_without_a_chip_fails_fast(tmp_path):
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, SCRIPT, "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert "TPU chip" in last["error"]
    assert time.time() - t0 < 60
    # the server it started is gone
    pids = subprocess.run(
        ["pgrep", "-f", str(tmp_path)], capture_output=True, text=True
    ).stdout.split()
    assert pids == []


def test_chip_smoke_never_imports_jax():
    """A parent that touched JAX would hold the chip its engine needs."""
    with open(SCRIPT) as f:
        src = f.read()
    code = src.split('"""', 2)[2]   # everything after the module docstring
    assert "import jax" not in code and "from jax" not in code
    assert "gpustack_tpu import" not in code and "import gpustack_tpu" not in code


def test_seeded_text_is_exact_and_repeatable():
    sys.path.insert(0, REPO)
    import chip_smoke

    a = chip_smoke.seeded_text(0, 1300)
    assert len(a) == 1300 and a == chip_smoke.seeded_text(0, 1300)
    assert a != chip_smoke.seeded_text(1, 1300)
    assert a.isascii()
