"""How far the engine's own programs are from the float32 reference, in
nats, and the run's end if that is too far.

The engine is stopped and the chip is free when the readers run. This one
starts ``perfbench/reference_check.py`` as a child on the chip: the
configuration's tree as the engine builds it (seed 0, int8), the
engine's ``ModelRunner`` at the deployment's slots and context, two
prompts drawn from the run's ``--seed`` in each of the buckets 4,096 and
8,192 through the prefill program and four steps of the decode program
over the latent cache with all four slots live, against
``perfbench/reference/``'s full forward in float32 at the published
widths, which sends each token to the experts the program sent it to
(``reference_check.py`` says what is compared with what, and why). The
number is the **largest** error of any prefill position and decode step;
over the configuration's ``reference_logit_tol`` (``deployment.json``),
or with a selection that is not the reference's over the program's own
scores, or router scores further than ``reference_score_tol`` from the
reference's, the run fails here and prints no last line: a program that
disagrees with the reference has no result (``reference_check.judge``).
``perfbench/check_noise/`` holds what a sound program reads and what
each of seven faults reads, with the verdict of the same function.

Nothing to read, and no child: a configuration without a reference
(``deployment.json`` has no ``reference`` entry), and any run whose
engine was not on a TPU (the CPU rehearsal and the test suite)."""

import json
import os
import subprocess
import sys
import time

from perfbench.cluster import BenchFailure
from perfbench.reference_check import judge

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHILD_LIMIT_S = 600.0


def run_seed(argv):
    """The run's ``--seed`` (``run.py``'s command line is the contract the
    driver calls it by; ``ctx`` does not carry it): the same seed draws
    the same prompts, so a reading can be made again. 0 without one."""
    for i, a in enumerate(argv):
        if a == "--seed" and i + 1 < len(argv):
            return int(argv[i + 1]) % (2**31)
        if a.startswith("--seed="):
            return int(a.split("=", 1)[1]) % (2**31)
    return 0


def read(ctx):
    config_dir = ctx["spec"]["local_path"]
    with open(os.path.join(config_dir, "deployment.json")) as f:
        deployment = json.load(f)
    on_chip = all(
        (h.get("device") or {}).get("platform") == "tpu"
        for h in ctx.get("healths") or [{}]
    )
    if "reference" not in deployment or not on_chip:
        return None
    seed = run_seed(sys.argv)
    out = os.path.join(
        ROOT, "chiprun_out", "perfbench", "reference_check",
        f"{deployment['name']}-s{seed}.json",
    )
    os.makedirs(os.path.dirname(out), exist_ok=True)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "reference_check.py"),
         "--config-dir", config_dir, "--seed", str(seed), "--out", out],
        cwd=ROOT, capture_output=True, text=True, timeout=CHILD_LIMIT_S,
    )
    if proc.returncode != 0:
        raise BenchFailure(
            f"reference check exited with {proc.returncode}: "
            f"{proc.stderr[-2000:]}"
        )
    with open(out) as f:
        got = json.load(f)
    print(json.dumps({
        "phase": "reference_check", "seed": seed,
        "seconds": round(time.time() - t0, 3), "child": got["seconds"],
        **{k: got[k] for k in (
            "readings", "err", "differs", "score_err", "rerun"
        )},
        "tolerances": {k: deployment[k] for k in (
            "reference_logit_tol", "reference_score_tol"
        )},
    }), flush=True)
    problems = judge(got, deployment)
    if problems:
        raise BenchFailure(
            "the engine's programs against the float32 reference: "
            + "; ".join(problems) + f" ({out})"
        )
    return got["err"]
