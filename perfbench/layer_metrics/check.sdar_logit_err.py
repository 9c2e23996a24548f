"""How far SDAR-MoE's own programs are from the float32 reference, in
nats, and the run's end if that is too far.

The engine is stopped and the chip is free when the readers run. This one
starts ``perfbench/reference_check_sdar.py`` as a child on the chip: the
configuration's tree as the engine builds it (seed 0, int8), the engine's
``ModelRunner`` at the deployment's slots and context, a prompt drawn
from the run's ``--seed`` in each of the two largest buckets the cell's
traffic reaches for ``P mod 4`` of 0 and of 2, through the prefill
program (the logits at every position), rows and tail into a slot, then
eleven passes of the block-pass program with every case live (denoise
passes at 0 to 3 decided positions, a commit, the whole next block, its
commit and the block after it), every pass's logits against
``perfbench/reference/sdar_moe.py``'s full forward in float32 over
``[decided tokens ..., the block with its mask ids]`` under the block
mask written out (``reference_check_sdar.py`` says what is compared with
what). The number is the **largest** error of any prefill position and
any row of any pass. Over the configuration's ``sdar_check.logit_tol``,
or with a kept key row further than ``rows_tol`` from the reference's,
router scores further than ``score_tol``, or a router computed in fewer
bits than float32 (``narrow_tol``), the run fails here and prints no
last line (``reference_check_sdar.judge``). ``perfbench/check_noise/``
holds what a sound program reads and what each of six faults reads, with
the verdict of the same function.

Nothing to read, and no child: a configuration without ``sdar_check`` in
its ``deployment.json`` (or without the file: the rehearsal's), and any
run whose engine was not on a TPU (the CPU rehearsal and the test
suite)."""

import json
import os
import subprocess
import sys
import time

from perfbench.cluster import BenchFailure
from perfbench.reference_check_sdar import LIMITS, judge

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHILD_LIMIT_S = 900.0


def run_seed(argv):
    """The run's ``--seed`` (``run.py``'s command line is the contract the
    driver calls it by; ``ctx`` does not carry it). 0 without one."""
    for i, a in enumerate(argv):
        if a == "--seed" and i + 1 < len(argv):
            return int(argv[i + 1])
        if a.startswith("--seed="):
            return int(a.split("=", 1)[1])
    return 0


def read(ctx):
    config_dir = ctx["spec"]["local_path"]
    try:
        with open(os.path.join(config_dir, "deployment.json")) as f:
            deployment = json.load(f)
    except FileNotFoundError:     # the rehearsal's tiny model has none
        return None
    on_chip = all(
        (h.get("device") or {}).get("platform") == "tpu"
        for h in ctx.get("healths") or [{}]
    )
    if "sdar_check" not in deployment or not on_chip:
        return None
    seed = run_seed(sys.argv)
    out = os.path.join(
        ROOT, "chiprun_out", "perfbench", "reference_check",
        f"{deployment['name']}-s{seed}.json",
    )
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "perfbench", "reference_check_sdar.py"),
         "--config-dir", config_dir, "--seed", str(seed), "--out", out],
        cwd=ROOT, capture_output=True, text=True, timeout=CHILD_LIMIT_S,
    )
    if proc.returncode != 0:
        raise BenchFailure(
            f"SDAR reference check exited with {proc.returncode}: "
            f"{proc.stderr[-2000:]}"
        )
    with open(out) as f:
        got = json.load(f)
    print(json.dumps({
        "phase": "reference_check", "seed": seed,
        "seconds": round(time.time() - t0, 3), "child": got["seconds"],
        **{key: got[key] for key, _, _ in LIMITS}, "rerun": got["rerun"],
        "tolerances": {
            tol: deployment["sdar_check"][tol] for _, tol, _ in LIMITS
        },
    }), flush=True)
    problems = judge(got, deployment)
    if problems:
        raise BenchFailure(
            "the engine's programs against the float32 reference: "
            + "; ".join(problems) + f" ({out})"
        )
    return got["err"]
