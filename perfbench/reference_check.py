#!/usr/bin/env python3
"""The engine's own programs against the float32 reference, on the chip.

    python perfbench/reference_check.py --config-dir perfbench/configs/<name> \\
        --seed <n> --out <file.json> [--fault <name>,...] [--prompts 2] [--steps 4]

Run by the reader ``layer_metrics/check.reference_logit_err.py`` after
the cell's cluster has stopped (this process takes the chip), and by hand
for the table in ``perfbench/check_noise/``.

What is compared with what. The configuration's parameter tree is built
as the engine builds it (``load_or_init_params``: seed 0, the
deployment's quantisation), and the engine's ``ModelRunner`` is made
with the deployment's ``max_slots`` and ``max_seq_len``: its prefill,
insert and decode programs are the served ones, at the served sizes.
For ``--prompts`` prompts drawn from ``--seed`` in each of the two
largest prefill buckets (4,096 and 8,192 for a context of 8,192; a
prompt is the bucket less 256 less ``--steps`` tokens long):

- the **prefill program**'s logits at the last prompt position, all of
  the vocabulary slice;
- the prompt's rows go into a slot of the decode state (``insert``), all
  the prompts of both buckets, and the **decode program** makes
  ``--steps`` greedy steps over the latent cache with every one of those
  slots live. It returns, a slot and step, the 20 highest
  log-probabilities and their ids (what the API's ``top_logprobs`` is
  made from; the program has no other output of its logits);
- the same prompts go once more through the same two programs **with one
  more output** (``ModelRunner.prefill(routing=True)``,
  ``decode_step(routing=True)``): every layer's chosen experts and router
  logits, every token. A router that takes 8 of 192 turns on a rounding:
  against a reference that routes for itself, one prompt position in
  four read 0.3-1.6 nats where the others read 0.10 (my chip runs,
  PR 35), and a limit above such readings holds nothing. That the two
  runs of a program are one computation is itself read: ``rerun`` is the
  largest difference between their logits (prefill) and their top
  log-probabilities (decode), and a decode step that chose another token
  the second time fails the check;
- ``perfbench/reference/axk1.py`` computes, in float32 at the highest
  matmul precision, one full forward over the prompt and the tokens the
  engine chose, layer by layer with the weights dequantised from the
  same tree, **sending each token to the experts the program sent it
  to** (``forward_following``), and gives the logits at the same
  positions.

Four readings (``judge``):

- ``err``, nats: the **largest**, over every prompt's prefill position
  (the largest difference of the log-softmax over the whole slice) and
  every decode step (the largest difference between the program's 20
  log-probabilities and the reference's log-softmax at the same ids).
  Held to ``reference_logit_tol``.
- ``differs``: in how many (token, layer) pairs the reference's
  selection rule, applied to the **program's own scores**, takes another
  set of experts than the program took. The same exact arithmetic on the
  same float32 numbers: held to 0. This is what holds the program's
  selection, since the logits follow its choices.
- ``score_err``: the largest difference between the program's router
  scores (sigmoid, in 0-1) and the reference's, any token, layer and
  expert: what reached the router. Held to ``reference_score_tol``.
- ``rerun``, above: reported, and a token chosen otherwise fails.

Beside them, a prompt: ``turned_pct``, the share of (token, layer) pairs
in which the reference, left to its own scores, would have sent the
token to other **held** experts than the program did, and
``turned_at_want``, in how many layers that holds of each compared
position itself. They bind nothing; with ``--unfollowed`` (by hand) the
reference's error without following is written beside each position's,
which is how the table in ``check_noise/`` shows that the positions that
read 0.3-1.6 are the turned ones.

``--fault`` makes the *reference* compute one thing wrongly
(``reference/axk1.py``, ``FAULTS``): what a program with that fault
would read against a sound reference; several, separated by commas, are
computed from one pass of the engine's programs over the first
``--fault-prompts`` prompts of each bucket, and ``--out`` then holds
``by_fault``, each with the verdict ``judge`` gives it. Never passed by
a run.

Anything but a TPU ends at once with code 3: a CPU gives no number that
may stand under this name.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

MARGIN = 256          # a prompt ends this far under its bucket, less --steps
BLOCK = 256           # the reference's attention, query rows at a time


def judge(got: dict, deployment: dict) -> list:
    """What of a comparison's readings (``--out``'s ``err``, ``differs``,
    ``score_err``, ``rerun``) is outside the configuration's limits: a
    list of sentences, empty for a sound program. The reader raises on
    it, and the fault table records it, so a fault is shown to fail where
    a run would."""
    problems = []
    tol = float(deployment["reference_logit_tol"])
    if not got["err"] <= tol:
        problems.append(
            f"the programs' logits are {got['err']:.4f} nats from the "
            f"float32 reference's, over the tolerance {tol}"
        )
    if got["differs"]:
        problems.append(
            f"the reference's selection over the program's own scores takes "
            f"other experts than the program in {got['differs']} (token, "
            "layer) pairs"
        )
    tol = float(deployment["reference_score_tol"])
    if not got["score_err"] <= tol:
        problems.append(
            f"the router's scores are {got['score_err']:.4f} from the "
            f"reference's, over the tolerance {tol}"
        )
    if got["rerun"]["tokens_differ"]:
        problems.append(
            f"{got['rerun']['tokens_differ']} decode steps chose another "
            "token when the program ran again with its routing as an output"
        )
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config-dir", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--fault", default="")
    ap.add_argument("--prompts", type=int, default=2)
    ap.add_argument("--fault-prompts", type=int, default=2)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--unfollowed", action="store_true",
                    help="also what the reference reads left to its own "
                    "routing, a position: the table in check_noise/")
    ap.add_argument("--any-platform", action="store_true",
                    help="for the tests: a small configuration on the CPU")
    args = ap.parse_args(argv)
    t_start = time.time()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from gpustack_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.any_platform:
        sys.stderr.write(f"reference_check: on {platform}, not a TPU\n")
        return 3

    from gpustack_tpu.engine.runner import ModelRunner
    from gpustack_tpu.engine.weights import load_or_init_params
    from gpustack_tpu.models.config import load_hf_config
    from perfbench.reference import axk1 as reference

    with open(os.path.join(args.config_dir, "deployment.json")) as f:
        deployment = json.load(f)
    with open(os.path.join(args.config_dir, "config.json")) as f:
        hf = json.load(f)
    spec = deployment["model"]
    cfg = load_hf_config(args.config_dir)
    params = load_or_init_params(
        cfg, args.config_dir, seed=0,
        quantization=spec.get("quantization", ""),
    )
    jax.block_until_ready(params)
    t_tree = time.time()

    runner = ModelRunner(
        cfg, params, max_slots=int(spec["max_slots"]),
        max_seq_len=int(spec["max_seq_len"]),
    )
    params = runner.params
    buckets = runner.prefill_buckets[-2:]
    steps, margin = args.steps, min(MARGIN, buckets[0] // 4)
    block = min(BLOCK, margin)
    if len(buckets) * args.prompts > runner.max_slots:
        raise SystemExit("more prompts than the deployment has slots")
    rng = np.random.default_rng(args.seed)
    cases = []
    for bucket in buckets:
        n = bucket - margin - steps
        for i in range(args.prompts):
            # ids of the byte tokenizer's printable range, as the traffic's
            prompt = rng.integers(33, 127, size=n).tolist()
            cases.append({
                "bucket": bucket, "n": n, "faulted": i < args.fault_prompts,
                "padded": prompt + [0] * (bucket - n), "tokens": prompt,
            })
    key = jax.random.key(args.seed)

    def programs(routing):
        """Every case through the prefill program and into its slot, then
        ``steps`` greedy steps of the decode program; a case's prefill
        logits, its steps' sampled tokens and top log-probabilities, and
        with ``routing`` ``(chosen, router logits)`` of its ``n + steps``
        positions, ``[L_moe, n + steps, ...]``."""
        state = runner.new_state()
        out = []
        for slot, c in enumerate(cases):
            last, k, v, *route = runner.prefill(
                c["padded"], c["n"], **({"routing": True} if routing else {})
            )
            last = np.asarray(last, np.float32)
            # the first token is the timed run's, so that both runs decode
            # the same sequence from the same rows
            first = c.get("first", int(np.argmax(last)))
            state = runner.insert(state, k, v, slot, c["n"], first, 0.0, 0, 1.0)
            out.append({
                "prefill": last, "first": first, "sampled": [],
                "top_ids": [], "top_lps": [],
                "route": [tuple(r[:, 0, : c["n"]] for r in route[0])]
                if routing else None,
            })
            del k, v
        for _ in range(steps):
            state, (sampled, _lp, top_ids, top_lps, *route) = (
                runner.decode_step(
                    state, key, **({"routing": True} if routing else {})
                )
            )
            sampled, top_ids, top_lps = (
                np.asarray(x) for x in (sampled, top_ids, top_lps)
            )
            for slot, o in enumerate(out):
                o["sampled"].append(int(sampled[slot]))
                o["top_ids"].append(top_ids[slot])
                o["top_lps"].append(top_lps[slot])
                if routing:
                    o["route"].append(tuple(r[:, slot] for r in route[0]))
        del state
        return out

    timed = programs(False)
    for c, o in zip(cases, timed):
        c["first"] = o["first"]
        c["tokens"] = c["tokens"] + [o["first"]] + o["sampled"]
    t_timed = time.time()
    routed = programs(True)
    rerun = {
        "prefill": max(
            float(np.max(np.abs(a["prefill"] - b["prefill"])))
            for a, b in zip(timed, routed)
        ),
        "decode": max(
            float(np.max(np.abs(np.asarray(a["top_lps"]) - np.asarray(b["top_lps"]))))
            for a, b in zip(timed, routed)
        ),
        "tokens_differ": sum(
            x != y for a, b in zip(timed, routed)
            for x, y in zip(a["sampled"], b["sampled"])
        ),
    }
    for c, o in zip(cases, routed):
        c["route"] = tuple(
            jnp.concatenate([r[i] for r in o["route"]], axis=1)
            for i in range(2)
        )
    del routed
    t_engine = time.time()

    def log_softmax(x):
        x = np.asarray(x, np.float64)
        return x - (np.log(np.sum(np.exp(x - x.max()))) + x.max())

    def errors(o, ref):
        """A case's prefill position and decode steps against the
        reference's logits at the same positions."""
        ref = np.asarray(ref)
        return {
            "prefill": float(np.max(np.abs(
                log_softmax(o["prefill"]) - log_softmax(ref[0])
            ))),
            "decode": [
                float(np.max(np.abs(
                    o["top_lps"][i] - log_softmax(ref[i + 1])[o["top_ids"][i]]
                )))
                for i in range(steps)
            ],
        }

    def compare(fault):
        by_bucket = {}
        differs, score_err = 0, 0.0
        for c, o in zip(cases, timed):
            if fault and not c["faulted"]:
                continue
            n = c["n"]
            seq = c["tokens"][: n + steps]   # the last token chosen is not fed
            want = list(range(n - 1, n + steps))
            how = dict(block=block, capacity=len(seq) // 8, fault=fault)
            ref, agreement = reference.forward_following(
                params, hf, seq, want, c["route"], **how
            )
            differs += sum(agreement["differs"])
            score_err = max(score_err, *agreement["score_err"])
            case = {
                **errors(o, ref), **agreement,
                "turned_pct": 100.0 * sum(agreement["turned"])
                / (len(agreement["turned"]) * len(seq)),
            }
            if args.unfollowed and not fault:
                case["unfollowed"] = errors(
                    o, reference.forward(params, hf, seq, want, **how)
                )
            by_bucket.setdefault(str(c["bucket"]), []).append(case)
        readings = {
            b: {
                "prefill": max(p["prefill"] for p in ps),
                "decode": max(e for p in ps for e in p["decode"]),
            }
            for b, ps in by_bucket.items()
        }
        got = {
            "buckets": by_bucket, "readings": readings,
            "err": max(v for r in readings.values() for v in r.values()),
            "differs": differs, "score_err": score_err, "rerun": rerun,
        }
        got["problems"] = judge(got, deployment)
        return got

    faults = args.fault.split(",")
    by_fault = {fault: compare(fault) for fault in faults}
    t_ref = time.time()
    result = {
        "config": deployment["name"], "seed": args.seed, "fault": faults[0],
        "platform": platform, "device_kind": jax.devices()[0].device_kind,
        "unit": "nats", "prompts": args.prompts, "steps": steps,
        **by_fault[faults[0]],
        "seconds": {
            "tree": round(t_tree - t_start, 3),
            "timed_programs": round(t_timed - t_tree, 3),
            "programs_again": round(t_engine - t_timed, 3),
            "reference": round(t_ref - t_engine, 3),
            "all": round(t_ref - t_start, 3),
        },
    }
    if len(faults) > 1:
        result["by_fault"] = by_fault
    with open(args.out, "w") as f:
        json.dump(result, f)
    print(json.dumps({k: result[k] for k in (
        "readings", "err", "differs", "score_err", "rerun", "problems",
        "seconds",
    )}))
    for fault in faults[1:]:
        print(json.dumps({"fault": fault, **{k: by_fault[fault][k] for k in (
            "readings", "err", "differs", "score_err", "problems",
        )}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
