#!/usr/bin/env python3
"""Solar-Open2's own programs against the float32 reference, on the chip.

    python perfbench/reference_check_kda.py --config-dir perfbench/configs/<name> \\
        --seed <n> --out <file.json> [--fault <name>,...]

Run by the reader ``layer_metrics/check.kda_logit_err.py`` after the
cell's cluster has stopped (this process takes the chip), and by hand for
the table in ``perfbench/check_noise/``. ``reference_check_linear.py`` is
Olmo-Hybrid's (one decay a head, no router) and
``reference_check_hybrid.py`` Nemotron's (it imports that reference by
name); this is the same comparison for a stack of KDA and gated
attention layers with routed experts in every layer
(``deployment.json``'s ``kda_check``).

What is compared with what. The configuration's parameter tree is built
as the engine builds it (``load_or_init_params``: seed 0, the
deployment's quantisation) and the engine's ``ModelRunner`` is made with
the deployment's ``max_slots`` and ``max_seq_len``: its prefill, insert
and decode programs are the served ones, at the served sizes. For
``prompts`` prompts drawn from ``--seed`` in each of ``buckets`` (the two
largest the cell's traffic reaches; a prompt is the bucket less a
quarter of it (at most 256) less ``steps`` tokens long, so every prefill
is **padded**):

- the **prefill program**'s logits at the last prompt position, all of
  the vocabulary slice;
- the prompt's rows **and the state the prefill ended in** (each KDA
  layer's matrix state and the rows before its convolutions) go into a
  slot (``insert``), every case, and the **decode program** makes
  ``steps`` greedy steps with every one of those slots live: the state
  moves through ``kda_state_update``, the experts through the touched
  kernel, attention through the GQA decode kernel. A slot and step it
  returns the 20 highest log-probabilities and their ids;
- the slot's state after the last step;
- the same prompts once more through the same programs with the routing
  as one more output (``prefill(routing=True)``,
  ``decode_step(routing=True)``), since a router that takes 8 of 320
  turns on a rounding: the reference sends each token where the program
  sent it. ``rerun`` says that the two runs are one computation;
- ``perfbench/reference/solar_open2.py``: one full forward in float32 at
  the highest matmul precision over the prompt and the tokens the engine
  chose, the recurrence **one position at a time**, no padding, no
  cache, the same share of the experts.

Readings (``judge`` holds each to its limit in ``kda_check``):

- ``err``, nats (``logit_tol``): the largest, over every case's prefill
  position (log-softmax over the whole slice) and every decode step (the
  program's 20 log-probabilities against the reference's log-softmax at
  the same ids);
- ``state_err`` (``state_tol``): the largest, over cases, KDA layers and
  heads, of ``|program's state - reference's| / |reference's|`` (a
  head's ``[128, 128]``) after the last step: what the logits show only
  through the layers that follow;
- ``state_narrow`` (``narrow_tol``): the largest share, of any layer, of
  that state's numbers that bf16 holds exactly. A state kept in bf16
  drifts by less than the bf16 activations that feed it move a float32
  one, so no error shows it; what it is kept in does: 1.0 against about
  2^-16;
- ``score_err`` (``score_tol``): the largest difference of a router
  score (0-1), any token, layer and expert;
- ``rerun.tokens_differ``: 0.

``--fault`` makes the *reference* compute one thing wrongly
(``reference/solar_open2.py FAULTS``): what a program with that fault
would read. Never passed by a run. Anything but a TPU ends at once with
code 3 (``--any-platform`` for the tests' small configuration).
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


def judge(got: dict, deployment: dict) -> list:
    """What of a comparison's readings is outside the configuration's
    limits (``kda_check``): a list of sentences, empty for a sound
    program. The reader raises on it and the fault table records it."""
    limits = deployment["kda_check"]
    problems = []
    for key, tol, what in (
        ("err", "logit_tol", "the programs' logits are {:.4f} nats from "
         "the float32 reference's"),
        ("state_err", "state_tol", "the recurrent state after the last "
         "decode step is {:.4f} (relative) from the reference's"),
        ("state_narrow", "narrow_tol", "{:.4f} of the recurrent state's "
         "numbers are held exactly by bf16: it is not kept in float32"),
        ("score_err", "score_tol", "the router's scores are {:.4f} from "
         "the reference's"),
    ):
        # not a number (a state that blew up) is over any limit; the
        # kept tables write it as null, which strict JSON can hold
        value = float("nan") if got[key] is None else got[key]
        if not value <= float(limits[tol]):
            problems.append(
                what.format(value) + f", over the tolerance {limits[tol]}"
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
        sys.stderr.write(f"reference_check_kda: on {platform}, not a TPU\n")
        return 3

    from gpustack_tpu.engine.runner import ModelRunner
    from gpustack_tpu.engine.weights import load_or_init_params
    from gpustack_tpu.models.config import load_hf_config
    from gpustack_tpu.ops.delta_rule import state_heads
    from perfbench.reference import solar_open2 as reference

    with open(os.path.join(args.config_dir, "deployment.json")) as f:
        deployment = json.load(f)
    with open(os.path.join(args.config_dir, "config.json")) as f:
        hf = json.load(f)
    spec, check = deployment["model"], deployment["kda_check"]
    steps, buckets = int(check["steps"]), [int(b) for b in check["buckets"]]
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
    if len(buckets) * int(check["prompts"]) > runner.max_slots:
        raise SystemExit("more prompts than the deployment has slots")
    rng = np.random.default_rng(args.seed % (2**32))
    cases = []
    for bucket in buckets:
        n = bucket - min(256, bucket // 4) - steps
        for _ in range(int(check["prompts"])):
            # ids of the byte tokenizer's printable range, as the traffic's
            prompt = rng.integers(33, 127, size=n).tolist()
            cases.append({
                "bucket": bucket, "n": n, "tokens": prompt,
                "padded": prompt + [0] * (bucket - n),
            })
    key = jax.random.key(args.seed % (2**31))
    heads = int(hf["linear_attn_config"]["num_heads"])

    def programs(routing):
        """Every case through the prefill program and into its slot, rows
        and state, then ``steps`` greedy steps of the decode program."""
        kw = {"routing": True} if routing else {}
        state = runner.new_state()
        out = []
        for slot, c in enumerate(cases):
            last, k, v, mixer, *route = runner.prefill(
                c["padded"], c["n"], **kw
            )
            last = np.asarray(last, np.float32)
            first = c.get("first", int(np.argmax(last)))
            state = runner.insert(
                state, k, v, slot, c["n"], first, 0.0, 0, 1.0, mixer=mixer
            )
            out.append({
                "prefill": last, "first": first, "sampled": [],
                "top_ids": [], "top_lps": [],
                "route": [tuple(r[:, 0, : c["n"]] for r in route[0])]
                if routing else None,
            })
            del k, v, mixer
        for _ in range(steps):
            state, (sampled, _lp, top_ids, top_lps, *route) = (
                runner.decode_step(state, key, **kw)
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
        for slot, o in enumerate(out):
            # a head at a time, as the reference keeps it
            o["state"] = state_heads(
                jnp.array(state.cache.ssm[:, slot]), heads
            )
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
            float(np.max(np.abs(
                np.asarray(a["top_lps"]) - np.asarray(b["top_lps"])
            ))) for a, b in zip(timed, routed)
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

    def compare(fault):
        by_case = []
        for c, o in zip(cases, timed):
            n = c["n"]
            seq = c["tokens"][: n + steps]   # the last token chosen is not fed
            ref, readings = reference.forward(
                params, hf, seq, list(range(n - 1, n + steps)),
                routing=c["route"], fault=fault, states=o["state"],
                # only the padding fault reads the bucket's padding
                pads=(n, c["bucket"] - n)
                if fault == "state_after_bucket" else None,
            )
            ref = np.asarray(ref)
            by_case.append({
                "bucket": c["bucket"], "n": n,
                "prefill": float(np.max(np.abs(
                    log_softmax(o["prefill"]) - log_softmax(ref[0])
                ))),
                "decode": [
                    float(np.max(np.abs(
                        o["top_lps"][i]
                        - log_softmax(ref[i + 1])[o["top_ids"][i]]
                    ))) for i in range(steps)
                ],
                **readings,
            })
        got = {
            "cases": by_case,
            "err": max(
                max(c["prefill"], *c["decode"]) for c in by_case
            ),
            **{
                key: max(c[key] for c in by_case)
                for key in ("state_err", "state_narrow", "score_err")
            },
            "rerun": rerun,
        }
        got["problems"] = judge(got, deployment)
        return got

    faults = args.fault.split(",")
    by_fault = {fault: compare(fault) for fault in faults}
    t_ref = time.time()
    result = {
        "config": deployment["name"], "seed": args.seed, "fault": faults[0],
        "platform": platform, "device_kind": jax.devices()[0].device_kind,
        "unit": "nats", "buckets": buckets, "steps": steps,
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
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f)
    keys = ("err", "state_err", "state_narrow", "score_err", "problems")
    print(json.dumps({
        **{k: result[k] for k in keys}, "rerun": rerun,
        "seconds": result["seconds"],
    }))
    for fault in faults[1:]:
        print(json.dumps(
            {"fault": fault, **{k: by_fault[fault][k] for k in keys}}
        ))
    return 0


if __name__ == "__main__":
    sys.exit(main())
