#!/usr/bin/env python3
"""SDAR-MoE's own programs against the float32 reference, on the chip.

    python perfbench/reference_check_sdar.py --config-dir perfbench/configs/<name> \\
        --seed <n> --out <file.json> [--fault <name>,...]

Run by the reader ``layer_metrics/check.sdar_logit_err.py`` after the
cell's cluster has stopped (this process takes the chip), and by hand for
the table in ``perfbench/check_noise/``. ``reference_check_kda.py`` is
Solar-Open2's; this is the same comparison for a model **generated by
diffusion over blocks** (``deployment.json``'s ``sdar_check``): what a
step is differs, so what is compared does.

What is compared with what. The configuration's parameter tree is built
as the engine builds it (``load_or_init_params``: seed 0, the
deployment's quantisation) and the engine's ``ModelRunner`` is made with
the deployment's ``max_slots`` and ``max_seq_len``: its prefill, insert
and block-pass programs are the served ones, at the served sizes. A case
is a prompt drawn from ``--seed``, one in each of ``buckets`` (the two
largest the cell's traffic reaches) for each of ``tails`` (``P mod L``):
``bucket - 4 L + tail`` tokens, so its whole blocks are ``bucket - 4 L``
rows in the bucket's (padded) prefill and four blocks fit the bucket
behind them.

- the **prefill**: the served program (``runner.prefill``) gives the
  rows that go into the slot and the last row's logits; the logits at
  **every** position come from ``forward`` as that program calls it (the
  same attention and dispatch, ``logits_at`` left out, the routing as one
  more output); ``rerun.prefill`` says the two agree on the row both
  have;
- ``insert_block`` places the rows and the prompt's tail, every case in
  a slot of its own, and the **block-pass program** runs ``passes``
  passes with all of them live: the first block's denoise passes at
  ``tail`` to 3 decided positions, its commit, the whole of the next
  block and its commit, and the block after it. Once as the engine runs
  it (``denoise_step``), and once more with the pass's logits and
  routing as outputs (``probe=True``); ``rerun`` says the two are one
  computation (``tokens_differ`` 0);
- ``perfbench/reference/sdar_moe.py``: for the prefill one full forward
  in float32 at the highest matmul precision over the whole blocks, and
  for **every pass of every case** one full forward over ``[decided
  tokens ..., the block with its mask ids]`` read at the block's rows,
  under the block mask written out, no cache, no kernel, each token
  routed where the program routed it when it wrote the rows it kept (a
  router that takes 8 of 128 turns on a rounding). So a commit pass is
  checked by every later pass, which attends the rows it kept.

Readings (``judge`` holds each to its limit in ``sdar_check``):

- ``err``, nats (``logit_tol``): the largest difference of log-softmax
  over the whole vocabulary, any prefill position, any row of any pass;
- ``rows_err`` (``rows_tol``): the largest, over cases, layers and the
  rows of the blocks the passes **completed**, of ``|the key row the
  program kept - the reference's| / |the reference's|`` (a row is the
  four kv heads' 128 numbers, normed and rotated): what a commit pass
  left out, or a rotation counted from the block's start, shows whole
  in, and the logits only through the few rows among hundreds;
- ``score_err`` (``score_tol``): the largest difference of a router
  score (0-1), any token, layer and expert;
- ``score_narrow`` (``narrow_tol``): the largest share, of any layer, of
  the router's logits that bf16 holds exactly: about 2^-16 for the
  float32 the configuration states, 1 for a router computed in bf16,
  whose logits read like a sound program's;
- ``rerun.tokens_differ``: 0.

``--fault`` makes the *reference* compute one thing wrongly
(``reference/sdar_moe.py FAULTS``): what a program with that fault would
read. Never passed by a run. Anything but a TPU ends at once with code 3
(``--any-platform`` for the tests' small configuration).
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

LIMITS = (
    ("err", "logit_tol", "the programs' logits are {:.4f} nats from the "
     "float32 reference's"),
    ("rows_err", "rows_tol", "a key row the passes kept is {:.4f} "
     "(relative) from the reference's"),
    ("score_err", "score_tol", "the router's scores are {:.4f} from the "
     "reference's"),
    ("score_narrow", "narrow_tol", "{:.4f} of the router's logits are "
     "held exactly by bf16: it is not computed in float32"),
)


def judge(got: dict, deployment: dict) -> list:
    """What of a comparison's readings is outside the configuration's
    limits (``sdar_check``): a list of sentences, empty for a sound
    program. The reader raises on it and the fault table records it."""
    limits = deployment["sdar_check"]
    problems = []
    for key, tol, what in LIMITS:
        # not a number is over any limit; the kept tables write it as
        # null, which strict JSON can hold
        value = float("nan") if got[key] is None else got[key]
        if not value <= float(limits[tol]):
            problems.append(
                what.format(value) + f", over the tolerance {limits[tol]}"
            )
    if got["rerun"]["tokens_differ"]:
        problems.append(
            f"{got['rerun']['tokens_differ']} positions were decided "
            "otherwise when the pass ran again with its logits as an output"
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
        sys.stderr.write(f"reference_check_sdar: on {platform}, not a TPU\n")
        return 3

    from gpustack_tpu.engine.runner import ModelRunner
    from gpustack_tpu.engine.weights import load_or_init_params
    from gpustack_tpu.models.config import load_hf_config
    from gpustack_tpu.models.transformer import KVCache, forward
    from perfbench.reference import sdar_moe as reference

    with open(os.path.join(args.config_dir, "deployment.json")) as f:
        deployment = json.load(f)
    with open(os.path.join(args.config_dir, "config.json")) as f:
        hf = json.load(f)
    spec, check = deployment["model"], deployment["sdar_check"]
    n_passes = int(check["passes"])
    buckets = [int(b) for b in check["buckets"]]
    cfg = load_hf_config(args.config_dir)
    L, mask_id = cfg.diffusion_block, cfg.mask_token_id
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
    rng = np.random.default_rng(args.seed % (2**32))
    cases = []
    for bucket in buckets:
        for tail in check["tails"]:
            whole = bucket - 4 * L
            # ids of the byte tokenizer's printable range, as the traffic's
            prompt = rng.integers(33, 127, size=whole + int(tail)).tolist()
            cases.append({"bucket": bucket, "whole": whole, "prompt": prompt})
    if len(cases) > runner.max_slots:
        raise SystemExit("more cases than the deployment has slots")
    key = np.asarray([args.seed % (2**31), 1], np.uint32)

    def whole_prefill(bucket):
        """``forward`` as ``runner._prefill_impl`` calls it, every row's
        logits and the routing out."""
        def run(params, tokens):
            positions = jnp.arange(bucket, dtype=jnp.int32)[None, :]
            logits, _, routing = forward(
                params, cfg, tokens, positions, KVCache.create(cfg, 1, bucket),
                attn_impl=runner.attn_impl_for(bucket), mesh=runner.mesh,
                routing_out=True,
            )
            return logits[0], tuple(r[:, 0] for r in routing)

        run.__name__ = f"prefill_{bucket}_every_row"
        return jax.jit(run)

    every_row = {b: whole_prefill(b) for b in buckets}

    def inserted():
        """Every case through the served prefill program and into its
        slot; the last row's logits of each."""
        state, lasts = runner.new_state(), []
        for slot, c in enumerate(cases):
            padded = c["prompt"][: c["whole"]] + [0] * (
                c["bucket"] - c["whole"]
            )
            last, k, v = runner.prefill(padded, c["whole"])
            lasts.append(np.asarray(last, np.float32))
            state = runner.insert_block(
                state, k, v, slot, c["prompt"], 0.0, 0, 1.0
            )
            del k, v
        return state, lasts

    # the passes as the engine runs them
    state, lasts = inserted()
    timed = []
    for _ in range(n_passes):
        state, out = runner.denoise_step(state, key)
        timed.append([np.asarray(x) for x in out[:4]])
    del state
    t_timed = time.time()

    # the same once more, the logits and the routing out
    for c in cases:
        padded = c["prompt"][: c["whole"]] + [0] * (c["bucket"] - c["whole"])
        logits, routing = every_row[c["bucket"]](
            params, np.asarray(padded, np.int32)[None]
        )
        c["prefill"] = np.asarray(logits[: c["whole"]], np.float32)
        # of every position, the routing of the pass that wrote the rows
        # the program keeps of it: [layers, bucket, k], [.., experts]
        c["route"] = [np.array(r) for r in routing]
        c["route_prefill"] = c["route"]
        c["seq"] = list(c["prompt"][: c["whole"]])
        c["passes"] = []
    state, lasts_again = inserted()
    probed = []
    for _ in range(n_passes):
        tokens = np.asarray(state.block_tokens)
        decided = np.asarray(state.block_decided)
        state, out = runner.denoise_step(state, key, probe=True)
        out = [np.asarray(x) for x in out[:4]] + [
            np.asarray(out[7], np.float32),
            [np.asarray(r) for r in out[8]],
        ]
        probed.append(out[:4])
        for slot, c in enumerate(cases):
            start = len(c["seq"])
            route = [r.copy() for r in c["route"]]
            for r, mine in zip(route, out[5]):
                r[:, start: start + L] = mine[:, slot]
            c["passes"].append({
                "start": start, "tokens": tokens[slot].tolist(),
                "decided": decided[slot].tolist(),
                "logits": out[4][slot].copy(), "route": route,
            })
            if out[2][slot]:       # a commit: the rows and their routing
                c["route"] = route
                c["seq"] += [int(t) for t in tokens[slot]]
    for slot, c in enumerate(cases):
        # the key rows the passes kept, of the blocks they completed
        c["kept"] = np.asarray(
            state.cache.k[:, slot, c["whole"]: len(c["seq"])], np.float32
        )
    del state
    rerun = {
        "prefill": max(
            float(np.max(np.abs(a - b))) for a, b in zip(lasts, lasts_again)
        ),
        "prefill_row": max(
            float(np.max(np.abs(last - c["prefill"][-1])))
            for last, c in zip(lasts, cases)
        ),
        "logprob": max(
            float(np.max(np.abs(a[3] - b[3]))) for a, b in zip(timed, probed)
        ),
        "tokens_differ": int(sum(
            np.sum((a[0] != b[0]) | (a[1] != b[1]))
            for a, b in zip(timed, probed)
        )),
    }
    t_engine = time.time()

    def log_softmax(x):
        x = np.asarray(x, np.float64)
        top = x.max(axis=-1, keepdims=True)
        return x - (np.log(np.sum(np.exp(x - top), axis=-1, keepdims=True)) + top)

    def compare(fault):
        by_case = []
        for c in cases:
            whole, bucket = c["whole"], c["bucket"]

            def padded(ids):
                return list(ids) + [0] * (bucket - len(ids))

            ref, readings = reference.forward(
                params, hf, padded(c["prompt"][:whole]), L,
                routing=tuple(jnp.asarray(r) for r in c["route_prefill"]),
                fault=fault, valid=whole,
            )
            got = {
                "bucket": bucket, "prompt": len(c["prompt"]),
                "prefill": float(np.max(np.abs(
                    log_softmax(c["prefill"])
                    - log_softmax(np.asarray(ref)[:whole])
                ))),
                "passes": [], "score_err": readings["score_err"],
                "score_narrow": readings["score_narrow"], "rows_err": 0.0,
            }
            for at, p in enumerate(c["passes"]):
                start = p["start"]
                ids = reference.pass_ids(
                    c["seq"][:start], p["tokens"], p["decided"], mask_id,
                    len(c["prompt"]), fault,
                )
                last = at == len(c["passes"]) - 1
                ref, readings = reference.forward(
                    params, hf, padded(ids), L,
                    routing=tuple(jnp.asarray(r) for r in p["route"]),
                    fault=fault, valid=start + L, gen_from=whole,
                    kept_ids=padded(reference.stale_ids(
                        ids, whole, start, L, mask_id
                    )) if fault == "no_commit" else None,
                    keys=last,
                )
                got["passes"].append(float(np.max(np.abs(
                    log_softmax(p["logits"])
                    - log_softmax(np.asarray(ref)[start: start + L])
                ))))
                for name in ("score_err", "score_narrow"):
                    got[name] = max(got[name], readings[name])
                if last and c["kept"].shape[1]:
                    theirs = c["kept"].reshape(*c["kept"].shape[:2], -1)
                    ours = np.asarray(
                        readings["keys"][:, whole: whole + theirs.shape[1]]
                    ).reshape(theirs.shape)
                    got["rows_err"] = float(np.max(
                        np.linalg.norm(theirs - ours, axis=-1)
                        / np.linalg.norm(ours, axis=-1)
                    ))
            by_case.append(got)
        out = {
            "cases": by_case,
            "err": max(max(c["prefill"], *c["passes"]) for c in by_case),
            **{
                name: max(c[name] for c in by_case)
                for name in ("rows_err", "score_err", "score_narrow")
            },
            "rerun": rerun,
        }
        out["problems"] = judge(out, deployment)
        return out

    faults = args.fault.split(",")
    by_fault = {fault: compare(fault) for fault in faults}
    t_ref = time.time()
    result = {
        "config": deployment["name"], "seed": args.seed, "fault": faults[0],
        "platform": platform, "device_kind": jax.devices()[0].device_kind,
        "unit": "nats", "buckets": buckets, "passes": n_passes,
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
    keys = ("err", "rows_err", "score_err", "score_narrow", "problems")
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
