"""The sampler alone, on the chip: ``engine/sampling.py sample`` with its
candidates found by ``lax.top_k`` over the whole row beside the chunked
selection (``top_candidates``), at the cells' shapes and at widths
between them.

    python hack/sample_bench.py [--shapes 32x151936,16x20480] [--ops N]

The whole of ``sample`` is timed, not the selection cut out of it: what
XLA makes of a ``top_k`` depends on what reads its result (alone it is a
``TopK`` custom call; in ``sample``, where the top-``TOPLP`` are sliced
from the top-``CAND``, it is a sort of the whole row: PERF.md section 6,
PR 36). Each line: device microseconds a call of either form (five calls
under the profiler, the trace's ``XLA Ops`` summed), its largest
operations, and the largest difference between the two forms in each of
``sample``'s outputs (bf16-rounded logits, so equal ones abound).
It is where ``CHUNKED_MIN_CHUNKS`` comes from. One JSON line a
measurement on stdout and in ``chiprun_out/sample_bench.jsonl``. Nothing
a cell runs imports this file.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import shutil
import sys
import tempfile
from unittest import mock

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from gpustack_tpu.engine import sampling  # noqa: E402
from perfbench import trace_reduce  # noqa: E402

# (slots, vocabulary): the three Qwen3 decode programs and their
# first-token program, the A.X-K1 slice, and widths round the crossover
SHAPES = (
    (32, 151936), (12, 151936), (1, 151936), (16, 20480),
    (16, 8192), (16, 12288), (16, 16384), (16, 32768), (16, 65536),
)
CALLS = 5
OUTPUTS = ("tokens", "token_logprob", "top_ids", "top_logprobs")


def form(min_chunks: int):
    """``sample`` with the crossover forced: 0 is always chunked, a
    huge one always the whole row."""
    def fn(*args):
        with mock.patch.object(sampling, "CHUNKED_MIN_CHUNKS", min_chunks):
            return sampling.sample(*args)

    return jax.jit(fn)


def inputs(B: int, V: int):
    """Half the rows greedy, half seeded at temperature 1, each with 64
    biased tokens as the benchmark's requests have; bf16-rounded logits."""
    logits = jax.random.normal(
        jax.random.key(B * V), (B, V), jnp.float32
    ).astype(jnp.bfloat16).astype(jnp.float32) * 4
    state = dataclasses.replace(
        sampling.SamplingState.create(B),
        temperature=(jnp.arange(B) % 2).astype(jnp.float32),
        seed=jnp.arange(B, dtype=jnp.uint32),
        seeded=jnp.ones((B,), jnp.bool_),
        bias_ids=jax.random.randint(
            jax.random.key(B), (B, sampling.MAX_BIAS), 0, V, jnp.int32
        ),
        bias_vals=jnp.full((B, sampling.MAX_BIAS), 3.0, jnp.float32),
    )
    return logits, state, jax.random.key(36), jnp.arange(B, dtype=jnp.int32)


def profiled(fn, args, k: int):
    """One call's outputs, device microseconds a call, and its ``k``
    largest operations."""
    out = jax.block_until_ready(fn(*args))
    trace_dir = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(CALLS):
                last = fn(*args)
            jax.block_until_ready(last)
        planes = trace_reduce.read_xplane(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    device = next(
        lines for name, lines in planes.items()
        if name.startswith("/device:TPU:0")
    )
    named = trace_reduce.by_name(
        e for e in device["XLA Ops"]
        if not trace_reduce.CONTAINER.match(e[0])
    )
    ops = sorted(
        (
            [trace_reduce.short_name(name, 72), v["total_ns"] / CALLS / 1e3]
            for name, v in named.items()
        ),
        key=lambda op: -op[1],
    )
    return out, sum(us for _, us in ops), ops[:k]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="")
    ap.add_argument("--ops", type=int, default=6)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="no chip, no times: only how far the two forms agree",
    )
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(json.dumps({"ok": False, "device": dev.device_kind,
                          "why": "not a chip"}))
        return 3
    shapes = [
        tuple(int(d) for d in s.split("x"))
        for s in args.shapes.split(",") if s
    ] or list(SHAPES)
    out_path = ROOT / "chiprun_out/sample_bench.jsonl"
    out_path.parent.mkdir(exist_ok=True)

    whole, chunked = form(1 << 30), form(0)
    for B, V in shapes:
        call = inputs(B, V)
        rec = {
            "device": dev.device_kind, "slots": B, "vocab": V,
            "chunks": -(-V // sampling.LANES),
            "chunked_by_default": bool(sampling.candidate_chunks(V)),
        }
        if args.rehearse:
            want, got = whole(*call), chunked(*call)
        else:
            want, rec["whole_us"], rec["whole_ops_us"] = profiled(
                whole, call, args.ops
            )
            got, rec["chunked_us"], rec["chunked_ops_us"] = profiled(
                chunked, call, args.ops
            )
        # largest difference an output of ``sample``: the tokens and the
        # ids are the same or the selection is wrong; the log-probs may
        # differ in the last place where XLA sums the row's logsumexp in
        # another order beside another selection (one row, on the chip)
        rec["largest_difference"] = {
            name: float(np.max(np.abs(
                np.asarray(w, np.float64) - np.asarray(g, np.float64)
            )))
            for name, w, g in zip(OUTPUTS, want, got)
        }
        line = json.dumps(rec)
        print(line, flush=True)
        with out_path.open("a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
