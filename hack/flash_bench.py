"""The flash prefill kernel alone, on the chip: the old tiling (one query
head and 128 x 128 keys a grid point, kept in
``tests/ops/test_flash_attention.py`` as the oracle) beside the new, at
the shapes the benchmark's cells run and at a continuation.

    python hack/flash_bench.py [--sweep] [--reps N]

Each line: ms a call (the ``pallas_call`` alone on head-major operands,
``reps`` calls chained inside one program so the host is out of it), the
call's share of its floor (``perfbench/roofline.py``'s, or
``perfbench/roofline_mla.py``'s where keys and values differ in width) as
counted and as issued (a key of 192 is two passes of the 128-deep matrix
unit: 256), the grid's points, and the count of output elements that
differ from the old kernel's (expected 0). ``--sweep`` times every pair of
block sizes and every inner shape (rows of a matmul, sub-blocks unrolled:
two constants of the kernel's module, which the sweep sets while it
traces), not only what the shapes choose; at a group of one the rows of a
matmul are swept at every pair of the larger blocks too. At a latent's
shapes (keys wider than values) two more lines, ``latent`` and
``flash_with_relayouts`` (:func:`latent_lines`): the latent's own prefill
call on operands as the projections make them, beside the flash call as a
layer ran it until PR 62, from the same operands. One JSON line a
measurement on stdout and in ``chiprun_out/flash_bench.jsonl``.
Nothing a cell runs imports this file.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import sys
import time
from unittest import mock

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from gpustack_tpu.ops import flash_attention as fa  # noqa: E402
from perfbench import roofline, roofline_mla  # noqa: E402

# (name, T, S, offset, q heads, kv heads, key width, value width)
SHAPES = [
    ("8b-2048", 2048, 2048, 0, 32, 8, 128, 128),
    ("8b-1024", 1024, 1024, 0, 32, 8, 128, 128),
    ("moe-2048", 2048, 2048, 0, 32, 4, 128, 128),
    ("moe-1024", 1024, 1024, 0, 32, 4, 128, 128),
    ("8b-chunk", 512, 2048, 1536, 32, 8, 128, 128),
    ("moe-chunk", 512, 2048, 1536, 32, 4, 128, 128),
    ("g7-2048", 2048, 2048, 0, 28, 4, 128, 128),    # Qwen2.5-7B: seven a group
    # a group of one: A.X-K1's decompressed latent (keys 192, values 128)
    # at the long-document cell's two buckets, Olmo-Hybrid's stored heads
    ("axk1-8192", 8192, 8192, 0, 64, 64, 192, 128),
    ("axk1-4096", 4096, 4096, 0, 64, 64, 192, 128),
    ("olmo-1024", 1024, 1024, 0, 32, 32, 128, 128),
    # a latent's widths at a size a rehearsal on the CPU gets through
    ("axk1-256", 256, 256, 0, 4, 4, 192, 128),
]
SWEEP_Q = (128, 256, 512, 1024)
SWEEP_K = (128, 256, 512, 1024, 2048)
SWEEP_ROWS = (512, 1024, 2048)
SWEEP_UNROLL = (1, 2, 4)
# where one head's rows are the whole of a matmul, its rows are swept at
# every pair of these too, not only at the chosen blocks
SWEEP_ONE_Q = (512, 1024, 2048)
SWEEP_ONE_K = (512, 1024, 2048)


def old_flash_call():
    spec = importlib.util.spec_from_file_location(
        "flash_oracle", ROOT / "tests/ops/test_flash_attention.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.old_flash_call


def floor_seconds(T, S, off, Hq, Hkv, d, dv, peaks) -> float:
    """``flash_prefill_call``'s floor; for a continuation the rows of the
    triangle below the offset are taken off its operations, and q and o
    count T rows against k's and v's S. Keys and values of different
    widths (from scratch, a key/value head a query head):
    ``roofline_mla.mla_prefill_call``'s."""
    if d != dv:
        call = roofline_mla.mla_prefill_call(T, Hq, d, dv)
        return roofline.least_seconds(
            call["flops"], call["bytes"], peaks
        )["seconds"]
    whole = roofline.flash_prefill_call(off + T, Hq, Hkv, d)
    below = roofline.flash_prefill_call(off, Hq, Hkv, d)
    bytes_ = 2.0 * d * (2 * T * Hq + 2 * S * Hkv)
    return roofline.least_seconds(
        whole["flops"] - below["flops"], bytes_, peaks
    )["seconds"]


def issued_over_counted(d: int, dv: int) -> float:
    """The matrix unit is 128 deep: a contraction over 192 is two passes,
    so QK^T issues 256 where the floor counts 192 (PV contracts over the
    128 keys of a sub-block whatever ``dv``)."""
    return (-(-d // 128) * 128 + dv) / (d + dv)


def timed(call, q, k, v, off, reps: int):
    """Seconds a call, ``reps`` calls chained in one program (each takes
    the last one's output as its q: same shape, and the time does not
    depend on the values), best of three; and one call's output."""
    chain = jax.jit(lambda q, k, v, off: lax.fori_loop(
        0, reps, lambda _, x: call(x, k, v, off), q
    ))
    out = jax.jit(call)(q, k, v, off)
    jax.block_until_ready(chain(q, k, v, off))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(q, k, v, off))
        best = min(best, (time.perf_counter() - t0) / reps)
    return best, out


def latent_lines(name, T, H, d, dv, floor, reps, rehearse, say):
    """``ops/mla_attention.py mla_prefill_attention`` on token-major
    operands (the query ``[1, T, H * d]`` not rotated, ``k_nope`` and
    ``v`` ``[1, T, H * dv]``, one rope key ``[1, T, d - dv]``) beside what
    it stands for: the query rotated outside, the keys built out to ``d``
    a head, and ``flash_attention_prefill`` with its transposes in and
    out. Each a program of its own, ``reps`` of them in flight behind one
    another (a call is 15 ms, a dispatch a tenth of one); the shares are
    of the attention's floor, which the second line's relayouts are no
    part of."""
    from gpustack_tpu.models import transformer as tf
    from gpustack_tpu.ops.mla_attention import mla_prefill_attention

    rope = d - dv
    ks = jax.random.split(jax.random.key(T), 4)
    draw = lambda k, *shape: jax.random.normal(
        k, shape, jnp.float32
    ).astype(jnp.bfloat16)
    q, k_nope = draw(ks[0], 1, T, H * d), draw(ks[1], 1, T, H * dv)
    k_pe, v = draw(ks[2], 1, T, rope), draw(ks[3], 1, T, H * dv)
    sin, cos = tf.rope_sin_cos(
        jnp.arange(T, dtype=jnp.int32)[None], tf._inv_freq(10000.0, rope)
    )
    scale = d ** -0.5

    def latent(q, k_nope, k_pe, v):
        return mla_prefill_attention(
            q, k_nope, k_pe, v, sin, cos, scale, interpret=rehearse
        )

    def flash_with_relayouts(q, k_nope, k_pe, v):
        q = q.reshape(1, T, H, d)
        q = jnp.concatenate([
            q[..., :dv], tf.apply_rope_interleaved(q[..., dv:], sin, cos)
        ], axis=-1)
        k = jnp.concatenate([
            k_nope.reshape(1, T, H, dv),
            jnp.broadcast_to(k_pe[:, :, None], (1, T, H, rope)),
        ], axis=-1)
        return fa.flash_attention_prefill(
            q, k, v.reshape(1, T, H, dv), scale, interpret=rehearse
        )

    outs = {}
    for which, call in (
        ("latent", latent), ("flash_with_relayouts", flash_with_relayouts)
    ):
        run = jax.jit(call)
        outs[which] = jax.block_until_ready(run(q, k_nope, k_pe, v))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps):
                out = run(q, k_nope, k_pe, v)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / reps)
        rec = {"shape": name, "kernel": which, "ms": best * 1e3,
               "roofline_pct": 100.0 * floor / best}
        if which != "latent":
            rec["largest_difference"] = float(jnp.max(jnp.abs(
                outs[which].astype(jnp.float32)
                - outs["latent"].astype(jnp.float32)
            )))
        say(rec)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--shapes", default="")
    ap.add_argument(
        "--rehearse", action="store_true",
        help="no chip: interpret mode, v5e's peaks; the counts mean "
        "something, the times nothing",
    )
    args = ap.parse_args()

    dev = jax.devices()[0]
    peaks = json.loads((ROOT / "perfbench/peaks.json").read_text()).get(
        "TPU v5 lite" if args.rehearse else dev.device_kind
    )
    if peaks is None:
        print(json.dumps({"ok": False, "device": dev.device_kind,
                          "why": "no peaks for this device: not a chip"}))
        return 3
    old_call = old_flash_call()
    out_path = ROOT / "chiprun_out/flash_bench.jsonl"
    out_path.parent.mkdir(exist_ok=True)
    want = set(filter(None, args.shapes.split(",")))

    def say(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        with out_path.open("a") as f:
            f.write(line + "\n")

    for name, T, S, off, Hq, Hkv, d, dv in SHAPES:
        if want and name not in want:
            continue
        G = Hq // Hkv
        ks = jax.random.split(jax.random.key(T + S + Hkv), 3)
        q = jax.random.normal(ks[0], (1, Hq, T, d), jnp.float32)
        k = jax.random.normal(ks[1], (1, Hkv, S, d), jnp.float32)
        v = jax.random.normal(ks[2], (1, Hkv, S, dv), jnp.float32)
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
        off_arr = jnp.full((1,), off, jnp.int32)
        floor = floor_seconds(T, S, off, Hq, Hkv, d, dv, peaks)
        issued = issued_over_counted(d, dv)
        kw = dict(scale=d ** -0.5, seq_k=S, interpret=args.rehearse)
        # a chained call takes its own output as its next q: narrower than
        # a key where the widths differ, so it is laid over q's first
        # columns (one more fused pass over q a call, 1 % of its time)
        widen = (
            lambda o, q0: q0.at[..., :dv].set(o[..., :dv])
        ) if d != dv else (lambda o, q0: o)

        def report(which, tiles, call, old_out=None):
            # the old grid has a point a query head, the new a point a group
            heads = Hq if which == "old" else Hkv
            rec = {"shape": name, "kernel": which, "tiles": tiles,
                   "grid_points": fa.grid_points(
                       fa.Tiles(*tiles), T, S, heads
                   )}
            try:
                sec, out = timed(
                    lambda x, k, v, o: widen(call(x, k, v, o), x),
                    q, k, v, off_arr, args.reps,
                )
                out = out[..., :dv]
            except Exception as e:   # a tile the chip's VMEM refuses
                rec["refused"] = str(e).splitlines()[0][:160]
                say(rec)
                return None
            rec["ms"] = sec * 1e3
            rec["roofline_pct"] = 100.0 * floor / sec
            rec["issued_pct"] = 100.0 * floor * issued / sec
            if old_out is not None:
                rec["differing"] = int(jnp.sum(
                    out.astype(jnp.float32) != old_out.astype(jnp.float32)
                ))
            say(rec)
            return out

        # the old kernel has one width: it takes the values padded with
        # zero columns to the keys' (a column of a product stands alone)
        old_out = report(
            "old", [128, 128, 128, 1],
            lambda q, k, v, o: old_call(
                q, k, jnp.pad(v, ((0, 0),) * 3 + ((0, d - dv),)), o, **kw
            ),
        )
        chosen = fa.choose_tiles(T, S, G, max(d, dv), q.dtype.itemsize)
        inner = (fa._MATMUL_ROWS, fa._UNROLL)
        # (block_q, block_k, rows of a matmul, sub-blocks unrolled)
        todo = [(chosen.block_q, chosen.block_k, *inner)]
        if args.sweep:
            # block sizes with the module's inner shape, then the inner
            # shape at the chosen blocks
            todo += [
                (bq, bk, *inner)
                for bq in SWEEP_Q if T % bq == 0
                for bk in SWEEP_K if S % bk == 0
            ] + [
                (chosen.block_q, chosen.block_k, rows, unroll)
                for rows in SWEEP_ROWS for unroll in SWEEP_UNROLL
            ] + [
                (bq, bk, rows, fa._UNROLL)
                for bq in SWEEP_ONE_Q if G == 1 and T % bq == 0
                for bk in SWEEP_ONE_K if S % bk == 0
                for rows in SWEEP_ROWS if rows <= bq
            ]
            todo = list(dict.fromkeys(todo))
        for bq, bk, rows, unroll in todo:
            def call(q, k, v, o):
                with mock.patch.multiple(
                    fa, _MATMUL_ROWS=rows, _UNROLL=unroll
                ):
                    return fa.flash_call(q, k, v, o, _blocks=(bq, bk), **kw)

            with mock.patch.multiple(fa, _MATMUL_ROWS=rows, _UNROLL=unroll):
                tiles = fa.tiles_of(bq, bk, G)
            report(
                "chosen" if tiles == chosen else "new", list(tiles), call,
                old_out,
            )
        if d != dv and Hq == Hkv and not off:
            latent_lines(
                name, T, Hq, d, dv, floor, args.reps, args.rehearse, say
            )
    print(json.dumps({"ok": True, "device": dev.device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
