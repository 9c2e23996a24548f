#!/usr/bin/env python3
"""Times one gated-delta-rule layer's recurrence alone on a chip, beside
what its shapes ask for (``perfbench/roofline_delta.py``): the chunked
form (``ops/delta_rule.py delta_chunk_scan``, plain einsums in float32)
at the buckets the benchmark's Olmo-Hybrid cell prefills, and the
one-step update kernel (``delta_state_update``) against its XLA form at
the deployment's slots with some of them live.

    python3 hack/delta_bench.py [--slots 12] [--live 4,8,12] [--buckets 256,512,1024]
    python3 hack/delta_bench.py --rehearse        # the CPU: agreement only

The chunked form is no kernel, so a trace cannot name its operations:
this is where its share of its roofline is measured (PERF.md section
5). A CPU run gives no time."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=12)
    ap.add_argument("--live", default="4,8,12")
    ap.add_argument("--buckets", default="256,512,1024")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from gpustack_tpu.ops.delta_rule import (
        CHUNK,
        delta_chunk_scan,
        delta_state_update,
        delta_step_xla,
    )
    from perfbench import roofline, roofline_delta

    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.rehearse:
        print(json.dumps({"ok": False, "why": "not a TPU"}))
        return 3
    H, Dk, Dv = (4, 6, 12) if args.rehearse else (30, 96, 192)
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    k = jax.random.split(jax.random.key(0), 8)

    def draw(*lead):
        key = jax.random.normal(k[1], (*lead, H, Dk))
        return (
            jax.random.normal(k[0], (*lead, H, Dk)) * Dk ** -0.5,
            key / jnp.linalg.norm(key, axis=-1, keepdims=True),
            jax.random.normal(k[2], (*lead, H, Dv)),
            -jnp.exp(jax.random.uniform(k[3], (*lead, H), minval=-7, maxval=0.5)),
            2.0 * jax.nn.sigmoid(jax.random.normal(k[4], (*lead, H))),
        )

    def seconds_a_round(step, carry, rounds):
        """``step`` (carry to carry) ``rounds`` times inside one program,
        so that the host's dispatch (0.3 ms a call on the chip's host:
        more than the update itself) is paid once; the second call is
        the timed one."""
        many = jax.jit(
            lambda c: lax.fori_loop(0, rounds, lambda _, c: step(c), c),
            donate_argnums=0,
        )
        carry = many(carry)
        jax.block_until_ready(carry)
        t0 = time.perf_counter()
        jax.block_until_ready(many(carry))
        return (time.perf_counter() - t0) / rounds

    for T in [int(b) for b in args.buckets.split(",")]:
        if args.rehearse:
            T //= 16
        prompt = draw(1, T)
        took = seconds_a_round(
            # each round goes on from the last one's state
            lambda h: delta_chunk_scan(*prompt, h)[1],
            jnp.zeros((1, H, Dk, Dv), jnp.float32), 2 if args.rehearse else 20,
        )
        call = roofline_delta.delta_scan_call(T, H, Dk, Dv, CHUNK)
        least = roofline.least_seconds(call["flops"], call["bytes"], peaks)
        line = {"scan_tokens": T, **call, "least_us": least["seconds"] * 1e6,
                "bound": least["bound"]}
        if on_chip:
            line.update(us=took * 1e6,
                        roofline_pct=100 * least["seconds"] / took)
        print(json.dumps(line), flush=True)

    B, L = (4, 2) if args.rehearse else (args.slots, args.layers)
    step = draw(B)
    kernel = jax.jit(
        lambda s, live: delta_state_update(
            s, jnp.int32(1), *step, live, interpret=not on_chip
        ), donate_argnums=0,
    )
    plain = jax.jit(
        lambda s: delta_step_xla(s, jnp.int32(1), *step), donate_argnums=0
    )
    for n_live in [int(n) for n in args.live.split(",")]:
        n_live = min(n_live, B)
        live = jnp.arange(B) < n_live
        state = jax.random.normal(k[5], (L, B, Dk, H * Dv))
        o_k, _ = kernel(state + 0, live)
        o_x, _ = plain(state + 0)
        agree = float(jnp.abs(
            jnp.where(live[:, None, None], o_k - o_x, 0.0)
        ).max())
        line = {"update_slots": B, "live": n_live, "o_diff": agree}
        if on_chip:
            took = {
                "kernel": seconds_a_round(
                    lambda s: delta_state_update(s, jnp.int32(1), *step, live)[1],
                    state + 0, 200,
                ),
                "xla": seconds_a_round(
                    lambda s: delta_step_xla(s, jnp.int32(1), *step)[1],
                    state + 0, 200,
                ),
            }
            call = roofline_delta.delta_update_call(n_live, H, Dk, Dv)
            least = call["bytes"] / peaks["hbm_bytes_per_s"]
            line.update(kernel_us=took["kernel"] * 1e6,
                        xla_us=took["xla"] * 1e6, least_us=least * 1e6,
                        roofline_pct=100 * least / took["kernel"])
        print(json.dumps(line), flush=True)
    print(json.dumps({"ok": True, "device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
