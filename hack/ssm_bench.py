#!/usr/bin/env python3
"""Times one state-space layer's selective scan alone on a chip, beside
what its shapes ask for (``perfbench/roofline_ssm.py``): the chunked scan
(``ops/ssm.py ssm_chunk_scan``, plain einsums) at the buckets the
benchmark's hybrid cell prefills, and the one-step update kernel
(``ssm_state_update``) against its XLA form at the deployment's slots
with some of them live, beside the same call with a body that only
copies its block (the bytes' own time through the kernel's pipeline:
what says how far fetch, body and write-back overlap).

    python3 hack/ssm_bench.py [--slots 32] [--live 8,16,32] [--buckets 256,512,1024]
    python3 hack/ssm_bench.py --rehearse        # the CPU: agreement only

The scan is no kernel, so a trace cannot name its operations
(``perfbench/layer_metrics/kernel.ssm_prefill_roofline.py``): this is
where its share of its roofline is measured (PERF.md section 5). Both
are timed **on the device**: a ``lax.fori_loop`` of calls inside one
program (the update a layer after another over the donated state), at
two trip counts; a call is the difference over the trips between (a
dispatch from the host is 0.3 ms and hid every update's own time until
PR 55). A CPU run gives no time."""

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
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--live", default="8,16,32")
    ap.add_argument("--buckets", default="256,512,1024")
    ap.add_argument("--layers", type=int, default=23)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from jax import lax

    from gpustack_tpu.ops import ssm
    from perfbench import roofline, roofline_ssm

    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.rehearse:
        print(json.dumps({"ok": False, "why": "not a TPU"}))
        return 3
    H, P, N, G, Q = (4, 8, 16, 2, 8) if args.rehearse else (64, 64, 128, 8, 128)
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    k = jax.random.split(jax.random.key(0), 8)

    def device_us(step, carry, trips):
        """What one ``step(i, carry) -> carry`` takes on the device: a
        ``lax.fori_loop`` of them inside one program over the donated
        carry, the best of five runs at each of two trip counts, their
        difference over the trips between (the host's dispatch and the
        loop's start fall out)."""
        best = []
        for n in trips:
            run = jax.jit(
                lambda c, n=n: lax.fori_loop(0, n, step, c), donate_argnums=0
            )
            c = run(jax.tree.map(lambda a: a + 0, carry))
            took = []
            for _ in range(5):
                jax.block_until_ready(c)
                t0 = time.perf_counter()
                c = run(c)
                jax.block_until_ready(c)
                took.append(time.perf_counter() - t0)
            best.append(min(took))
        return (best[1] - best[0]) / (trips[1] - trips[0]) * 1e6

    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    for T in [int(b) for b in args.buckets.split(",")]:
        if args.rehearse:
            T //= 16
        x = jax.random.normal(k[0], (1, T, H, P)).astype(jnp.bfloat16)
        dt = jax.nn.softplus(jax.random.normal(k[1], (1, T, H)))
        Bm = jax.random.normal(k[3], (1, T, G, N)).astype(jnp.bfloat16)
        Cm = jax.random.normal(k[4], (1, T, G, N)).astype(jnp.bfloat16)
        h0 = jnp.zeros((1, H, P, N), jnp.float32)

        def scan(i, carry):
            """A prefill after another, each from the state the last one
            left; ``y`` is kept alive by a sum."""
            h, kept = carry
            y, h = ssm.ssm_chunk_scan(x, dt, A, Bm, Cm, h, Q)
            return h, kept + y[0, 0, 0, 0]

        call = roofline_ssm.ssm_scan_call(T, H, P, N, G, Q)
        least = roofline.least_seconds(call["flops"], call["bytes"], peaks)
        line = {"scan_tokens": T, **call, "least_us": least["seconds"] * 1e6,
                "bound": least["bound"]}
        if on_chip:
            took = device_us(scan, (h0, jnp.float32(0)), (20, 120))
            line.update(us=took, roofline_pct=100e6 * least["seconds"] / took)
        else:
            jax.block_until_ready(jax.jit(scan)(0, (h0, jnp.float32(0))))
        print(json.dumps(line), flush=True)

    B, L = (4, 2) if args.rehearse else (args.slots, args.layers)
    x = jax.random.normal(k[0], (B, H, P)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, H)))
    Bm = jax.random.normal(k[3], (B, G, N)).astype(jnp.bfloat16)
    Cm = jax.random.normal(k[4], (B, G, N)).astype(jnp.bfloat16)

    def kernel(s, layer, live):
        return ssm.ssm_state_update(
            s, layer, x, dt, A, Bm, Cm, live, interpret=not on_chip
        )

    def plain(s, layer, live):
        return ssm.ssm_step_xla(s, layer, x, dt, A, Bm, Cm)

    def copies(*refs, **_):
        """The kernel's body, nothing but its block's copy (the state's
        two blocks and ``y`` are the last three references)."""
        h_ref, h_out_ref, y_ref = refs[-3:]
        h_out_ref[...] = h_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    def calls(step, live):
        """``step`` a layer after another over the stacked state."""
        def one(i, carry):
            s, kept = carry
            y, s = step(s, i % L, live)
            return s, kept + y[0, 0, 0]
        return one

    body = ssm._update_kernel
    for n_live in [int(n) for n in args.live.split(",")]:
        n_live = min(n_live, B)
        live = jnp.arange(B) < n_live
        state = jax.random.normal(k[5], (L, B, H, P, N))
        y_k, s_k = jax.jit(kernel)(state, jnp.int32(1), live)
        y_x, s_x = jax.jit(plain)(state, jnp.int32(1), live)
        line = {
            "update_slots": B, "live": n_live,
            "y_diff": float(jnp.abs(
                jnp.where(live[:, None, None], y_k - y_x, 0.0)
            ).max()),
            "state_diff": float(jnp.abs(jnp.where(
                live[:, None, None, None], s_k[1] - s_x[1], 0.0
            )).max()),
        }
        if on_chip:
            call = roofline_ssm.ssm_update_call(n_live, H, P, N, G)
            least = call["bytes"] / peaks["hbm_bytes_per_s"] * 1e6
            carry, trips = (state, jnp.float32(0)), (2 * L, 12 * L)
            took = device_us(calls(kernel, live), carry, trips)
            ssm._update_kernel = copies
            try:
                took_copy = device_us(calls(kernel, live), carry, trips)
            finally:
                ssm._update_kernel = body
            line.update(
                kernel_us=took, copy_only_us=took_copy,
                xla_us=device_us(calls(plain, live), carry, trips),
                least_us=least, roofline_pct=100 * least / took,
            )
        print(json.dumps(line), flush=True)
    print(json.dumps({"ok": True, "device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
