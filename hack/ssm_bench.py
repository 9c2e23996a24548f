#!/usr/bin/env python3
"""Times one state-space layer's selective scan alone on a chip, beside
what its shapes ask for (``perfbench/roofline_ssm.py``): the chunked scan
(``ops/ssm.py ssm_chunk_scan``, plain einsums) at the buckets the
benchmark's hybrid cell prefills, and the one-step update kernel
(``ssm_state_update``) against its XLA form at the deployment's slots
with some of them live.

    python3 hack/ssm_bench.py [--slots 32] [--live 8,16,32] [--buckets 256,512,1024]
    python3 hack/ssm_bench.py --rehearse        # the CPU: agreement only

The scan is no kernel, so a trace cannot name its operations
(``perfbench/layer_metrics/kernel.ssm_prefill_roofline.py``): this is
where its share of its roofline is measured (PERF.md section 5). A CPU
run gives no time."""

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
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from gpustack_tpu.ops.ssm import (
        ssm_chunk_scan,
        ssm_state_update,
        ssm_step_xla,
    )
    from perfbench import roofline, roofline_ssm

    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.rehearse:
        print(json.dumps({"ok": False, "why": "not a TPU"}))
        return 3
    H, P, N, G, Q = (4, 8, 16, 2, 8) if args.rehearse else (64, 64, 128, 8, 128)
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    k = jax.random.split(jax.random.key(0), 8)

    def timed(fn, *a, n=20):
        out = fn(*a)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n, out

    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    for T in [int(b) for b in args.buckets.split(",")]:
        if args.rehearse:
            T //= 16
        x = jax.random.normal(k[0], (1, T, H, P)).astype(jnp.bfloat16)
        dt = jax.nn.softplus(jax.random.normal(k[1], (1, T, H)))
        Bm = jax.random.normal(k[3], (1, T, G, N)).astype(jnp.bfloat16)
        Cm = jax.random.normal(k[4], (1, T, G, N)).astype(jnp.bfloat16)
        h0 = jnp.zeros((1, H, P, N), jnp.float32)
        scan = jax.jit(ssm_chunk_scan, static_argnums=6)
        took, _ = timed(scan, x, dt, A, Bm, Cm, h0, Q)
        call = roofline_ssm.ssm_scan_call(T, H, P, N, G, Q)
        least = roofline.least_seconds(call["flops"], call["bytes"], peaks)
        line = {"scan_tokens": T, **call, "least_us": least["seconds"] * 1e6,
                "bound": least["bound"]}
        if on_chip:
            line.update(us=took * 1e6,
                        roofline_pct=100 * least["seconds"] / took)
        print(json.dumps(line), flush=True)

    B, L = (4, 2) if args.rehearse else (args.slots, args.layers)
    x = jax.random.normal(k[0], (B, H, P)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, H)))
    Bm = jax.random.normal(k[3], (B, G, N)).astype(jnp.bfloat16)
    Cm = jax.random.normal(k[4], (B, G, N)).astype(jnp.bfloat16)
    kernel = jax.jit(
        lambda s, live: ssm_state_update(
            s, jnp.int32(1), x, dt, A, Bm, Cm, live,
            interpret=not on_chip,
        ), donate_argnums=0,
    )
    plain = jax.jit(
        lambda s: ssm_step_xla(s, jnp.int32(1), x, dt, A, Bm, Cm),
        donate_argnums=0,
    )
    for n_live in [int(n) for n in args.live.split(",")]:
        n_live = min(n_live, B)
        live = jnp.arange(B) < n_live
        state = jax.random.normal(k[5], (L, B, H, P, N))
        y_k, s_k = kernel(state + 0, live)
        y_x, s_x = plain(state + 0)
        agree = float(jnp.abs(
            jnp.where(live[:, None, None], y_k - y_x, 0.0)
        ).max())
        line = {"update_slots": B, "live": n_live, "y_diff": agree}
        if on_chip:
            s = state + 0
            jax.block_until_ready(s)
            t0 = time.perf_counter()
            for _ in range(50):
                _, s = kernel(s, live)
            jax.block_until_ready(s)
            took = (time.perf_counter() - t0) / 50
            s = state + 0
            jax.block_until_ready(s)
            t0 = time.perf_counter()
            for _ in range(50):
                _, s = plain(s)
            jax.block_until_ready(s)
            took_xla = (time.perf_counter() - t0) / 50
            call = roofline_ssm.ssm_update_call(n_live, H, P, N, G)
            least = call["bytes"] / peaks["hbm_bytes_per_s"]
            line.update(kernel_us=took * 1e6, xla_us=took_xla * 1e6,
                        least_us=least * 1e6,
                        roofline_pct=100 * least / took)
        print(json.dumps(line), flush=True)
    print(json.dumps({"ok": True, "device": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
