#!/usr/bin/env python3
"""What the TPU's compiler makes of one kernel: compiles it at a cell's
shapes for a described v5e (no chip; ``tests/ops/test_chip_compile.py``'s
way) with libtpu's LLO dump on, and prints the final schedule of one
grid point: bundles, how many carry a cross-lane or a vector operation,
slot uses a unit, spills and fills, the cross-lane and matrix opcodes by
count.

    python3 hack/kernel_bundles.py ssm            # ops/ssm.py, Nemotron's cell
    python3 hack/kernel_bundles.py delta      # ops/delta_rule.py, Olmo-Hybrid's
    python3 hack/kernel_bundles.py latent     # ops/mla_attention.py's row write
    python3 hack/kernel_bundles.py flash      # ops/flash_attention.py, A.X-K1's
    python3 hack/kernel_bundles.py prefill    # ops/mla_attention.py's prefill
    python3 hack/kernel_bundles.py flash --tile 1024,1024,512
    python3 hack/kernel_bundles.py ssm --keep <an empty directory>

``flash`` is the prefill kernel at the long-document cell's call (64
heads of one, 8,192 rows, keys 192 and values 128) with the tile its
shapes choose, or with ``--tile block_q,block_k,rows`` (``rows``: of one
matmul, the module's ``_MATMUL_ROWS``). Its body holds every sweep of a
grid point, masked and clear, once a q sub-block: the counts are of the
body, not of the path a point takes through it. ``prefill`` is the
latent's own call that took that call's place in the cell (PR 62), at the
same rows and widths, token-major operands: to be read beside ``flash``.

The dump aborts the process once the kernel's files are written (a
report template the wheel lacks), so the compile is a child process and
this is a script, not a test. A count of bundles is no time: it is what
says why a time is what it is (PERF.md section 7)."""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_ssm(on):
    import jax
    import jax.numpy as jnp

    from gpustack_tpu.ops.ssm import ssm_state_update

    L, B, H, P, N, G = 23, 32, 64, 64, 128, 8
    f32, bf16 = jnp.float32, jnp.bfloat16
    jax.jit(ssm_state_update, donate_argnums=0).lower(
        on((L, B, H, P, N), f32), on((), jnp.int32), on((B, H, P), bf16),
        on((B, H), f32), on((H,), f32), on((B, G, N), bf16),
        on((B, G, N), bf16), on((B,), jnp.bool_),
    ).compile()


def compile_delta(on):
    import jax
    import jax.numpy as jnp

    from gpustack_tpu.ops.delta_rule import delta_state_update

    L, B, H, Dk, Dv = 24, 12, 30, 96, 192
    f32 = jnp.float32
    jax.jit(delta_state_update, donate_argnums=0).lower(
        on((L, B, Dk, H * Dv), f32), on((), jnp.int32), on((B, H, Dk), f32),
        on((B, H, Dk), f32), on((B, H, Dv), f32), on((B, H), f32),
        on((B, H), f32), on((B,), jnp.bool_),
    ).compile()


def compile_latent(on):
    import jax
    import jax.numpy as jnp

    from gpustack_tpu.ops.mla_attention import mla_write_latent_rows

    L, B, S, rank = 12, 16, 8192, 512
    bf16 = jnp.bfloat16
    jax.jit(mla_write_latent_rows, donate_argnums=0).lower(
        on((L, B, S, rank), bf16), on((B, rank), bf16), on((), jnp.int32),
        on((B,), jnp.int32),
    ).compile()


def compile_flash(on, tile=None):
    import contextlib
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from gpustack_tpu.ops import flash_attention as fa

    T, H, d, dv = 8192, 64, 192, 128
    bf16 = jnp.bfloat16
    other = contextlib.nullcontext()
    if tile:
        block_q, block_k, rows = tile
        with mock.patch.object(fa, "_MATMUL_ROWS", rows):
            tiles = fa.tiles_of(block_q, block_k, 1)
        other = mock.patch.object(fa, "choose_tiles", lambda *shapes: tiles)
    with other:
        jax.jit(
            lambda q, k, v: fa.flash_attention_prefill(q, k, v, d ** -0.5)
        ).lower(
            on((1, T, H, d), bf16), on((1, T, H, d), bf16),
            on((1, T, H, dv), bf16),
        ).compile()


def compile_prefill(on):
    import jax
    import jax.numpy as jnp

    from gpustack_tpu.ops.mla_attention import mla_prefill_attention

    T, H, nope, rope, vd = 8192, 64, 128, 64, 128
    bf16, f32 = jnp.bfloat16, jnp.float32
    jax.jit(
        lambda *operands: mla_prefill_attention(*operands, 192 ** -0.5)
    ).lower(
        on((1, T, H * (nope + rope)), bf16), on((1, T, H * nope), bf16),
        on((1, T, rope), bf16), on((1, T, H * vd), bf16),
        on((1, T, rope // 2), f32), on((1, T, rope // 2), f32),
    ).compile()


# the script's name for a kernel -> (its pallas_call's name, what lowers
# and compiles it at a cell's shapes given ``on(shape, dtype)``)
KERNELS = {
    "ssm": ("ssm_state_update", compile_ssm),
    "delta": ("delta_state_update", compile_delta),
    "latent": ("mla_write_latent_rows", compile_latent),
    "flash": ("flash_attention_prefill", compile_flash),
    "prefill": ("mla_prefill_attention", compile_prefill),
}


def child(kernel: str, tile=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    chip = SingleDeviceSharding(topo.devices[0])

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    compile_at = KERNELS[kernel][1]
    compile_at(on, tile) if tile else compile_at(on)
    return 0


def read(dump: str, call: str) -> dict:
    """The table's columns from the two final files of ``call``."""
    def one(suffix):
        found = sorted(glob.glob(os.path.join(dump, f"*-{call}*-{suffix}")))
        if not found:
            raise SystemExit(f"no *-{call}*-{suffix} under {dump}")
        return found[0]

    with open(one("final_hlo-static-per-bundle-utilization.txt")) as f:
        lines = f.read().splitlines()
    units = [u.strip() for u in lines[1].split(",")]
    capacity = [int(n) for n in lines[2].split()]
    rows = [[int(n) for n in ln.split()] for ln in lines[4:] if ln.strip()]
    col = {u: [r[i] for r in rows] for i, u in enumerate(units)}
    with open(one("final_bundles.txt")) as f:
        text = f.read()
    ops = collections.Counter(re.findall(r"= ([a-z][\w.]*)", text))
    # a cross-lane or matrix operation is named once, where it is
    # pushed (not its pop, nor a pattern set for it), whichever unit
    merged = collections.Counter()
    for op, n in sorted(ops.items()):
        if re.search(r"\.xlu\d|\.mxu\d|^vrot", op) and not op.startswith(
            ("vpop", "vset")
        ):
            merged[re.sub(r"\.(xlu|mxu)\d+", "", op)] += n
    return {
        "call": call,
        "bundles": len(rows),
        "capacity_a_bundle": dict(zip(units, capacity)),
        "bundles_with": {
            "cross_lane": sum(1 for n in col["XLU"] if n),
            "vector_alu": sum(1 for n in col["VALU"] if n),
            "matrix": sum(1 for n in col["MXU"] if n),
            "a_store": sum(1 for n in col["VSTORE"] if n),
        },
        "slot_uses": {u: sum(col[u]) for u in units},
        "spills": sum(col["VSTORE:SPILL"]),
        "fills": sum(col["VLOAD:FILL"]),
        "cross_lane_and_matrix_ops": dict(merged),
        "loads": ops["vld"], "stores": ops["vst"] + ops["vst.msk"],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("kernel", choices=sorted(KERNELS))
    ap.add_argument(
        "--keep", help="the dump's directory, empty (else a temporary one)"
    )
    ap.add_argument(
        "--tile", help="flash only: block_q,block_k,rows of a matmul",
        type=lambda s: tuple(int(n) for n in s.split(",")),
    )
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.tile and args.kernel != "flash":
        ap.error("--tile goes with flash")
    if args.child:
        return child(args.kernel, args.tile)
    call = KERNELS[args.kernel][0]
    with tempfile.TemporaryDirectory() as tmp:
        dump = args.keep or tmp
        os.makedirs(dump, exist_ok=True)
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            LIBTPU_INIT_ARGS=(
                f"--xla_jf_dump_to={dump} --xla_jf_dump_llo_text=true"
            ),
        )
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), args.kernel,
             "--child"] + (
                ["--tile", ",".join(map(str, args.tile))] if args.tile
                else []
            ),
            env=env, capture_output=True, text=True,
        )
        # the child aborts after the dump (see the docstring): what
        # counts is whether the kernel's files are there
        try:
            print(json.dumps(read(dump, call), indent=1))
        except SystemExit:
            sys.stderr.write(done.stderr[-4000:])
            raise
    return 0


if __name__ == "__main__":
    sys.exit(main())
