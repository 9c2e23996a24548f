"""One layer's decode attention alone, on the chip: the XLA form
``forward`` keeps (the layer's slab sliced out of the stacked cache, then
``_attend`` over all ``slots x max_len`` positions under a mask) beside
the kernel of ``ops/decode_attention.py`` (the cache read where it lies,
each slot as far as its length).

    python hack/decode_attn_bench.py [--cases 12x8:5x350,32x4:8x1450]
                                     [--blocks 1024,512]

A case is ``<slots>x<kv heads>:<live slots>x<their length>``, 32 query
heads of 128 over a cache of 2,048 positions in bf16, four layers of it
with the second attended: the shapes of the benchmark's two Qwen3
deployments, from the chat cell's occupancy to every slot full and long,
where the kernel has nothing to leave out. The live slots are spread
over the slots as a first-in first-out free list leaves them; a slot
nobody holds has length 0 for the kernel and its last tenant's rows in
the cache, which the XLA form reads.

Each line: device microseconds a call of either form (five calls under
the profiler, the trace's ``XLA Ops`` summed), the XLA form's largest
operations, the K and V bytes the live rows are, the kernel's share of
the HBM's rate for them, and the largest difference between the two
forms' results over the live slots. ``--blocks`` times the kernel at
each block size beside the chooser's own. One JSON line a case on stdout
and in ``chiprun_out/decode_attn_bench.jsonl``. ``--rehearse`` runs the
kernel in interpret mode on the CPU over a short cache: whether the two
forms agree, no time. Nothing a cell runs imports this file.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
from unittest import mock

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "hack"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from gpustack_tpu.models.transformer import _attend  # noqa: E402
from gpustack_tpu.ops import decode_attention  # noqa: E402
from sample_bench import profiled  # noqa: E402

CASES = ("12x8:5x350", "12x8:8x1450", "12x8:12x2047",
         "32x4:8x1450", "32x4:32x2047")
LAYERS, LAYER, HQ, HD = 4, 1, 32, 128


def xla_form(q, k_cache, v_cache, layer, lengths, scale):
    """What ``forward`` traces without the kernel."""
    B, S, Hkv = k_cache.shape[1:4]
    k, v = (
        lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)
        for buf in (k_cache, v_cache)
    )
    mask = jnp.arange(S)[None, None, :] < lengths[:, None, None]
    return _attend(
        q.reshape(B, 1, Hkv, HQ // Hkv, HD), k, v, mask, scale
    )[:, 0]


def kernel_form(block: int = 0, interpret: bool = False):
    """The kernel, with its block of positions forced where ``block``."""
    def fn(*args):
        if not block:
            return decode_attention.gqa_decode_attention(
                *args, interpret=interpret
            )
        with mock.patch.object(decode_attention, "_GQA_POSITIONS", block):
            return decode_attention.gqa_decode_attention(*args)

    return jax.jit(fn, static_argnums=(5,))


def inputs(slots: int, kv_heads: int, live: int, length: int, S: int):
    keys = jax.random.split(jax.random.key(slots * live + length), 3)
    shape = (LAYERS, slots, S, kv_heads, HD)
    q = jax.random.normal(keys[0], (slots, HQ, HD), jnp.bfloat16)
    k = jax.random.normal(keys[1], shape, jnp.bfloat16)
    v = jax.random.normal(keys[2], shape, jnp.bfloat16)
    held = np.zeros((slots,), bool)
    held[np.linspace(0, slots - 1, live).round().astype(int)] = True
    lengths = jnp.asarray(np.where(held, length, 0), jnp.int32)
    return (q, k, v, jnp.int32(LAYER), lengths, HD ** -0.5), held


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", default="")
    ap.add_argument("--blocks", default="")
    ap.add_argument("--ops", type=int, default=4)
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
    peaks = json.loads((ROOT / "perfbench/peaks.json").read_text())
    S = 256 if args.rehearse else 2048
    out_path = ROOT / "chiprun_out/decode_attn_bench.jsonl"
    out_path.parent.mkdir(exist_ok=True)
    xla = jax.jit(xla_form, static_argnums=(5,))

    for case in (args.cases.split(",") if args.cases else CASES):
        (slots, kv_heads), (live, length) = (
            [int(n) for n in part.split("x")] for part in case.split(":")
        )
        length = min(length, S)
        call, held = inputs(slots, kv_heads, live, length, S)
        need = 2 * live * length * kv_heads * HD * 2      # K and V, bf16
        rec = {
            "device": dev.device_kind, "case": case, "max_len": S,
            "block": decode_attention.gqa_block_positions(S, kv_heads, HD),
            "live_kv_bytes": need,
        }
        if args.rehearse:
            want = xla(*call)
            got = kernel_form(interpret=True)(*call)
        else:
            rate = peaks[dev.device_kind]["hbm_bytes_per_s"]
            want, rec["xla_us"], rec["xla_ops_us"] = profiled(
                xla, call, args.ops
            )
            got, rec["kernel_us"], _ = profiled(kernel_form(), call, 1)
            rec["kernel_hbm_share"] = need / rate / (rec["kernel_us"] / 1e6)
            rec["kernel_us_by_block"] = {
                b: profiled(kernel_form(int(b)), call, 1)[1]
                for b in args.blocks.split(",") if b
            }
        diff = np.abs(
            np.asarray(want, np.float64) - np.asarray(got, np.float64)
        )
        rec["largest_difference_live"] = float(diff[held].max())
        rec["dead_slots_are_zeros"] = not np.asarray(got)[~held].any()
        line = json.dumps(rec)
        print(line, flush=True)
        with out_path.open("a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
