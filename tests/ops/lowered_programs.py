"""The decode and prefill programs of the benchmark's
configurations, lowered for a described v5e chip, as text: what
``test_chip_compile.py::test_the_other_models_programs_lower_to_the_text_
they_had`` holds to ``lowered_programs.json``.

A change to ``forward``, the runner's choosers or a kernel that is meant
for one model must leave the others' programs as they were, to the
letter: PR 38's change to the cache broke the one cell whose cache is of
another shape. The file holds a hash of each program's lowered text
(StableHLO), taken from the tree before the change, with the kernels'
serialised Mosaic bodies left out: a body carries its source's path and
line numbers, so it changes with any edit to the kernel's file (a call's
name, operands, results and layouts stay in; the kernels themselves are
held by their interpret-mode tests). A PR that means to change these
programs takes the hashes again and says so:

    JAX_PLATFORMS=cpu python tests/ops/lowered_programs.py --write

Not a test module (no ``test_`` prefix): it describes the chip only when
called, from the one test file that may (``test_chip_compile.py``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
HASHES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "lowered_programs.json")
# (name, configuration's directory under perfbench/configs, layers kept
# (0: as the file says), slots, context, prefill bucket)
PROGRAMS = (
    ("qwen3-8b-int8", "qwen3-8b-int8", 0, 12, 2048, 2048),
    ("qwen3-30b-a3b-int8-l12", "qwen3-30b-a3b-int8-l12", 0, 32, 2048, 2048),
    ("ax-k1-int8-ep16-l12", "ax-k1-int8-ep16-l12", 0, 16, 8192, 4096),
    ("nemotron-3-nano-30b-a3b-int8-ep8",
     "nemotron-3-nano-30b-a3b-int8-ep8", 0, 32, 4096, 1024),
    ("command-a-plus-int8-ep8-l8", "command-a-plus-int8-ep8-l8",
     0, 16, 8192, 4096),
    ("olmo-hybrid-7b-int8", "olmo-hybrid-7b-int8", 0, 12, 2560, 1024),
    ("granite-4.0-h-micro-int8", "granite-4.0-h-micro-int8",
     0, 64, 2048, 1024),
    ("solar-open2-250b-int8-ep8-l12", "solar-open2-250b-int8-ep8-l12",
     0, 32, 2560, 1024),
    # ``.decode`` is its step over the cache: a block pass, 4 rows a slot
    ("sdar-30b-a3b-chat-int8-l12", "sdar-30b-a3b-chat-int8-l12",
     0, 32, 2560, 1024),
)


def lowered(one_chip) -> dict:
    """``{"<name>.decode" | "<name>.prefill": lowered text}`` of
    ``forward`` as the runner calls it on one TPU chip, from shapes
    alone."""
    import jax
    import jax.numpy as jnp

    from gpustack_tpu.models import init_params
    from gpustack_tpu.models.config import load_hf_config
    from gpustack_tpu.models.quant import quantize_params
    from gpustack_tpu.models.transformer import (
        KVCache,
        decode_attention_impl,
        forward,
        moe_dispatch,
    )

    def shapes(make):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(make),
        )

    out = {}
    for name, directory, layers, slots, context, bucket in PROGRAMS:
        cfg = load_hf_config(
            os.path.join(ROOT, "perfbench", "configs", directory)
        )
        if layers:
            cfg = dataclasses.replace(cfg, num_layers=layers)
        hybrid = cfg.state_mixer is not None   # a state a slot
        params = shapes(
            lambda: quantize_params(init_params(cfg, jax.random.key(0)))
        )
        experts = cfg.is_moe
        # rows a slot of a step over the cache: a diffusion block's, or 1
        rows = cfg.diffusion_block or 1
        attends = decode_attention_impl(cfg, rows, context, "tpu", None)

        def decode(params, tokens, positions, cache, live):
            return forward(
                params, cfg, tokens, positions, cache, live=live,
                decode_attn_impl=attends,
                moe_dispatch_impl=moe_dispatch(
                    slots * rows, cfg, "tpu", None, decode=True
                ) if experts else None,
                count_experts_read=experts,
                **({"ssm_impl": "kernel"} if hybrid else {}),
            )

        def prefill(params, tokens, true_len):
            cache = KVCache.create(cfg, 1, bucket)
            positions = jnp.arange(bucket, dtype=jnp.int32)[None]
            return forward(
                params, cfg, tokens, positions, cache, attn_impl="flash",
                moe_dispatch_impl=moe_dispatch(
                    bucket, cfg, "tpu", None
                ) if experts else None,
                count_held_pairs=bool(cfg.experts_held),
                logits_at=(true_len - 1)[None],
                # as ``ModelRunner._prefill_impl`` tells any model that
                # keeps something a slot beside its rows
                **({"true_len": true_len[None]} if cfg.beside_rows else {}),
                **({"ssm_impl": "scan"} if hybrid else {}),
            )

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

        out[name + ".decode"] = jax.jit(decode, donate_argnums=(3,)).lower(
            params, ints(slots, rows), ints(slots, rows),
            shapes(lambda: KVCache.create(cfg, slots, context)),
            jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
        ).as_text()
        out[name + ".prefill"] = jax.jit(prefill).lower(
            params, ints(1, bucket), ints(),
        ).as_text()
    return out


_KERNEL_BODY = re.compile(r'(\\22body\\22: \\22)[A-Za-z0-9+/=]+(\\22)')


def hashes(one_chip) -> dict:
    return {
        name: hashlib.sha256(
            _KERNEL_BODY.sub(r"\1\2", text).encode()
        ).hexdigest()
        for name, text in sorted(lowered(one_chip).items())
    }


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.environ.get("LOWER_FROM", ROOT))
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    got = hashes(SingleDeviceSharding(topo.devices[0]))
    if "--write" in sys.argv:
        with open(HASHES, "w") as f:
            json.dump(got, f, indent=1)
            f.write("\n")
    print(json.dumps(got, indent=1))
