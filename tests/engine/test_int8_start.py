"""An int8 engine start never holds the model's whole bf16 tree.

The 8B bf16 tree (16.4 GB) does not fit a 16 GB chip; the int8 tree
(8.2 GB) does. The start path therefore builds the int8 tree leaf by
leaf (models/quant.py init_params_int8, engine/weights.py).
"""

import jax
import jax.numpy as jnp
import numpy as np

from gpustack_tpu.models import init_params
from gpustack_tpu.models.config import get_config
from gpustack_tpu.models.quant import (
    QuantW,
    init_params_int8,
    quantize_params,
)

IS_Q = lambda x: isinstance(x, QuantW)  # noqa: E731


def test_init_params_int8_is_the_quantized_seeded_tree():
    """Same tree as quantize_params(init_params(...)) for the same key:
    structure, scales, and int8 values (the eager reference may round a
    handful of entries the other way; the jitted one is bit-equal)."""
    for preset in ("tiny", "tiny-moe"):
        cfg = get_config(preset)
        key = jax.random.key(0)
        got = init_params_int8(cfg, key)
        ref = jax.jit(lambda k: quantize_params(init_params(cfg, k)))(key)
        assert jax.tree.structure(got, is_leaf=IS_Q) == jax.tree.structure(
            ref, is_leaf=IS_Q
        )
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        eager = quantize_params(init_params(cfg, key))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(eager)):
            assert a.dtype == b.dtype and a.shape == b.shape
            diff = np.abs(
                np.asarray(a, np.float32) - np.asarray(b, np.float32)
            )
            assert diff.max() <= 1.0


def test_int8_start_never_holds_the_bf16_tree():
    """load_or_init_params(quantization='int8') — what the engine start
    calls — leaves no bf16 array the size of a quantized weight alive,
    and each of its programs returns one quantized leaf at a time."""
    from gpustack_tpu.engine.weights import load_or_init_params

    cfg = get_config("tiny")
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    big_bf16 = {
        (tuple(shapes["layers"][n].shape), jnp.bfloat16)
        for n in ("w_gate", "w_up", "w_down", "wq", "wo")
    }
    before = {id(a) for a in jax.live_arrays()}
    params = load_or_init_params(cfg, None, seed=0, quantization="int8")
    jax.block_until_ready(params)
    new = [a for a in jax.live_arrays() if id(a) not in before]
    assert not [
        a for a in new if (tuple(a.shape), a.dtype) in big_bf16
    ], "a bf16 copy of a quantized weight is alive after an int8 start"
    for name in ("w_gate", "w_up", "w_down", "wq", "wk", "wv", "wo"):
        assert IS_Q(params["layers"][name]), name
    # bytes of everything the start left on the device: the int8 tree,
    # well under the bf16 tree's size
    bf16_bytes = sum(
        int(np.prod(x.shape)) * 2 for x in jax.tree.leaves(shapes)
    )
    live_bytes = sum(a.nbytes for a in new)
    assert live_bytes < 0.75 * bf16_bytes, (live_bytes, bf16_bytes)


def test_engine_start_path_passes_quantization_to_the_loader(monkeypatch):
    """build_engine_from_args with --quantization int8 asks the loader
    for the int8 tree directly and never calls quantize_params on a
    whole tree afterwards."""
    import argparse

    from gpustack_tpu.engine import api_server, weights
    from gpustack_tpu.models import quant

    seen = {}
    real = weights.load_or_init_params

    def spy(cfg, model_dir, seed=0, quantization=""):
        seen["quantization"] = quantization
        return real(cfg, model_dir, seed=seed, quantization=quantization)

    real_quantize = quant.quantize_params

    def no_whole_tree_quantize(params):
        # under a trace it is init_params_int8's per-leaf programs; on
        # concrete arrays it would be the whole bf16 tree on the device
        if not any(
            isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(params)
        ):
            raise AssertionError("quantize_params called on a whole tree")
        return real_quantize(params)

    monkeypatch.setattr(weights, "load_or_init_params", spy)
    monkeypatch.setattr(quant, "quantize_params", no_whole_tree_quantize)
    args = argparse.Namespace(
        model_dir="", preset="tiny", served_name="t", mesh_plan="",
        num_devices=1, quantization="int8", speculative="", spec_tokens=4,
        max_slots=2, max_seq_len=64, lora=[],
    )
    engine = api_server.build_engine_from_args(args)
    assert seen["quantization"] == "int8"
    assert IS_Q(engine.runner.params["layers"]["wq"])


def test_engine_health_names_its_device():
    """health() says what the replica runs on: platform, device kind,
    count — read from the devices of the runner's mesh."""
    from gpustack_tpu.engine.engine import LLMEngine

    cfg = get_config("tiny")
    engine = LLMEngine(
        cfg, init_params_int8(cfg, jax.random.key(0)),
        max_slots=2, max_seq_len=64,
    )
    dev = engine.health()["device"]
    mesh_devices = list(engine.runner.mesh.devices.flat)
    assert dev["platform"] == mesh_devices[0].platform == "cpu"
    assert dev["device_kind"] == mesh_devices[0].device_kind
    assert dev["count"] == len(mesh_devices) == 1
    assert dev["ids"] == [d.id for d in mesh_devices]
    assert dev["memory"] == []   # the CPU backend reports no memory stats
