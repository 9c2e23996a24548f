"""Long-context composition: flash + chunked prefill + sp2 + host KV cache
running TOGETHER through one engine.

Each feature has its own tests; this is the composition proof the
reference's Long-Context profile exercises in one deployment
(gpustack/assets/profiles_config/profiles_config.yaml:29-38 — 32k ISL on
8 chips). Scaled down for hermetic CPU: a ~350-token prompt ("32k
analog") through a sequence-parallel (sp2) mesh with chunked prefill,
the pallas flash kernel (interpret mode) on every big-enough bucket, and
the host-RAM prefix KV cache — asserting token-identical output with the
plain single-device engine.

fp32 compute: flash vs XLA differ by output ulps in bf16, which flips
argmax near-ties on random tiny weights (same rationale as
test_chunked_prefill.py).
"""

import dataclasses

import jax
import pytest

from gpustack_tpu.engine.engine import GenRequest, LLMEngine
from gpustack_tpu.models import init_params
from gpustack_tpu.models.config import get_config
from gpustack_tpu.parallel.mesh import MeshPlan

SEQ = 512
PROMPT_LEN = 350
CHUNK = 64
OUT = 4


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_config("tiny"), dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def _prompt(cfg, n, seed=3):
    import numpy as np

    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, n).tolist()


def _reference(cfg, params, prompt, n_tokens):
    """Plain engine: no sp, no chunking, no cache, XLA attention."""
    eng = LLMEngine(cfg, params, max_slots=1, max_seq_len=SEQ)
    eng.start()
    try:
        return eng.generate(
            GenRequest(
                prompt_ids=prompt, max_tokens=n_tokens,
                temperature=0.0, stop_ids=(),
            ),
            timeout=900,
        ).output_ids
    finally:
        eng.stop()


def test_long_context_composition(setup, monkeypatch):
    cfg, params = setup
    prompt = _prompt(cfg, PROMPT_LEN)
    expect = _reference(cfg, params, prompt, OUT)

    from gpustack_tpu.engine.runner import ModelRunner

    # steer from the test: any non-ring bucket would take the pallas
    # kernel in interpret mode (the sp mesh keeps its ring path)
    monkeypatch.setattr(
        ModelRunner, "attn_impl_for",
        lambda self, bucket: "ring" if self.sp_mode else "flash_interpret",
    )
    eng = LLMEngine(
        cfg, params,
        max_slots=2, max_seq_len=SEQ,
        plan=MeshPlan(sp=2),
        prefill_chunk=CHUNK,
        host_kv_cache_mb=64,
    )
    eng.start()
    try:
        # 1) cold: chunked prefill through flash+ring over the sp2 mesh
        req = eng.generate(
            GenRequest(
                prompt_ids=list(prompt), max_tokens=OUT,
                temperature=0.0, stop_ids=(),
            ),
            timeout=1800,
        )
        assert req.output_ids == expect, (req.output_ids, expect)

        # let the async device->host KV copy land
        import time

        deadline = time.time() + 60
        while time.time() < deadline and eng.host_kv_cache.bytes_used == 0:
            time.sleep(0.5)
        assert eng.host_kv_cache.bytes_used > 0, "KV never stored"

        # 2) warm: identical prompt must hit the host cache and still
        # produce identical tokens
        req2 = eng.generate(
            GenRequest(
                prompt_ids=list(prompt), max_tokens=OUT,
                temperature=0.0, stop_ids=(),
            ),
            timeout=1800,
        )
        assert req2.output_ids == expect
        assert eng.host_kv_cache.hits >= 1

        # 3) prefix extension: long cached prefix + fresh suffix
        suffix = _prompt(cfg, 40, seed=11)
        extended = list(prompt) + suffix
        expect_ext = _reference(cfg, params, extended, OUT)
        req3 = eng.generate(
            GenRequest(
                prompt_ids=extended, max_tokens=OUT,
                temperature=0.0, stop_ids=(),
            ),
            timeout=1800,
        )
        assert req3.output_ids == expect_ext, (
            req3.output_ids, expect_ext
        )
    finally:
        eng.stop()
