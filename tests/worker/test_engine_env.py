"""The environment the worker hands an engine process: which chips it
may open, that a chip which cannot be opened is an error (never a CPU
run), and that the compile cache cannot be pointed elsewhere."""

import pytest

from gpustack_tpu.schemas import Model, ModelInstance
from gpustack_tpu.worker.backends import build_command, chip_env


def _env(chips, force_platform="", model_env=None, coordinator=""):
    model = Model(name="m", preset="tiny", env=model_env or {})
    inst = ModelInstance(
        id=7, model_id=1, model_name="m", chip_indexes=list(chips),
        coordinator_address=coordinator,
    )
    _, env = build_command(
        model, inst, 40000, None, force_platform=force_platform
    )
    return env


@pytest.mark.parametrize(
    "chips,bounds",
    [([0], "1,1,1"), ([3], "1,1,1"), ([2, 3], "1,2,1"),
     ([0, 1, 2, 3], "2,2,1"), (list(range(8)), "2,4,1")],
)
def test_chip_env_for_one_host(chips, bounds):
    env = chip_env(chips)
    assert env["TPU_VISIBLE_CHIPS"] == ",".join(map(str, chips))
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == bounds
    # its chips are a slice of their own: engines on one host are apart
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert "" not in env.values()


def test_chip_env_refuses_a_count_without_a_grid():
    with pytest.raises(ValueError, match="3 chips"):
        chip_env([0, 1, 2])


def test_engine_on_detected_chips_must_open_a_tpu():
    env = _env([1])
    assert env["JAX_PLATFORMS"] == "tpu"
    assert env["TPU_VISIBLE_CHIPS"] == "1"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert "GPUSTACK_TPU_PLATFORM" not in env


def test_force_platform_is_the_one_way_to_the_cpu():
    env = _env([0, 1], force_platform="cpu")
    assert env["GPUSTACK_TPU_PLATFORM"] == "cpu"
    assert "JAX_PLATFORMS" not in env
    assert "TPU_VISIBLE_CHIPS" not in env
    assert "--xla_force_host_platform_device_count=2" in env["XLA_FLAGS"]


def test_model_env_cannot_move_the_cache_or_the_chips():
    env = _env(
        [2],
        model_env={
            "JAX_COMPILATION_CACHE_DIR": "/somewhere/else",
            "TPU_VISIBLE_CHIPS": "0,1,2,3",
            "MY_FLAG": "1",
        },
    )
    assert "JAX_COMPILATION_CACHE_DIR" not in env
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["MY_FLAG"] == "1"


def test_multi_host_replica_keeps_the_hosts_own_tpu_environment():
    env = _env([0, 1, 2, 3], coordinator="10.0.0.1:41000")
    assert env["JAX_PLATFORMS"] == "tpu"
    assert "TPU_PROCESS_BOUNDS" not in env
    assert "TPU_VISIBLE_CHIPS" not in env
