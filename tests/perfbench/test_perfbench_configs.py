"""The configuration files are the published models, cut only as listed."""

import dataclasses
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIGS = os.path.join(ROOT, "perfbench", "configs")

# The preset keeps ModelConfig's default rms_norm_eps (1e-5); the hub
# files say 1e-6, and the hub files are what the benchmark runs (PERF.md,
# Open questions). Nothing else may differ, bar the name and a listed cut.
CASES = [
    ("qwen3-8b-int8", "qwen3-8b", {"name", "rms_norm_eps"}),
    ("qwen3-30b-a3b-int8-l12", "qwen3-30b-a3b",
     {"name", "rms_norm_eps", "num_layers"}),
]


@pytest.mark.parametrize("directory,preset,may_differ", CASES)
def test_config_json_equals_the_preset(directory, preset, may_differ):
    from gpustack_tpu.models.config import PRESETS, load_hf_config

    got = dataclasses.asdict(load_hf_config(os.path.join(CONFIGS, directory)))
    want = dataclasses.asdict(PRESETS[preset])
    differs = {k for k in want if got[k] != want[k]}
    assert differs <= may_differ, {k: (got[k], want[k]) for k in differs}
    assert got["qk_norm"] is True


@pytest.mark.parametrize("directory", [c[0] for c in CASES])
def test_deployment_lists_its_cuts(directory):
    with open(os.path.join(CONFIGS, directory, "deployment.json")) as f:
        dep = json.load(f)
    with open(os.path.join(CONFIGS, directory, "config.json")) as f:
        cfg = json.load(f)
    assert dep["name"] == directory
    assert dep["source"].startswith("https://huggingface.co/Qwen/")
    assert len(dep["source"]) <= 200
    published_depth = {"qwen3-30b-a3b-int8-l12": 48}.get(directory, 36)
    cut = cfg["num_hidden_layers"] != published_depth
    assert ("num_hidden_layers" in dep["reduced"]) == cut
    assert set(dep["model"]) >= {"quantization", "max_seq_len", "max_slots"}
    assert dep["chips"] in (1, 4) and dep["assumed"]

