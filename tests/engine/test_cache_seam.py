"""What a slot keeps beside its rows is ``KVCache``'s to know and
``ModelConfig.beside_rows``'s to name: the runner and the engine carry it
without looking inside. The three kinds of cache through the one seam:
rows a position and nothing else (a tiny Qwen3), a recurrent state (the
tiny Nemotron-H of ``test_hybrid_engine.py``), a ring of window rows
(the tiny Command A+ of ``test_window_engine.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_hybrid_engine import HF as HYBRID_HF
from test_window_engine import HF as WINDOW_HF
from test_window_engine import WINDOW

from gpustack_tpu.engine.engine import LLMEngine
from gpustack_tpu.engine.runner import ModelRunner
from gpustack_tpu.models.config import config_from_hf, get_config
from gpustack_tpu.models.transformer import init_params

KINDS = ("rows", "state", "ring")
# the fields a kind keeps beside k and v
BESIDE = {"rows": (), "state": ("ssm", "conv"), "ring": ("wk", "wv")}
FIELDS = ("k", "v", "ssm", "conv", "wk", "wv")


def _cfg(kind):
    if kind == "rows":
        cfg = get_config("tiny-qwen3")
    elif kind == "state":
        cfg = config_from_hf(HYBRID_HF, "tiny-nemotron-h")
    else:
        cfg = config_from_hf(WINDOW_HF, "tiny-command-a-plus")
    return dataclasses.replace(cfg, dtype="float32")


@pytest.fixture(scope="module")
def runners():
    made = {}

    def runner(kind):
        if kind not in made:
            cfg = _cfg(kind)
            made[kind] = ModelRunner(
                cfg, init_params(cfg, jax.random.key(0), jnp.float32),
                max_slots=3, max_seq_len=64,
            )
        return made[kind]

    return runner


def _prefilled(runner, n=11, bucket=32):
    ids = [(5 + 7 * i) % 250 + 5 for i in range(n)]
    _, k, v, *mixer = runner.prefill(ids + [0] * (bucket - n), n)
    return k, v, mixer


@pytest.mark.parametrize("kind", KINDS)
def test_a_prefill_s_share_lands_in_its_slot_field_for_field(runners, kind):
    """What ``prefill`` hands on and ``insert`` takes back is, in slot 1
    of a fresh state, what the arrays say written out by hand: the rows'
    first ``Tb`` positions, a state whole, a ring's first rows; every
    other slot, and every field the kind does not keep, as it was."""
    runner = runners(kind)
    k, v, mixer = _prefilled(runner)
    assert len(mixer) == (1 if BESIDE[kind] else 0)
    assert (runner.cfg.beside_rows is None) == (not mixer)
    fresh = runner.new_state().cache
    want = {
        "k": fresh.k.at[:, 1, :32].set(k), "v": fresh.v.at[:, 1, :32].set(v),
    }
    if kind == "state":
        ssm, conv = mixer[0]
        want["ssm"] = fresh.ssm.at[:, 1].set(ssm)
        want["conv"] = fresh.conv.at[:, 1].set(conv)
        assert float(jnp.abs(ssm).max()) > 0
    if kind == "ring":
        wk, wv = mixer[0]
        assert wk.shape[1] == WINDOW
        want["wk"] = fresh.wk.at[:, 1, :wk.shape[1]].set(wk)
        want["wv"] = fresh.wv.at[:, 1, :wv.shape[1]].set(wv)
        assert float(jnp.abs(wk).max()) > 0
    state = runner.insert(
        runner.new_state(), k, v, 1, 11, 7, 0.0, 0, 1.0,
        **({"mixer": mixer[0]} if mixer else {}),
    )
    for name in FIELDS:
        got = getattr(state.cache, name)
        if name not in want:
            assert got is None, name
            continue
        np.testing.assert_array_equal(got, want[name], err_msg=name)
        for other in (0, 2):
            assert not np.asarray(got[:, other]).any(), (name, other)
    assert [int(x) for x in state.positions] == [0, 11, 0]
    assert [bool(x) for x in state.active] == [False, True, False]


@pytest.mark.parametrize("kind", KINDS)
def test_a_snapshot_carries_what_a_mask_cannot_undo_and_no_more(runners, kind):
    """Snapshot, two decode steps, restore: a recurrent state comes back
    bit for bit; rows need no copy (those above a restored position are
    masked), and a ring is copied for nobody (speculation is refused for
    it at engine start): for both the snapshot is positions and last
    tokens alone and the cache is left as the steps made it."""
    runner = runners(kind)
    k, v, mixer = _prefilled(runner)
    state = runner.insert(
        runner.new_state(), k, v, 1, 11, 7, 0.0, 0, 1.0,
        **({"mixer": mixer[0]} if mixer else {}),
    )
    snap = runner.snapshot_sequence(state)
    assert len(snap) == 2 + (2 if kind == "state" else 0)
    before = {
        name: np.array(getattr(state.cache, name)) for name in BESIDE[kind]
    }
    for _ in range(2):
        state, _ = runner.decode_step(state, jax.random.key(0))
    stepped = {
        name: np.array(getattr(state.cache, name)) for name in BESIDE[kind]
    }
    assert int(state.positions[1]) == 13
    assert all((before[name] != stepped[name]).any() for name in before)
    state = runner.restore_sequence(state, snap)
    assert int(state.positions[1]) == 11 and int(state.last_tokens[1]) == 7
    kept = before if kind == "state" else stepped
    for name in BESIDE[kind]:
        np.testing.assert_array_equal(
            getattr(state.cache, name), kept[name], err_msg=name
        )


def test_the_configuration_names_what_a_slot_keeps_beside_its_rows():
    assert _cfg("rows").beside_rows is None
    assert get_config("qwen3-8b").beside_rows is None
    state, ring = _cfg("state").beside_rows, _cfg("ring").beside_rows
    assert state.keeps == "has state-space layers"
    assert state.lost == "a recurrent state"
    assert ring.keeps == "keeps its sliding layers' rows at window size"
    assert ring.lost == "rows a ring has overwritten"
    assert "recurrent state" in state.span and "window has passed" in ring.span
    # and the bytes a slot keeps beside its rows are the two stores'
    for cfg in (_cfg("rows"), _cfg("state"), _cfg("ring")):
        assert cfg.beside_bytes_per_slot(64, 32) == (
            cfg.state_bytes_per_slot(32) + cfg.window_bytes_per_slot(64, 32)
        )
    assert _cfg("rows").beside_bytes_per_slot(64) == 0
    assert _cfg("state").beside_bytes_per_slot(64) > 0
    assert _cfg("ring").beside_bytes_per_slot(64) > 0


MECHANISMS = {
    "speculative": (dict(speculative="ngram"), "a verify step"),
    "prefix_cache": (dict(host_kv_cache_mb=8), "the prefix cache"),
    "spill": (dict(kv_spill_mb=8), "the spill tier"),
    "handoff": (dict(kv_role="decode"), "a KV handoff"),
    "chunked_prefill": (dict(prefill_chunk=16), "a chunk goes on from"),
}


@pytest.mark.parametrize("mechanism", sorted(MECHANISMS))
@pytest.mark.parametrize("kind", ["state", "ring"])
def test_the_one_refusal_names_each_mechanism_for_either_kind(
    kind, mechanism
):
    """One function refuses the five, and says for either kind the model,
    what it keeps, the option, the mechanism, and what a span of
    positions lacks, in the configuration's own words."""
    cfg = _cfg(kind)
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    options, names = MECHANISMS[mechanism]
    with pytest.raises(ValueError) as err:
        LLMEngine(cfg, params, max_slots=2, max_seq_len=32, **options)
    said = str(err.value)
    beside = cfg.beside_rows
    assert said.startswith(f"{cfg.name} {beside.keeps} and cannot be served")
    assert next(iter(options)) in said and names in said
    assert (beside.lost if mechanism == "speculative" else beside.span) in said


def test_a_model_of_rows_alone_is_refused_none_of_them(tmp_path):
    cfg = _cfg("rows")
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    eng = LLMEngine(
        cfg, params, max_slots=2, max_seq_len=32, host_kv_cache_mb=8,
        kv_spill_mb=8, kv_spill_dir=str(tmp_path), prefill_chunk=16,
        kv_role="decode",
    )
    assert eng.health()["cache"]["state_bytes"] == 0
