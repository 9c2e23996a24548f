"""Between two serving programs the scheduler's thread hands the device
nothing (ROADMAP A11): what a program needs from the host goes in as
NumPy arguments of the call itself, a draw's key is made inside its
program from the engine's seed and its count of draws, and a finished
slot is switched off by the next decode program (``freed``). So an
engine's thread lowers ``prefill_<bucket>``, ``_insert_impl``,
``_sample_first_impl`` and ``_decode_impl`` and no other program; what
had to hold through the change holds: a seeded request's tokens, a key of
its own for every draw, a finished slot that stands still, a slot that
changes hands before the next step and is live."""

import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_hybrid_engine import HF as HYBRID_HF
from test_window_engine import HF as WINDOW_HF

from gpustack_tpu.engine.engine import GenRequest, LLMEngine
from gpustack_tpu.engine.runner import ModelRunner, draw_words
from gpustack_tpu.models.config import config_from_hf, get_config
from gpustack_tpu.models.transformer import init_params
from gpustack_tpu.observability.startup import process_programs

KINDS = ("dense", "hybrid", "windowed")
DEPTHS = pytest.mark.parametrize(
    "depth", [0, 2], ids=["synchronous", "overlapped"]
)
SERVING = re.compile(
    r"jit\((prefill_\d+|_insert_impl|_sample_first_impl|_decode_impl)\)"
)
# the seeded requests of ``_traffic`` as the parent commit served them
# (taken on its tree before the host's key split went into the programs),
# either depth: a seeded row's noise is fold_in(seed, position) and never
# met the engine's key
SEEDED = {
    "dense": ([2, 250, 170, 138, 115, 158], [78, 223, 45, 211, 146]),
    "hybrid": ([65, 245, 112, 43, 4, 171], [164, 197, 18, 195, 2]),
    "windowed": ([54, 157, 97, 54, 4, 202], [62, 163, 262, 205, 115]),
}


@pytest.fixture(scope="module")
def models():
    dense = get_config("tiny")
    out = {"dense": (dense, init_params(dense, jax.random.key(0)))}
    for kind, hf in (("hybrid", HYBRID_HF), ("windowed", WINDOW_HF)):
        cfg = dataclasses.replace(
            config_from_hf(hf, "tiny-" + kind), dtype="float32"
        )
        out[kind] = (cfg, init_params(cfg, jax.random.key(0), jnp.float32))
    return out


def prompt(n, start=5):
    return [(start + 7 * i) % 250 + 5 for i in range(n)]


def _traffic():
    """Two slots' worth: a seeded request that runs on, a greedy one
    that ends after three tokens, and a seeded third that takes the
    slot the second leaves."""
    return [
        GenRequest(prompt_ids=prompt(9), max_tokens=6, temperature=0.9,
                   top_k=20, seed=1234, stop_ids=()),
        GenRequest(prompt_ids=prompt(12, 9), max_tokens=3, temperature=0.0,
                   stop_ids=()),
        GenRequest(prompt_ids=prompt(7, 3), max_tokens=5, temperature=0.8,
                   seed=77, stop_ids=()),
    ]


def _drive(eng, reqs, seconds=120.0):
    """The scheduler's loop on this thread, so that what it lowers is
    this thread's. An idle step waits a little: an overlapped engine's
    requests are done when its detokenizer's thread says so."""
    deadline = time.monotonic() + seconds
    try:
        while time.monotonic() < deadline:
            if not eng.step():
                time.sleep(0.001)
            if all(r.done.is_set() for r in reqs):
                return reqs
    finally:
        eng.stop()
    raise AssertionError("the requests did not end")


@DEPTHS
@pytest.mark.parametrize("kind", KINDS)
def test_the_scheduler_s_thread_lowers_serving_programs_only(
    models, kind, depth
):
    """An admission, steps, a finish, an admission into the freed slot,
    steps, the last finishes: every program lowered meanwhile is one of
    the four a start lowers too. The process's programs are forgotten
    first (``jax.clear_caches``): an eager ``convert_element_type`` or
    ``scatter`` that an earlier test had lowered would run from the
    cache and leave the log nothing to show."""
    cfg, params = models[kind]
    eng = LLMEngine(
        cfg, params, max_slots=2, max_seq_len=64, pipeline_depth=depth
    )
    log = process_programs()
    jax.clear_caches()
    closed = log.counts()[2]
    reqs = _drive(eng, [eng.submit(r) for r in _traffic()])
    names = [r["name"] for r in log.since(closed)]
    assert {"jit(prefill_32)", "jit(_insert_impl)", "jit(_sample_first_impl)",
            "jit(_decode_impl)"} <= set(names)
    assert [n for n in names if not SERVING.fullmatch(n)] == []
    # the third request did take the slot the second left
    assert [len(r.output_ids) for r in reqs] == [6, 3, 5]
    assert eng._draws >= 3 + 5 and not eng._freed - {0, 1}


@DEPTHS
@pytest.mark.parametrize("kind", KINDS)
def test_a_seeded_request_s_tokens_are_the_parent_s(models, kind, depth):
    cfg, params = models[kind]
    eng = LLMEngine(
        cfg, params, max_slots=2, max_seq_len=64, pipeline_depth=depth
    )
    first, _, third = _drive(eng, [eng.submit(r) for r in _traffic()])
    assert (first.output_ids, third.output_ids) == SEEDED[kind]


@DEPTHS
def test_every_draw_has_a_key_of_its_own(models, depth):
    """Two decode steps and the admission between them, and every other
    draw of the run: no two from one key (the words the calls were
    handed are the key's data). Two unseeded requests for one prompt
    then part ways."""
    cfg, params = models["dense"]
    eng = LLMEngine(
        cfg, params, max_slots=2, max_seq_len=64, pipeline_depth=depth
    )
    words = []
    runner = eng.runner
    decode_step, sample_first = runner.decode_step, runner.sample_first

    def spy_decode(state, key, **kw):
        words.append(("step", tuple(int(w) for w in key)))
        return decode_step(state, key, **kw)

    def spy_first(*a, **kw):
        words.append(("admission", tuple(int(w) for w in a[7])))
        return sample_first(*a, **kw)

    runner.decode_step, runner.sample_first = spy_decode, spy_first
    one = eng.submit(GenRequest(
        prompt_ids=prompt(9), max_tokens=8, temperature=1.0, stop_ids=()
    ))
    eng.step()
    eng.step()
    two = eng.submit(GenRequest(
        prompt_ids=prompt(9), max_tokens=8, temperature=1.0, stop_ids=()
    ))
    _drive(eng, [one, two])
    kinds = [k for k, _ in words]
    at = kinds.index("admission", 1)        # the second admission
    assert kinds[at - 1] == kinds[at + 1] == "step"
    drawn = [w for _, w in words]
    assert len(set(drawn[at - 1: at + 2])) == 3
    assert len(set(drawn)) == len(drawn) == eng._draws
    assert {w[0] for w in drawn} == {0}     # the engine's seed
    assert one.output_ids != two.output_ids


def test_a_typed_key_gives_its_data_s_words():
    """A caller that is not the engine hands a step a typed key: the
    program takes its data's two words and wraps them again."""
    key = jax.random.key(7)
    np.testing.assert_array_equal(draw_words(key), jax.random.key_data(key))
    words = np.array([3, 9], np.uint32)
    assert draw_words(words) is words
    np.testing.assert_array_equal(
        jax.random.key_data(jax.random.wrap_key_data(draw_words(key))),
        jax.random.key_data(key),
    )


@pytest.mark.parametrize("kind", KINDS)
def test_a_freed_slot_stands_still_in_the_next_step(models, kind):
    """``freed`` is the eager switch-off folded into the step: the state
    a step leaves is, leaf for leaf, what it leaves after ``deactivate``;
    the slot's position and token stay, its neighbour's move."""
    cfg, params = models[kind]
    runner = ModelRunner(cfg, params, max_slots=3, max_seq_len=64)

    def filled():
        state = runner.new_state()
        for slot, n in ((0, 9), (1, 17)):
            _, k, v, *mixer = runner.prefill(
                prompt(n, slot) + [0] * (32 - n), n
            )
            state = runner.insert(
                state, k, v, slot, n, 7, 0.0, 0, 1.0,
                **({"mixer": mixer[0]} if mixer else {}),
            )
        state, _ = runner.decode_step(state, jax.random.key(0))
        return state

    mask = np.zeros((3,), np.bool_)
    mask[1] = True
    masked, _ = runner.decode_step(filled(), jax.random.key(1), freed=mask)
    eager, _ = runner.decode_step(
        runner.deactivate(filled(), 1), jax.random.key(1)
    )
    for got, want in zip(jax.tree.leaves(masked), jax.tree.leaves(eager)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(masked.active).tolist() == [True, False, False]
    assert np.asarray(masked.positions).tolist() == [11, 18, 0]
    # and the step after it moves the live slot alone
    token = int(masked.last_tokens[1])
    later, _ = runner.decode_step(masked, jax.random.key(2))   # donates
    assert np.asarray(later.positions).tolist() == [12, 18, 0]
    assert int(later.last_tokens[1]) == token


@DEPTHS
@pytest.mark.parametrize("kind", KINDS)
def test_a_slot_that_changes_hands_before_the_next_step_is_live(
    models, kind, depth
):
    """One slot, two requests: the second is admitted into the slot the
    first has just left, before any step has switched it off, and decodes
    what it decodes with an engine to itself."""
    cfg, params = models[kind]

    def serve(*prompts):
        eng = LLMEngine(
            cfg, params, max_slots=1, max_seq_len=64, pipeline_depth=depth
        )
        return [r.output_ids for r in _drive(eng, [
            eng.submit(GenRequest(
                prompt_ids=p, max_tokens=6, temperature=0.0, stop_ids=()
            ))
            for p in prompts
        ])]

    alone = serve(prompt(11, 40))
    assert len(alone[0]) == 6
    assert serve(prompt(9), prompt(11, 40))[1] == alone[0]


def test_the_depth_caps_what_is_in_flight_through_admissions(models):
    """An admission's first token is an entry of the fetch line beside
    the step's: with the device setting the pace (no entry ready before
    it is waited for) the line is back at the depth after every step,
    where one fetch a step let it grow by one with every admission and
    put each new prefill behind all of it."""
    cfg, params = models["dense"]
    eng = LLMEngine(
        cfg, params, max_slots=4, max_seq_len=64, pipeline_depth=2
    )
    eng._entry_ready = lambda entry: False
    reqs, longest = [], 0
    try:
        for n in range(40):
            if n in (0, 3):     # one alone, then a burst of three
                reqs += [
                    eng.submit(GenRequest(
                        prompt_ids=prompt(9, n + i), max_tokens=24,
                        temperature=0.0, stop_ids=(),
                    ))
                    for i in range(3 if n else 1)
                ]
            eng.step()
            longest = max(longest, len(eng._pending))
    finally:
        eng.stop()
    assert len(reqs) == 4 and all(r.output_ids for r in reqs)
    assert longest == eng.pipeline_depth
