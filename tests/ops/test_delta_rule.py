"""``ops/delta_rule.py``: the chunked form, the one-step update as XLA
operations and the Pallas kernel (interpret mode), each against the
recurrence taken one position at a time, at widths that are no tile's
(4 heads, key width 6, value width 12) and with ``beta`` on both sides
of 1."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.ops.delta_rule import (
    delta_chunk_scan,
    delta_recurrence,
    delta_state_update,
    delta_step_xla,
    heads_a_group,
    state_heads,
    state_layout,
)

B, H, DK, DV = 2, 4, 6, 12


def recurrence(q, k, v, g, beta, h0):
    """``S_t = a_t S_{t-1} + k_t (outer) beta_t (v_t - (a_t S_{t-1})^T
    k_t)``, ``o_t = S_t^T q_t``, written out in NumPy float64: what
    ``delta_recurrence`` (the other forms' yardstick) is itself held to."""
    q, k, v, g, beta, S = (
        np.asarray(a, np.float64) for a in (q, k, v, g, beta, h0)
    )
    out = []
    for t in range(q.shape[1]):
        S = np.exp(g[:, t])[..., None, None] * S
        u = beta[:, t][..., None] * (
            v[:, t] - np.einsum("bhkv,bhk->bhv", S, k[:, t])
        )
        S = S + k[:, t][..., :, None] * u[..., None, :]
        out.append(np.einsum("bhkv,bhk->bhv", S, q[:, t]))
    return np.stack(out, 1), S


def draw(T, seed=0, heads=H, dk=DK, dv=DV):
    key = jax.random.split(jax.random.key(seed), 6)
    k = jax.random.normal(key[1], (B, T, heads, dk))
    return (
        jax.random.normal(key[0], (B, T, heads, dk)) * dk ** -0.5,
        k / jnp.linalg.norm(k, axis=-1, keepdims=True),
        jax.random.normal(key[2], (B, T, heads, dv)),
        # log decays of -0.001 .. -1.6, as A in -(0..16) and dt 0.001..0.1
        -jnp.exp(jax.random.uniform(key[3], (B, T, heads), minval=-7, maxval=0.5)),
        # beta over (0, 2): negative eigenvalues on half the draws
        2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(key[4], (B, T, heads))),
        jax.random.normal(key[5], (B, heads, dk, dv)),
    )


def test_the_draws_have_beta_on_both_sides_of_one():
    beta = draw(64)[4]
    assert float(jnp.min(beta)) < 0.5 and float(jnp.max(beta)) > 1.5


def test_the_yardstick_is_the_rule_written_out():
    args = draw(19, seed=1)
    want_o, want_S = recurrence(*args)
    o, S = delta_recurrence(*args)
    np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(S, want_S, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("initial", [False, True], ids=["zeros", "carried"])
@pytest.mark.parametrize(
    "T,chunk", [(37, 16), (48, 16), (5, 16), (65, 64), (23, 2)],
    ids=["ragged", "whole_chunks", "under_a_chunk", "one_over_64",
         "chunks_of_two"],
)
def test_the_chunked_form_is_the_recurrence(T, chunk, initial):
    q, k, v, g, beta, h0 = draw(T, seed=T)
    if not initial:
        h0 = jnp.zeros_like(h0)
    want_o, want_S = delta_recurrence(q, k, v, g, beta, h0)
    o, S = jax.jit(delta_chunk_scan, static_argnums=6)(
        q, k, v, g, beta, h0, chunk
    )
    assert o.dtype == S.dtype == jnp.float32
    np.testing.assert_allclose(o, want_o, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(S, want_S, rtol=2e-4, atol=2e-4)


def test_keys_that_are_alike_do_not_cost_the_solve_its_digits():
    """Keys with a common part (what ``silu`` leaves, and any key width
    under the chunk's 64) make ``A``'s powers grow to 1e15 before they
    cancel; the inverse itself stays moderate, and the solve forms that."""
    T = 128
    q, k, v, g, beta, h0 = draw(T, seed=11)
    k = k + 1.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jnp.full_like(beta, 1.9)
    g = jnp.full_like(g, -0.01)
    want_o, want_S = recurrence(q, k, v, g, beta, h0)
    o, S = delta_chunk_scan(q, k, v, g, beta, h0, 64)
    np.testing.assert_allclose(o, want_o, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(S, want_S, rtol=2e-3, atol=2e-3)


def test_a_chunk_is_a_power_of_two():
    q, k, v, g, beta, h0 = draw(12)
    with pytest.raises(ValueError, match="no power of two"):
        delta_chunk_scan(q, k, v, g, beta, h0, 6)


def test_a_padded_tail_moves_no_state_and_adds_nothing():
    """How a padded prefill keeps its padding out of the state: ``g = 0``
    and ``beta = 0`` there."""
    T, n = 40, 27
    q, k, v, g, beta, h0 = draw(T, seed=3)
    g, beta = g.at[:, n:].set(0.0), beta.at[:, n:].set(0.0)
    o, S = delta_chunk_scan(q, k, v, g, beta, h0, 16)
    o_n, S_n = delta_chunk_scan(
        q[:, :n], k[:, :n], v[:, :n], g[:, :n], beta[:, :n], h0, 16
    )
    np.testing.assert_allclose(S, S_n, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(o[:, :n], o_n, rtol=1e-5, atol=1e-5)


def test_the_stored_layout_puts_every_head_s_values_side_by_side():
    S = jax.random.normal(jax.random.key(0), (3, B, H, DK, DV))
    stored = state_layout(S)
    assert stored.shape == (3, B, DK, H * DV)
    np.testing.assert_array_equal(
        stored[1, 0, :, 2 * DV:3 * DV], S[1, 0, 2]
    )
    np.testing.assert_array_equal(state_heads(stored, H), S)


@pytest.mark.parametrize(
    "heads,width,want", [(30, 192, 2), (4, 12, 4), (8, 128, 1), (6, 64, 2)],
)
def test_a_group_of_heads_is_whole_lane_tiles_or_every_head(
    heads, width, want
):
    assert heads_a_group(heads, width) == want


@pytest.mark.parametrize(
    "heads,dk,dv", [(4, 6, 12), (4, 8, 64)],
    ids=["every_head_a_group", "two_heads_a_group"],
)
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_one_step_moves_its_layer_of_the_stacked_state(form, heads, dk, dv):
    q, k, v, g, beta, _ = draw(1, seed=9, heads=heads, dk=dk, dv=dv)
    state = state_layout(
        jax.random.normal(jax.random.key(4), (3, B, heads, dk, dv))
    )
    layer = jnp.int32(1)
    want_o, want_S = delta_recurrence(
        q, k, v, g, beta, state_heads(state[1], heads)
    )
    args = (state, layer, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    if form == "xla":
        o, new = jax.jit(delta_step_xla)(*args)
    else:
        o, new = jax.jit(delta_state_update, static_argnames="interpret")(
            *args, jnp.ones((B,), bool), interpret=True
        )
    np.testing.assert_allclose(o, want_o[:, 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        state_heads(new[1], heads), want_S, rtol=1e-5, atol=1e-5
    )
    # the other layers are as they were
    np.testing.assert_array_equal(new[0], state[0])
    np.testing.assert_array_equal(new[2], state[2])


@pytest.mark.parametrize(
    "live", [[True, False, True, False], [False, False, True, True],
             [False, True, False, False]],
    ids=["alternating", "dead_first", "one_live"],
)
def test_the_kernel_leaves_dead_slots_untouched(live):
    slots = len(live)
    key = jax.random.split(jax.random.key(2), 7)
    k = jax.random.normal(key[1], (slots, H, DK))
    q = jax.random.normal(key[0], (slots, H, DK))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(key[2], (slots, H, DV))
    g = -jax.random.uniform(key[3], (slots, H))
    beta = 2.0 * jax.random.uniform(key[4], (slots, H))
    state = jax.random.normal(key[5], (2, slots, DK, H * DV))
    live = jnp.asarray(live)
    want_o, want = delta_step_xla(state, jnp.int32(1), q, k, v, g, beta)
    o, new = delta_state_update(
        state, jnp.int32(1), q, k, v, g, beta, live, interpret=True
    )
    for b in range(slots):
        if live[b]:
            np.testing.assert_allclose(o[b], want_o[b], rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(
                new[1, b], want[1, b], rtol=1e-5, atol=1e-5
            )
        else:
            np.testing.assert_array_equal(o[b], jnp.zeros_like(o[b]))
            np.testing.assert_array_equal(new[1, b], state[1, b])
    np.testing.assert_array_equal(new[0], state[0])


def test_the_kernel_writes_the_stacked_state_where_it_lies():
    """The whole stack is the call's operand and its result, aliased;
    nothing of it is sliced out for the call (the compiled decode program
    is held to no copy of it in ``test_chip_compile.py``)."""
    state = jnp.zeros((2, B, DK, H * DV), jnp.float32)
    q = jnp.zeros((B, H, DK))
    v = jnp.zeros((B, H, DV))
    g = jnp.zeros((B, H))
    jaxpr = jax.make_jaxpr(delta_state_update)(
        state, jnp.int32(0), q, q, v, g, g, jnp.ones((B,), bool)
    )
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert tuple(call.params["input_output_aliases"]) == ((6, 0),)
    assert call.invars[6].aval.shape == state.shape
    assert call.outvars[0].aval.shape == state.shape


def test_the_chunked_form_then_steps_is_one_sequence():
    """A prefill's chunked scan, its state into the stored layout, then
    decode steps through the kernel: the recurrence over all of it."""
    T, steps = 21, 4
    q, k, v, g, beta, _ = draw(T + steps, seed=5)
    h0 = jnp.zeros((B, H, DK, DV))
    want_o, want_S = delta_recurrence(q, k, v, g, beta, h0)
    o, S = delta_chunk_scan(
        q[:, :T], k[:, :T], v[:, :T], g[:, :T], beta[:, :T], h0, 8
    )
    state = state_layout(S)[None]
    outs = [o]
    for t in range(T, T + steps):
        o_t, state = delta_state_update(
            state, jnp.int32(0), q[:, t], k[:, t], v[:, t], g[:, t],
            beta[:, t], jnp.ones((B,), bool), interpret=True,
        )
        outs.append(o_t[:, None])
    np.testing.assert_allclose(
        jnp.concatenate(outs, 1), want_o, rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        state_heads(state[0], H), want_S, rtol=2e-4, atol=2e-4
    )


# ---- a decay a key channel (KDA): ``g [B, T, H, Dk]`` ----

def recurrence_a_channel(q, k, v, g, beta, h0):
    """:func:`recurrence` with ``S_t = diag(a_t) S_{t-1} + ...``, a
    decay a key channel, in NumPy float64."""
    q, k, v, g, beta, S = (
        np.asarray(a, np.float64) for a in (q, k, v, g, beta, h0)
    )
    out = []
    for t in range(q.shape[1]):
        S = np.exp(g[:, t])[..., None] * S
        u = beta[:, t][..., None] * (
            v[:, t] - np.einsum("bhkv,bhk->bhv", S, k[:, t])
        )
        S = S + k[:, t][..., :, None] * u[..., None, :]
        out.append(np.einsum("bhkv,bhk->bhv", S, q[:, t]))
    return np.stack(out, 1), S


def draw_a_channel(T, decay="mild", seed=0, heads=H, dk=DK, dv=DV):
    """:func:`draw` with a log decay a key channel: ``"mild"`` -0.001 ..
    -1.6 as the mixer draws them, ``"strong"`` -2 a position on every
    channel (over a chunk of 64 the running sum passes -88, where the
    factored form's ``exp(-G)`` is no float32), ``"mixed"`` a strong
    channel beside one that never forgets."""
    q, k, v, _, beta, h0 = draw(T, seed, heads, dk, dv)
    key = jax.random.key(seed + 100)
    shape = (B, T, heads, dk)
    if decay == "mild":
        g = -jnp.exp(jax.random.uniform(key, shape, minval=-7, maxval=0.5))
    elif decay == "strong":
        g = jnp.full(shape, -2.0)
    else:
        g = jnp.where(jnp.arange(dk) % 2 == 0, -2.0, -1e-4) * jnp.ones(shape)
    return q, k, v, g, beta, h0


def test_the_yardstick_takes_a_decay_a_channel():
    args = draw_a_channel(37)
    o, last = delta_recurrence(*args)
    want_o, want_last = recurrence_a_channel(*args)
    np.testing.assert_allclose(o, want_o, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(last, want_last, rtol=2e-5, atol=2e-5)
    # and a decay a channel whose channels are alike is a decay a head
    q, k, v, g, beta, h0 = draw(37)
    wide = jnp.broadcast_to(g[..., None], g.shape + (DK,))
    np.testing.assert_allclose(
        delta_recurrence(q, k, v, wide, beta, h0)[0],
        delta_recurrence(q, k, v, g, beta, h0)[0], rtol=1e-6, atol=1e-6,
    )


@pytest.mark.parametrize("decay", ["mild", "strong", "mixed"])
@pytest.mark.parametrize("initial", [False, True], ids=["zeros", "carried"])
@pytest.mark.parametrize(
    "T,chunk",
    [(64, 64), (128, 64), (150, 64), (37, 16), (96, 32)],
    ids=["one_chunk", "two_chunks", "a_ragged_tail", "one_sub_block",
         "two_sub_blocks"],
)
def test_the_chunked_form_takes_a_decay_a_channel(T, chunk, initial, decay):
    """Nothing non-finite and the recurrence's numbers, the overflow
    case included: -2 a position over a whole chunk."""
    q, k, v, g, beta, h0 = draw_a_channel(T, decay)
    if not initial:
        h0 = jnp.zeros_like(h0)
    o, last = jax.jit(delta_chunk_scan, static_argnames="chunk")(
        q, k, v, g, beta, h0, chunk=chunk
    )
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(last)))
    want_o, want_last = recurrence_a_channel(q, k, v, g, beta, h0)
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(last, want_last, rtol=1e-4, atol=1e-4)


def test_the_factored_form_would_overflow_where_the_sub_blocks_do_not():
    """What the sub-block rule is for: at -2 a position the running sum
    over a chunk of 64 reaches -128, and ``exp(128)`` is no float32."""
    g = draw_a_channel(64, "strong")[3]
    cum = jnp.cumsum(g, axis=1)
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(-cum))))


@pytest.mark.parametrize("decay", ["mild", "strong"])
def test_a_padded_tail_moves_no_state_with_a_decay_a_channel(decay):
    q, k, v, g, beta, h0 = draw_a_channel(80, decay)
    real = jnp.arange(80) < 53
    g = jnp.where(real[None, :, None, None], g, 0.0)
    beta = jnp.where(real[None, :, None], beta, 0.0)
    _, last = delta_chunk_scan(q, k, v, g, beta, h0)
    _, want = recurrence_a_channel(
        q[:, :53], k[:, :53], v[:, :53], g[:, :53], beta[:, :53], h0
    )
    np.testing.assert_allclose(last, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("decay", ["mild", "strong"])
@pytest.mark.parametrize(
    "heads,dk,dv", [(H, DK, DV), (4, 16, 128), (2, 8, 192)],
    ids=["no_tile_s_widths", "a_head_a_lane_tile", "two_heads_three_tiles"],
)
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_one_step_takes_a_decay_a_channel(form, heads, dk, dv, decay):
    """Both one-step forms against the recurrence; the kernel (interpret
    mode) multiplies each sublane row of each head's lanes by its own
    number, moves the live slots only and leaves the other layers."""
    q, k, v, g, beta, h0 = draw_a_channel(1, decay, 3, heads, dk, dv)
    L, layer = 3, 1
    stored = jnp.full((L, B, dk, heads * dv), 5.0).at[layer].set(
        state_layout(h0)
    )
    step = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    if form == "xla":
        o, new = delta_step_xla(stored, jnp.int32(layer), *step)
        live = np.ones(B, bool)
    else:
        live = np.array([True, False])
        o, new = delta_state_update(
            stored, jnp.int32(layer), *step, jnp.asarray(live),
            interpret=True,
        )
    want_o, want_last = recurrence_a_channel(q, k, v, g, beta, h0)
    got = state_heads(new[layer], heads)
    for b in range(B):
        if live[b]:
            np.testing.assert_allclose(
                o[b], want_o[b, 0], rtol=2e-5, atol=2e-5
            )
            np.testing.assert_allclose(
                got[b], want_last[b], rtol=2e-5, atol=2e-5
            )
        else:
            np.testing.assert_array_equal(o[b], 0.0)
            np.testing.assert_array_equal(got[b], h0[b])
    np.testing.assert_array_equal(new[0], 5.0)
    np.testing.assert_array_equal(new[2], 5.0)


def test_the_call_is_named_by_the_decay_s_shape():
    """``delta_state_update`` for a decay a head, ``kda_state_update``
    for one a key channel: a device trace tells the two apart, and the
    reader of each finds its own."""
    q, k, v, g, beta, h0 = draw(1)
    stored = state_layout(h0)[None]
    live = jnp.ones((B,), bool)

    # (interpret mode lowers to no custom call: the names are the
    # scopes the call is traced under)
    a_head = jax.make_jaxpr(lambda s: delta_state_update(
        s, jnp.int32(0), q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
        live, interpret=True,
    ))(stored)
    a_channel = jax.make_jaxpr(lambda s: delta_state_update(
        s, jnp.int32(0), q[:, 0], k[:, 0], v[:, 0],
        jnp.broadcast_to(g[:, 0, :, None], (B, H, DK)), beta[:, 0],
        live, interpret=True,
    ))(stored)
    assert "delta_state_update" in str(a_head)
    assert "kda_state_update" not in str(a_head)
    assert "kda_state_update" in str(a_channel)
    assert "delta_state_update" not in str(a_channel)


def test_the_chunked_form_then_steps_is_one_sequence_with_a_decay_a_channel():
    q, k, v, g, beta, h0 = draw_a_channel(70)
    want_o, want_last = recurrence_a_channel(q, k, v, g, beta, h0)
    o, last = delta_chunk_scan(
        q[:, :66], k[:, :66], v[:, :66], g[:, :66], beta[:, :66], h0
    )
    stored = state_layout(last)[None]
    outs = [o]
    for t in range(66, 70):
        o_t, stored = delta_state_update(
            stored, jnp.int32(0), q[:, t], k[:, t], v[:, t], g[:, t],
            beta[:, t], jnp.ones((B,), bool), interpret=True,
        )
        outs.append(o_t[:, None])
    np.testing.assert_allclose(
        jnp.concatenate(outs, 1), want_o, rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        state_heads(stored[0], H), want_last, rtol=1e-4, atol=1e-4
    )
