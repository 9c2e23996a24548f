"""``ops/ssm.py``: the chunked scan, the one-step update as XLA
operations and the Pallas kernel (interpret mode), each against the
recurrence taken one position at a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpustack_tpu.ops.ssm import (
    ssm_chunk_scan,
    ssm_state_update,
    ssm_step_xla,
)

B, H, P, G, N = 2, 8, 16, 2, 32


def recurrence(x, dt, A, Bm, Cm, h0):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t``, ``y_t = S_t
    C_t``, a position at a time."""
    r = x.shape[2] // Bm.shape[2]
    h, ys = h0, []
    for t in range(x.shape[1]):
        Bh = jnp.repeat(Bm[:, t], r, axis=1)
        Ch = jnp.repeat(Cm[:, t], r, axis=1)
        h = (
            jnp.exp(dt[:, t] * A)[..., None, None] * h
            + (dt[:, t, :, None] * x[:, t])[..., None] * Bh[:, :, None, :]
        )
        ys.append(jnp.einsum("bhpn,bhn->bhp", h, Ch))
    return jnp.stack(ys, 1), h


def draw(T, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    return (
        jax.random.normal(k[0], (B, T, H, P)),
        jax.nn.softplus(jax.random.normal(k[1], (B, T, H))),
        -jnp.exp(jax.random.normal(k[2], (H,))),
        jax.random.normal(k[3], (B, T, G, N)),
        jax.random.normal(k[4], (B, T, G, N)),
        jax.random.normal(k[5], (B, H, P, N)),
    )


@pytest.mark.parametrize("initial", [False, True], ids=["zeros", "initial"])
@pytest.mark.parametrize(
    "T,chunk", [(37, 16), (48, 16), (5, 16), (33, 32)],
    ids=["ragged", "whole_chunks", "under_a_chunk", "one_over"],
)
def test_the_chunked_scan_is_the_recurrence(T, chunk, initial):
    x, dt, A, Bm, Cm, h0 = draw(T, seed=T)
    if not initial:
        h0 = jnp.zeros_like(h0)
    want_y, want_h = recurrence(x, dt, A, Bm, Cm, h0)
    y, h = jax.jit(ssm_chunk_scan, static_argnums=6)(
        x, dt, A, Bm, Cm, h0, chunk
    )
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h, want_h, rtol=2e-4, atol=2e-4)


def test_a_position_with_dt_zero_moves_no_state_and_adds_nothing():
    """How a padded prefill keeps its padding out of the state."""
    T, n = 40, 27
    x, dt, A, Bm, Cm, h0 = draw(T, seed=3)
    dt = dt.at[:, n:].set(0.0)
    y, h = ssm_chunk_scan(x, dt, A, Bm, Cm, h0, 16)
    y_n, h_n = ssm_chunk_scan(
        x[:, :n], dt[:, :n], A, Bm[:, :n], Cm[:, :n], h0, 16
    )
    np.testing.assert_allclose(h, h_n, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y[:, :n], y_n, rtol=1e-5, atol=1e-5)


# (H, P, G, N): the small shape, and the benchmark's hybrid cell's own
SMALL = (H, P, G, N)
CELL = (64, 64, 8, 128)


def draw_step(shape, slots, layers, seed, spread=0.0):
    """One decode step's operands at ``shape`` and a stacked state;
    ``spread`` > 0 scales the state's entries by ``10 ** (spread * z)``,
    so that large ones lie beside small ones."""
    h, p, g, n = shape
    k = jax.random.split(jax.random.key(seed), 8)
    state = jax.random.normal(k[5], (layers, slots, h, p, n))
    if spread:
        state = state * 10.0 ** (
            spread * jax.random.normal(k[6], state.shape)
        )
    return (
        state,
        jax.random.normal(k[0], (slots, h, p)),
        jax.nn.softplus(jax.random.normal(k[1], (slots, h))),
        -jnp.exp(jax.random.normal(k[2], (h,))),
        jax.random.normal(k[3], (slots, g, n)),
        jax.random.normal(k[4], (slots, g, n)),
    )


def one_step(state, x, dt, A, Bm, Cm):
    """The recurrence's one position in float64 on the host: ``(y,
    new state, the size of the two terms a new entry is the sum of)`` of
    one layer's ``state [B, H, P, N]``."""
    f = np.float64
    state, x, dt, A, Bm, Cm = (
        np.asarray(a, f) for a in (state, x, dt, A, Bm, Cm)
    )
    r = x.shape[1] // Bm.shape[1]
    Bh, Ch = np.repeat(Bm, r, axis=1), np.repeat(Cm, r, axis=1)
    kept = np.exp(dt * A)[..., None, None] * state
    added = (dt[..., None] * x)[..., None] * Bh[:, :, None, :]
    new = kept + added
    return (
        np.einsum("bhpn,bhn->bhp", new, Ch), new,
        np.abs(kept) + np.abs(added),
    )


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_one_step_moves_its_layer_of_the_stacked_state(form):
    x, dt, A, Bm, Cm, _ = draw(1, seed=9)
    state = jax.random.normal(jax.random.key(4), (3, B, H, P, N))
    want_y, want_h = recurrence(x, dt, A, Bm, Cm, state[1])
    args = (state, jnp.int32(1), x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0])
    if form == "xla":
        y, new = ssm_step_xla(*args)
    else:
        y, new = ssm_state_update(
            *args, jnp.ones((B,), bool), interpret=True
        )
    np.testing.assert_allclose(y, want_y[:, 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new[1], want_h, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(new[0], state[0])
    np.testing.assert_array_equal(new[2], state[2])


@pytest.mark.parametrize(
    "shape,live,spread",
    [
        (CELL, [True] * 4, 0.0),
        (CELL, [False, True, True, False], 0.0),
        (CELL, [False] * 4, 0.0),
        ((16, 16, 16, 32), [True] * 4, 0.0),
        ((8, 16, 1, 32), [True] * 4, 0.0),
        ((48, 8, 4, 128), [True, False, True, True], 0.0),
        (CELL, [True] * 4, 2.0),
    ],
    ids=[
        "cell_all_live", "cell_some_live", "cell_nobody_live",
        "a_group_a_head", "one_group", "heads_over_two_tiles_unevenly",
        "cell_large_beside_small",
    ],
)
def test_the_kernel_is_the_recurrence_at_the_cell_s_shape_and_others(
    shape, live, spread
):
    """Interpret mode, 4 slots, 2 layers: ``y`` to 1e-5 and the state to
    1e-6 of the recurrence in float64; a slot nobody holds keeps its
    state and reads zeros; the other layer is not touched. The last case
    has entries of 1e4 beside 1e-4 in one row, what a readout through
    one pass of bfloat16 products would lose; there an entry is held to
    1e-6 of its two terms (they may cancel) and a row's sum to 1e-5 of
    its largest entry besides."""
    state, x, dt, A, Bm, Cm = draw_step(shape, 4, 2, seed=13, spread=spread)
    live = jnp.asarray(live)
    want_y, want_h, terms = one_step(state[1], x, dt, A, Bm, Cm)
    y, new = ssm_state_update(
        state, jnp.int32(1), x, dt, A, Bm, Cm, live, interpret=True
    )
    assert y.shape == x.shape and y.dtype == jnp.float32
    assert new.shape == state.shape and new.dtype == state.dtype
    for b in range(4):
        if live[b]:
            if not spread:
                np.testing.assert_allclose(
                    new[1, b], want_h[b], rtol=1e-6, atol=1e-6
                )
                np.testing.assert_allclose(
                    y[b], want_y[b], rtol=1e-5, atol=1e-5
                )
                continue
            assert (
                np.abs(np.asarray(new[1, b]) - want_h[b])
                <= 1e-6 + 1e-6 * terms[b]
            ).all()
            assert (
                np.abs(np.asarray(y[b]) - want_y[b])
                <= 1e-5 * (1 + np.abs(want_y[b]) + terms[b].max(axis=-1))
            ).all()
        elif live.any():
            np.testing.assert_array_equal(new[1, b], state[1, b])
            assert not np.asarray(y[b]).any()
    np.testing.assert_array_equal(new[0], state[0])


@pytest.mark.parametrize("shape", [SMALL, CELL], ids=["small", "cell"])
def test_a_step_with_dt_zero_leaves_the_state_bit_for_bit(shape):
    """``exp(0) = 1`` and ``0 * x = 0``: a slot whose step carries
    nothing keeps every bit of its state (and the parts ``dt x`` is
    handed over in sum to exactly zero)."""
    state, x, dt, A, Bm, Cm = draw_step(shape, 4, 2, seed=17)
    dt = dt.at[1].set(0.0).at[3].set(0.0)
    _, new = ssm_state_update(
        state, jnp.int32(0), x, dt, A, Bm, Cm, jnp.ones((4,), bool),
        interpret=True,
    )
    for b in (1, 3):
        np.testing.assert_array_equal(new[0, b], state[0, b])
    assert (np.asarray(new[0, 0]) != np.asarray(state[0, 0])).any()


def test_dt_x_comes_back_whole_from_its_three_parts():
    """What the kernel hands the matrix unit: three float32 arrays that
    are each a bfloat16 and sum to the float32 they came from, exactly
    and in any order."""
    from gpustack_tpu.ops.ssm import _bf16_parts

    v = jax.random.normal(jax.random.key(2), (4096,)) * 10.0 ** (
        6 * jax.random.normal(jax.random.key(3), (4096,))
    )
    v = jnp.concatenate([v, jnp.asarray([0.0, -0.0, 1.0, -3.0e38, 1e-30])])
    a, b, c = _bf16_parts(v)
    for part in (a, b, c):
        np.testing.assert_array_equal(
            part, part.astype(jnp.bfloat16).astype(jnp.float32)
        )
    for total in ((a + b) + c, a + (b + c), (a + c) + b):
        np.testing.assert_array_equal(total, v)


@pytest.mark.parametrize(
    "live", [[False, True], [True, False], [False, False]],
    ids=["second", "first", "nobody"],
)
def test_the_kernel_leaves_a_slot_nobody_holds_as_it_was(live):
    x, dt, A, Bm, Cm, _ = draw(1, seed=11)
    state = jax.random.normal(jax.random.key(5), (2, B, H, P, N))
    live = jnp.asarray(live)
    want_y, want_h = recurrence(x, dt, A, Bm, Cm, state[0])
    y, new = ssm_state_update(
        state, jnp.int32(0), x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], live,
        interpret=True,
    )
    for b in range(B):
        if live[b]:
            np.testing.assert_allclose(
                new[0, b], want_h[b], rtol=1e-6, atol=1e-6
            )
            np.testing.assert_allclose(
                y[b], want_y[b, 0], rtol=1e-5, atol=1e-5
            )
        elif live.any():
            # (with nobody live the one named block is written back as
            # it was fetched or not at all: no slot's state means
            # anything then, and the next insert sets it whole)
            np.testing.assert_array_equal(new[0, b], state[0, b])
            assert not np.asarray(y[b]).any()
    np.testing.assert_array_equal(new[1], state[1])
