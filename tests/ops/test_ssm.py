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
