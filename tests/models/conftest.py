"""What several model test files share."""

import jax
import pytest


@pytest.fixture
def with_and_without_the_barrier(monkeypatch):
    """``run(make_step, *args, barriers=1) -> (got, want)``: the jitted
    ``make_step()`` on ``args`` as the tree has it, and with no barrier
    in it: the attention's projections handed on as they were before
    PR 47 (``transformer.finish_products``), and a hybrid's stacked conv
    rows not tied to a layer's input (``hybrid.rows_read_a_layer``,
    PR 64). ``make_step`` builds a new function each call, since JAX
    keeps a trace by its function; the tree's form is held to
    ``barriers`` barriers in its jaxpr, the other to none."""
    from gpustack_tpu.models import hybrid, transformer

    def count(step, args):
        return str(jax.make_jaxpr(step)(*args)).count("optimization_barrier")

    def run(make_step, *args, barriers=1):
        assert count(make_step(), args) == barriers
        got = jax.jit(make_step())(*args)
        monkeypatch.setattr(
            transformer, "finish_products", lambda decode, *products: products
        )
        monkeypatch.setattr(hybrid, "rows_read_a_layer", lambda conv, x: conv)
        assert count(make_step(), args) == 0
        return got, jax.jit(make_step())(*args)

    return run
