"""The window's compile counter catches a program built inside it."""

import jax
import jax.numpy as jnp

import run


def test_counter_sees_a_shape_change_and_nothing_else():
    counter = run.CompileCounter()
    f = jax.jit(lambda x: jnp.sin(x) * 2)
    f(jnp.ones(8)).block_until_ready()  # warm-up, outside the window
    with counter.watch():
        f(jnp.ones(8)).block_until_ready()
    assert counter.count == 0
    with counter.watch():
        f(jnp.ones(9)).block_until_ready()  # new shape: a new program
    assert counter.count >= 1
    seen = counter.count
    f(jnp.ones(10)).block_until_ready()  # outside the window: not counted
    assert counter.count == seen
