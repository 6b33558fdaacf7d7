"""The benchmark's copies of the input generators equal the program's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import inputs
from repro.core import faults, mixing, partition, topology
from repro.data import synthetic
from repro.data.loader import round_batch_indices
from repro.models.mlp import init_mlp

SEEDS = (0, 2**31 + 17)


@pytest.mark.parametrize("seed", SEEDS)
def test_make_mnist_like(seed):
    ds = synthetic.make_mnist_like(train_per_class=30, test_per_class=7, seed=seed)
    got = inputs.make_mnist_like(train_per_class=30, test_per_class=7, seed=seed)
    for a, b in zip(got, (ds.x_train, ds.y_train, ds.x_test, ds.y_test)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("seed", SEEDS)
def test_barabasi_albert(seed):
    g = topology.barabasi_albert(100, 2, seed=seed)
    np.testing.assert_array_equal(inputs.barabasi_albert(100, 2, seed=seed), g.adj)


@pytest.mark.parametrize("seed", SEEDS)
def test_hub_focused(seed):
    s = inputs.derive_seed(seed)
    g = topology.barabasi_albert(100, 2, seed=s)
    _, y, _, _ = inputs.make_mnist_like(seed=s)
    want = partition.hub_focused(y, g, seed=s)
    got = inputs.hub_focused(y, g.adj, seed=s)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_decavg_matrix():
    g = topology.barabasi_albert(60, 2, seed=3)
    sizes = np.arange(60) % 7 + 1
    np.testing.assert_allclose(
        inputs.decavg_matrix(g.adj, sizes), mixing.decavg_matrix(g, sizes), rtol=0, atol=1e-15)


@pytest.mark.parametrize("seed", SEEDS)
def test_churn_alive(seed):
    spec = "churn:p_leave=0.05,p_join=0.5@targeted=hubs"
    sched = topology.make_schedule("ba:n=100,m=2", seed=1)
    adj = sched.graph_at(0).adj
    trace = faults.FaultTrace(spec, sched, seed=inputs.derive_seed(seed))
    got = inputs.churn_alive(adj, 40, seed=inputs.derive_seed(seed), **inputs.parse_churn(spec))
    np.testing.assert_array_equal(got, trace.alive_matrix(40))
    assert not got.all()


def test_batch_sampler_and_init():
    key = jax.random.PRNGKey(5)
    sizes = jnp.asarray([3, 9, 27, 1], jnp.int32)
    np.testing.assert_array_equal(
        inputs.round_batch_indices(key, 4, 2, 8, sizes), round_batch_indices(key, 4, 2, 8, sizes))
    want = init_mlp(jax.random.PRNGKey(9))
    got = inputs.init_mlp(jax.random.PRNGKey(9), [784, 512, 256, 128, 10])
    for (w, b), layer in zip(got, want["layers"]):
        np.testing.assert_array_equal(w, layer["w"])
        np.testing.assert_array_equal(b, layer["b"])


def test_derive_seed_is_31_bits_and_stable():
    assert inputs.derive_seed(2**31 + 5) == inputs.derive_seed(2**31 + 5)
    assert all(0 <= inputs.derive_seed(s) < 2**31 for s in (0, 1, 2**31 + 5, 2**40))
    assert inputs.derive_seed(7) != inputs.derive_seed(2**32 + 7)
