"""DecAvg gossip: path equivalence (dense / pallas / shard_map), consensus
contraction, fixed points — the system invariants behind the paper's Eq. 1."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import decavg as D
from repro.core import mixing as M
from repro.core import topology as T


def _setup(n=24, seed=0, dtype=jnp.float32):
    g = T.erdos_renyi(n, 0.3, seed=seed)
    w = jnp.asarray(M.decavg_matrix(g, np.ones(n)), jnp.float32)
    key = jax.random.PRNGKey(seed)
    params = {
        "a": jax.random.normal(key, (n, 17, 3)).astype(dtype),
        "b": {"w": jax.random.normal(jax.random.fold_in(key, 1), (n, 41)).astype(dtype)},
    }
    return g, w, params


class TestEquivalence:
    def test_dense_vs_pallas(self):
        _, w, params = _setup()
        dense = D.mix_dense(w, params)
        pallas = D.mix_pallas(w, params)
        for dl, pl_ in zip(jax.tree.leaves(dense), jax.tree.leaves(pallas)):
            np.testing.assert_allclose(np.asarray(dl), np.asarray(pl_), rtol=3e-5, atol=3e-5)

    def test_bf16_dense_mixing_tolerance(self):
        """Pin the dense path's precision contract (module docstring): it
        accumulates in the LEAF dtype, so bf16 mixing tracks the f32
        reference only to bf16 resolution — while f32 inputs are exact."""
        _, w, params32 = _setup()
        ref = D.mix_dense(w, params32)
        params16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params32)
        out16 = D.mix_dense(w, params16)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out16)):
            assert b.dtype == jnp.bfloat16  # cast back to the leaf dtype
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b, dtype=np.float32),
                rtol=0.05, atol=0.05,
            )
        # f32 leaves really do take the tight path
        out32 = D.mix_dense(w, params32)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out32)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    def test_per_call_backend_override_does_not_mutate_engine(self):
        """mix(backend=...) is call-local: it must not change the engine's
        resolved backend, mesh, or the cached layouts its own backend uses."""
        g, w, params = _setup()
        e = D.GossipEngine(g, backend="dense")
        assert e.mesh is None and e.backend == "dense"
        want = D.mix_dense(e.w, params)
        for override in ("sparse", "sparse_sharded", "pallas"):
            got = e.mix(params, backend=override)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=3e-5, atol=3e-5,
                    err_msg=override,
                )
            # sparse_sharded builds a call-local default mesh; none of the
            # overrides may leak into the engine's capability surface
            assert e.mesh is None and e.backend == "dense", override
        got = e.mix(params)  # the engine's own backend still works after
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    def test_halo_schedule_validated(self):
        g, _, _ = _setup()
        with pytest.raises(ValueError, match="halo_schedule"):
            D.GossipEngine(g, halo_schedule="spiral")

    def test_dense_vs_shardmap_subprocess(self):
        """shard_map schedules need >1 device: run with 8 fake CPU devices."""
        code = textwrap.dedent(
            """
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp, numpy as np
            from repro.core import decavg as D, mixing as M, topology as T
            g = T.erdos_renyi(16, 0.4, seed=0)
            w = jnp.asarray(M.decavg_matrix(g, np.ones(16)), jnp.float32)
            params = {"a": jax.random.normal(jax.random.PRNGKey(0), (16, 33, 2))}
            mesh = jax.make_mesh((4, 2), ("data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 2)
            dense = D.mix_dense(w, params)
            for sched in ("allgather", "reduce_scatter"):
                out = D.mix_sharded(w, params, mesh=mesh, node_axis="data", schedule=sched)
                np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(dense["a"]),
                                           rtol=1e-5, atol=1e-5)
            print("OK")
            """
        )
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True, timeout=300
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert "OK" in r.stdout


class TestGossipDynamics:
    def test_row_stochastic_fixed_point(self):
        """Identical node models are a fixed point of any valid mixing."""
        _, w, _ = _setup()
        n = w.shape[0]
        same = {"x": jnp.broadcast_to(jnp.arange(7.0), (n, 7))}
        out = D.mix_dense(w, same)
        np.testing.assert_allclose(np.asarray(out["x"]), np.asarray(same["x"]), rtol=1e-5)

    def test_consensus_contraction(self):
        """gossip_error strictly decreases round over round on a connected
        graph — the spectral-gap mechanism the paper's results rest on."""
        g, w, params = _setup(n=30, seed=1)
        assert T.connected_components(g.adj).max() == 0
        errs = [float(D.gossip_error(params))]
        for _ in range(5):
            params = D.mix_dense(w, params)
            errs.append(float(D.gossip_error(params)))
        assert all(b < a for a, b in zip(errs, errs[1:]))
        assert errs[-1] < 0.1 * errs[0]

    def test_disconnected_no_global_consensus(self):
        """Two components never agree: 'weak connectivity spreads information
        but zero connectivity spreads nothing' (paper §1, inverted)."""
        adj = np.zeros((8, 8), dtype=bool)
        for i, j in [(0, 1), (1, 2), (2, 3), (4, 5), (5, 6), (6, 7)]:
            adj[i, j] = adj[j, i] = True
        g = T.Graph(adj=adj)
        w = jnp.asarray(M.decavg_matrix(g, np.ones(8)), jnp.float32)
        params = {"x": jnp.concatenate([jnp.zeros((4, 5)), jnp.ones((4, 5))])}
        for _ in range(200):
            params = D.mix_dense(w, params)
        x = np.asarray(params["x"])
        assert np.allclose(x[:4], 0.0, atol=1e-4)
        assert np.allclose(x[4:], 1.0, atol=1e-4)

    @given(st.integers(6, 30), st.integers(0, 10**4))
    @settings(max_examples=10, deadline=None)
    def test_mean_preserved_by_mh(self, n, seed):
        """Doubly-stochastic (MH) gossip preserves the global average."""
        g = T.erdos_renyi(n, 0.5, seed=seed)
        w = jnp.asarray(M.metropolis_hastings_matrix(g), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(seed), (n, 9))
        mixed = D.mix_dense(w, {"x": x})["x"]
        np.testing.assert_allclose(
            np.asarray(mixed.mean(0)), np.asarray(x.mean(0)), rtol=2e-4, atol=2e-5
        )
