"""Cross-backend differential suite: every GossipEngine mixing backend must
compute the same DecAvg round as the dense reference, on every topology
family shape class, for ragged pytrees — and preserve consensus fixed points.

This is the lockdown for the sparse/scale paths: one parametrized matrix
over backends x topologies x pytree shapes, plus subprocess runs with 8 fake
CPU devices for the genuinely multi-device backends (sparse_sharded with
real cross-shard halos, permute, both dense sharded schedules) and the
permute x TopologySchedule recolor-per-period regression.
"""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import decavg as D
from repro.core import mixing as M
from repro.core import sparse as S
from repro.core import topology as T

N = 24
TOPOLOGIES = [
    f"ring:n={N}",
    f"star:n={N}",
    f"ws:n={N},k=4,beta=0.2",
    "caveman:cliques=4,size=6",
    "torus:rows=4,cols=6",
]
# Backends runnable in-process on any jax backend (sparse_sharded builds its
# default 1-device mesh; the >1-shard halo path runs in the subprocess test).
# The sparse_sharded+ring entry pins the degenerate local-copy-only ring.
BACKENDS = ["dense", "pallas", "sparse", "sparse_pallas", "sparse_sharded",
            "sparse_sharded+ring"]

PYTREES = {
    "ragged": lambda n, key: {
        "a": jax.random.normal(key, (n, 13, 2)),
        "b": {"w": jax.random.normal(jax.random.fold_in(key, 1), (n, 41))},
    },
    "odd": lambda n, key: {
        "x": jax.random.normal(key, (n, 1)),
        "y": jax.random.normal(jax.random.fold_in(key, 2), (n, 129)),
        "z": jax.random.normal(jax.random.fold_in(key, 3), (n, 5, 3, 2)),
    },
}


def _engine(spec: str, backend: str) -> D.GossipEngine:
    n = T.make(spec, seed=2).num_nodes
    backend, _, halo = backend.partition("+")
    return D.GossipEngine(
        spec, backend=backend, seed=2,
        halo_schedule=halo or "auto",
        data_sizes=np.arange(1, n + 1, dtype=np.float64),
    )


@pytest.mark.parametrize("pytree", sorted(PYTREES))
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("spec", TOPOLOGIES)
def test_backend_matches_dense_reference(spec, backend, pytree):
    e = _engine(spec, backend)
    params = PYTREES[pytree](e.num_nodes, jax.random.PRNGKey(7))
    want = D.mix_dense(e.w, params)
    got = e.mix(params)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-5, atol=3e-5,
            err_msg=f"{backend} vs dense on {spec} ({pytree})",
        )


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("spec", TOPOLOGIES)
def test_fixed_point_preserved(spec, backend):
    """Consensus state (all nodes identical) is invariant under one round of
    any backend — W is row-stochastic, so W @ (1 x c^T) == 1 x c^T."""
    e = _engine(spec, backend)
    n = e.num_nodes
    params = {
        "a": jnp.broadcast_to(jnp.arange(13.0 * 2).reshape(13, 2), (n, 13, 2)),
        "b": {"w": jnp.broadcast_to(jnp.linspace(-3.0, 5.0, 41), (n, 41))},
    }
    out = e.mix(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5,
            err_msg=f"{backend} broke the consensus fixed point on {spec}",
        )


# The two extra graphs have N off the 8-row block grid (a ragged last
# block); er:n=150 has 19 source blocks per destination block, so the
# kernel accumulates over two 16-tile grid steps, the rest over one.
@pytest.mark.parametrize("spec", TOPOLOGIES + ["ba:n=30,m=3", "er:n=150,p=0.2"])
def test_blocked_ell_kernel_matches_mix_sparse(spec):
    """Acceptance: the 8-row-blocked ELL kernel matches the segment-sum
    sparse path to 1e-6 (forced through the interpreter off-TPU)."""
    g = T.make(spec, seed=2)
    n = g.num_nodes
    w = M.decavg_matrix(g, np.arange(1, n + 1, dtype=np.float64))
    csr = S.csr_from_dense(w)
    params = PYTREES["ragged"](n, jax.random.PRNGKey(9))
    want = S.mix_sparse(csr, params)
    got = S.mix_sparse_pallas(csr, params, blocked=True, interpret=True)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6,
            err_msg=f"blocked ELL vs mix_sparse on {spec}",
        )


def test_block_ell_layout_invariants():
    """Blocked layout reconstructs W exactly; padding is lane-aligned."""
    g = T.make("ba:n=30,m=3", seed=0)
    w = M.decavg_matrix(g, np.ones(30))
    csr = S.csr_from_dense(w)
    bell = S.block_ell_from_csr(csr)
    assert bell.n == 30 and bell.num_blocks == 4  # ceil(30 / 8)
    assert bell.max_blocks_per_row % 16 == 0  # lane padding
    assert bell.val.shape == (bell.num_blocks * 8, bell.max_blocks_per_row * 8)
    rec = np.zeros((bell.num_blocks * 8, bell.num_blocks * 8), np.float32)
    for b in range(bell.num_blocks):
        for t in range(bell.max_blocks_per_row):
            sb = int(bell.idx[b, t])
            rec[b * 8:(b + 1) * 8, sb * 8:(sb + 1) * 8] += bell.val[
                b * 8:(b + 1) * 8, t * 8:(t + 1) * 8
            ]
    np.testing.assert_allclose(rec[:30, :30], w.astype(np.float32), atol=1e-7)
    assert np.all(rec[30:] == 0.0) and np.all(rec[:, 30:] == 0.0)


def test_shard_csr_layout_invariants():
    """Sharded CSR reconstructs W; halos cover exactly the referenced
    sources; padded entries are weightless and keep segments sorted."""
    g = T.make("ws:n=24,k=4,beta=0.3", seed=5)
    w = M.decavg_matrix(g, np.ones(24))
    csr = S.csr_from_dense(w)
    sh = S.shard_csr(csr, 4)
    assert sh.shards == 4 and sh.rows_per_shard == 6
    rec = np.zeros((24, 24), np.float32)
    for s in range(4):
        halo = np.asarray(sh.halo[s])
        rows = np.asarray(sh.rows[s])
        cols = np.asarray(sh.cols[s])
        vals = np.asarray(sh.values[s])
        assert np.all(np.diff(rows) >= 0), "segment ids must stay sorted"
        assert np.all((rows >= 0) & (rows < 6))
        np.add.at(rec, (rows + s * 6, halo[cols]), vals)
    np.testing.assert_allclose(rec, w.astype(np.float32), atol=1e-7)
    with pytest.raises(ValueError, match="not divisible"):
        S.shard_csr(csr, 5)


def test_sparse_sharded_subprocess_multi_shard():
    """The real halo path: 8 node shards over 8 fake CPU devices, every
    topology in the matrix, both halo schedules (ring ppermute vs allgather,
    allclose to dense at 1e-6 — the acceptance bar), plus both dense sharded
    schedules as a cross-check of the shard_map shim. Halos genuinely span
    several shards here (24 nodes / 8 shards = 3 rows per shard, degree >= 2).
    """
    code = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import decavg as D, mixing as M, sparse as S, topology as T
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("nodes",))
        for spec in {TOPOLOGIES!r}:
            g = T.make(spec, seed=2)
            n = g.num_nodes
            w = M.decavg_matrix(g, np.arange(1, n + 1, dtype=np.float64))
            wj = jnp.asarray(w, jnp.float32)
            csr = S.csr_from_dense(w)
            shcsr = S.shard_csr(csr, 8)
            params = {{"a": jax.random.normal(jax.random.PRNGKey(0), (n, 9, 3)),
                       "b": jax.random.normal(jax.random.PRNGKey(1), (n, 41))}}
            dense = D.mix_dense(wj, params)
            sched_outs = {{
                sched: D.mix_sharded_sparse(shcsr, params, mesh=mesh,
                                            node_axis="nodes",
                                            halo_schedule=sched)
                for sched in ("allgather", "ring", "auto")
            }}
            for sched, out in sched_outs.items():
                for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(out)):
                    np.testing.assert_allclose(
                        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6,
                        err_msg=f"{{spec}} halo_schedule={{sched}}")
            # ring wire never exceeds the allgather's on a sparse graph
            wire = S.halo_wire_bytes(shcsr, 41)
            assert wire["ring"] <= wire["allgather"], (spec, wire)
            for sched in ("allgather", "reduce_scatter"):
                out = D.mix_sharded(wj, params, mesh=mesh,
                                    node_axis="nodes", schedule=sched)
                for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(out)):
                    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                               rtol=2e-5, atol=2e-5, err_msg=spec)
        # ring + p_chunk: the feature-chunked segment-sum consumes the same
        # ring-assembled halo buffer
        g = T.make("ws:n=24,k=4,beta=0.2", seed=2)
        w = M.decavg_matrix(g, np.ones(24))
        shcsr = S.shard_csr(S.csr_from_dense(w), 8)
        params = {{"a": jax.random.normal(jax.random.PRNGKey(3), (24, 131))}}
        dense = D.mix_dense(jnp.asarray(w, jnp.float32), params)
        out = D.mix_sharded_sparse(shcsr, params, mesh=mesh, node_axis="nodes",
                                   p_chunk=32, halo_schedule="ring")
        np.testing.assert_allclose(np.asarray(dense["a"]), np.asarray(out["a"]),
                                   rtol=1e-6, atol=1e-6)
        print("OK")
        """
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=500
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_sparse_sharded_ring_time_varying_subprocess():
    """GossipEngine(sparse_sharded, halo_schedule=ring) tracks a @rewire
    schedule: the per-period ShardedCSR (peer metadata included) is rebuilt
    at period boundaries and every round still matches dense mixing."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import decavg as D, topology as T
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("nodes",))
        e = D.GossipEngine("ws:n=24,k=4,beta=0.3@rewire=2", backend="sparse_sharded",
                           halo_schedule="ring", mesh=mesh, node_axis="nodes", seed=4)
        params = {"a": jax.random.normal(jax.random.PRNGKey(5), (24, 7, 2))}
        seen = set()
        for r in range(6):
            out = e.mix(params, round=r)
            want = D.mix_dense(e.w, params)  # refreshed for round r by mix()
            np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(want["a"]),
                                       rtol=1e-6, atol=1e-6, err_msg=f"round {r}")
            seen.add(bytes(np.asarray(e.w).tobytes()))
        assert len(seen) == 3, len(seen)  # rewire=2 over 6 rounds -> 3 periods
        print("OK")
        """
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=500
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_permute_schedule_recolor_subprocess():
    """Regression: permute + @rewire schedule equals dense mixing at every
    round boundary, and colorings are computed once per period (counter)."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import decavg as D, mixing as M, topology as T
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("nodes",))
        calls = []
        orig = M.edge_coloring
        M.edge_coloring = lambda g: (calls.append(1), orig(g))[1]
        e = D.GossipEngine("er:n=8,p=0.5@rewire=2", backend="permute",
                           mesh=mesh, node_axis="nodes", seed=3)
        params = {"a": jax.random.normal(jax.random.PRNGKey(2), (8, 7, 2))}
        for r in range(6):
            out = e.mix(params, round=r)
            want = D.mix_dense(e.w, params)  # W refreshed for round r by mix()
            np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(want["a"]),
                                       rtol=2e-5, atol=2e-5, err_msg=f"round {r}")
        # periods 0, 1, 2 -> exactly 3 colorings; re-mixing inside a period
        # must reuse the cached one.
        assert len(calls) == 3, calls
        e.mix(params, round=5)
        assert len(calls) == 3, calls
        # a static permute engine on the same mesh still works (n == |axis|)
        e2 = D.GossipEngine("ring:n=8", backend="permute", mesh=mesh,
                            node_axis="nodes", seed=0)
        out2 = e2.mix(params, round=0)
        want2 = D.mix_dense(e2.w, params)
        np.testing.assert_allclose(np.asarray(out2["a"]), np.asarray(want2["a"]),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
        """
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=500
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
