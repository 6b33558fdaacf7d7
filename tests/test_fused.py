"""Fused single-lax.scan training path vs the Python loop.

The contract under test (ISSUE 5 acceptance): same seed => ``run_fused``
produces params and metrics allclose (1e-6, f32) to ``run`` across
dense/sparse backends, static and ``@rewire`` schedules, and
``gossip_every`` in {0, 1, 3} — plus the satellites riding along: the
round-keyed sampler both paths share, the MixingProgram staging, the
no-re-jit-per-period round closure, and the opt-in gossip compression.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import decavg
from repro.core import partition as P
from repro.data.loader import NodeLoader, round_batch_indices
from repro.models.mlp import init_mlp
from repro.train.trainer import DecentralizedTrainer

N_NODES = 10
DIM = 32


@pytest.fixture(scope="module")
def setup():
    from repro.data.synthetic import make_mnist_like

    ds = make_mnist_like(train_per_class=60, test_per_class=20, dim=DIM, seed=0)
    parts = P.iid(ds.y_train, N_NODES, seed=1)
    return ds, parts


def make_trainer(setup, topology="er:n=10,p=0.5", **kw):
    ds, parts = setup
    loader = NodeLoader(ds.x_train, ds.y_train, parts, batch_size=8, seed=2)
    kw.setdefault("lr", 0.05)
    kw.setdefault("momentum", 0.9)
    return DecentralizedTrainer(topology, loader, seed=0, in_dim=DIM, **kw)


def assert_trees_close(a, b, **kw):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **kw)


def assert_histories_close(ha, hb):
    assert [m.round for m in ha] == [m.round for m in hb]
    for ma, mb in zip(ha, hb):
        np.testing.assert_allclose(ma.per_node_acc, mb.per_node_acc, atol=1e-6)
        assert ma.mean_acc == pytest.approx(mb.mean_acc, abs=1e-6)
        np.testing.assert_allclose(ma.consensus, mb.consensus, rtol=1e-4, atol=1e-5)
        if ma.group_acc is None:
            assert mb.group_acc is None
        else:
            np.testing.assert_allclose(ma.group_acc, mb.group_acc, atol=1e-6)


class TestFusedLoopEquivalence:
    """The acceptance matrix: backend x schedule x gossip cadence."""

    @pytest.mark.parametrize("backend", ["dense", "sparse"])
    @pytest.mark.parametrize(
        "topology", ["er:n=10,p=0.5", "er:n=10,p=0.5@rewire=2"],
        ids=["static", "rewire"],
    )
    @pytest.mark.parametrize("gossip_every", [0, 1, 3])
    def test_params_and_metrics_match(self, setup, backend, topology, gossip_every):
        ds, _ = setup
        kw = dict(topology=topology, mix_impl=backend, gossip_every=gossip_every)
        loop = make_trainer(setup, **kw)
        ha = loop.run(5, eval_every=2, x_test=ds.x_test, y_test=ds.y_test)
        fused = make_trainer(setup, **kw)
        hb = fused.run_fused(5, eval_every=2, x_test=ds.x_test, y_test=ds.y_test)
        assert_trees_close(loop.params, fused.params, rtol=1e-6, atol=1e-6)
        assert_trees_close(loop.opt_state, fused.opt_state, rtol=1e-6, atol=1e-6)
        assert_histories_close(ha, hb)

    def test_sparse_p_chunk_matches(self, setup):
        ds, _ = setup
        kw = dict(mix_impl="sparse", sparse_p_chunk=8)
        loop = make_trainer(setup, **kw)
        loop.run(3)
        fused = make_trainer(setup, **kw)
        fused.run_fused(3)
        assert_trees_close(loop.params, fused.params, rtol=1e-6, atol=1e-6)

    def test_gossip_first_matches(self, setup):
        ds, _ = setup
        loop = make_trainer(setup)
        loop.run(3, gossip_first=True)
        fused = make_trainer(setup)
        fused.run_fused(3, gossip_first=True)
        assert_trees_close(loop.params, fused.params, rtol=1e-6, atol=1e-6)

    def test_group_metrics_match(self, setup):
        ds, _ = setup
        groups = np.array([0] * 5 + [1] * 5)
        loop = make_trainer(setup, class_groups=groups)
        ha = loop.run(3, x_test=ds.x_test, y_test=ds.y_test)
        fused = make_trainer(setup, class_groups=groups)
        hb = fused.run_fused(3, x_test=ds.x_test, y_test=ds.y_test)
        assert ha[-1].group_acc is not None
        assert_histories_close(ha, hb)

    def test_rejects_unsupported_backend(self, setup):
        tr = make_trainer(setup, mix_impl="pallas")
        with pytest.raises(ValueError, match="run_fused supports"):
            tr.run_fused(2)

    def test_fused_backends_mirror_capability_matrix(self):
        from repro.train.trainer import _FUSED_BACKENDS

        caps = decavg.GossipEngine.capabilities()
        assert set(_FUSED_BACKENDS) == {b for b, c in caps.items() if c["fused"]}

    def test_streams_chunks_to_on_round(self, setup):
        """eval_every chunking: one scan dispatch per eval round, callbacks
        in the same order/rounds as the loop, wall clock monotone."""
        ds, _ = setup
        tr = make_trainer(setup)
        seen = []
        hist = tr.run_fused(
            8, eval_every=3, x_test=ds.x_test, y_test=ds.y_test,
            on_round=lambda m: seen.append(m),
        )
        assert [m.round for m in seen] == [0, 3, 6, 7]
        assert all(h is s for h, s in zip(hist, seen))
        walls = [m.wall_s for m in seen]
        assert walls == sorted(walls) and walls[0] > 0

    def test_no_eval_single_scan(self, setup):
        tr = make_trainer(setup)
        assert tr.run_fused(4) == []
        assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(tr.params))


class TestFusedEngineBackends:
    """Fused-vs-loop for the engine-held backends the tentpole adds.

    ``sparse_sharded`` must be BIT-identical (ring/allgather halo assembly is
    pure data movement and both paths build W via csr_from_graph); locally the
    mesh has one device, so this exercises the degenerate 1-shard layout —
    tests/test_fused_sharded.py covers 8 shards in a subprocess.
    ``sparse_pallas`` fuses the 8-row-blocked kernel while the loop runs the
    scalar interpret kernel off-TPU: the two sum in different orders, and the
    ~1e-7 per-mix gap is amplified by the SGD rounds *between* mixes, so the
    sparser the cadence the looser the budget. A small member model keeps the
    interpret-mode kernels affordable.
    """

    SMALL = dict(init_fn=lambda k: init_mlp(k, in_dim=DIM, hidden=(16,)))

    @pytest.mark.parametrize("backend", ["sparse_pallas", "sparse_sharded"])
    @pytest.mark.parametrize(
        "topology", ["er:n=10,p=0.5", "er:n=10,p=0.5@rewire=2"],
        ids=["static", "rewire"],
    )
    @pytest.mark.parametrize("gossip_every", [1, 3])
    def test_params_and_metrics_match(self, setup, backend, topology, gossip_every):
        ds, _ = setup
        kw = dict(topology=topology, mix_impl=backend, gossip_every=gossip_every,
                  **self.SMALL)
        if backend == "sparse_sharded":
            loop = make_trainer(setup, **kw)
            ha = loop.run(4, eval_every=2, x_test=ds.x_test, y_test=ds.y_test)
            fused = make_trainer(setup, **kw)
            hb = fused.run_fused(4, eval_every=2, x_test=ds.x_test, y_test=ds.y_test)
            assert_trees_close(loop.params, fused.params, rtol=0, atol=0)
            assert_trees_close(loop.opt_state, fused.opt_state, rtol=0, atol=0)
            assert_histories_close(ha, hb)
        else:
            loop = make_trainer(setup, **kw)
            loop.run(4)
            fused = make_trainer(setup, **kw)
            fused.run_fused(4)
            tol = 1e-6 if gossip_every == 1 else 5e-4
            assert_trees_close(loop.params, fused.params, rtol=tol, atol=tol)
            assert_trees_close(loop.opt_state, fused.opt_state, rtol=tol, atol=tol)

    def test_sharded_csr_concrete_after_jitted_round(self, setup):
        """Regression: the loop path's jitted round reads the engine's
        ShardedCSR; one built lazily inside that trace cached tracers that
        the next round (or any host read) then tripped over."""
        tr = make_trainer(
            setup, topology="er:n=10,p=0.5@rewire=2", mix_impl="sparse_sharded",
            **self.SMALL,
        )
        tr.run(3)  # crosses the period-1 boundary at round 2
        for leaf in jax.tree.leaves(tr.engine.sharded_csr()):
            assert not isinstance(leaf, jax.core.Tracer)
            np.asarray(leaf)  # raises on a leaked tracer

    def test_loop_backends_agree_across_periods(self, setup):
        """Regression: ``_jit_for_period`` once jitted the bound method, and
        equal bound methods share one pjit cache entry — after the first
        period change the loop silently reused the executable traced with the
        OLD period's engine state. All loop backends must agree on a @rewire
        schedule."""
        kw = dict(topology="er:n=10,p=0.5@rewire=2", gossip_every=1, **self.SMALL)
        ref = make_trainer(setup, mix_impl="sparse", **kw)
        ref.run(4)  # crosses the period-1 boundary at round 2
        for backend in ("sparse_pallas", "sparse_sharded"):
            tr = make_trainer(setup, mix_impl=backend, **kw)
            tr.run(4)
            assert_trees_close(tr.params, ref.params, rtol=1e-6, atol=1e-6)


class TestMixingProgram:
    def test_period_and_cadence_staging(self):
        e = decavg.GossipEngine("er:n=8,p=0.6@rewire=2", seed=3, gossip_every=3)
        prog = e.program(7)
        assert prog.kind == "dense" and prog.w.shape == (4, 8, 8)
        assert prog.num_periods == 4 and prog.rounds == 7
        assert np.asarray(prog.period_idx).tolist() == [0, 0, 1, 1, 2, 2, 3]
        assert np.asarray(prog.gossip_mask).tolist() == [
            True, False, False, True, False, False, True,
        ]
        assert prog.cadence == "mask"
        # the engine is left where a fresh Python-loop run expects it
        assert e.schedule.period_of(0) == 0 and np.asarray(e.w).shape == (8, 8)
        assert decavg.GossipEngine("ring:n=8").program(3).cadence == "always"
        assert decavg.GossipEngine("ring:n=8", gossip_every=0).program(3).cadence == "never"

    def test_sparse_padding_is_exact(self):
        """Padded stacked CSR periods mix identically to the dense stack."""
        e = decavg.GossipEngine("er:n=8,p=0.4@regen=1", seed=5)
        dense = e.program(3, kind="dense")
        sp = e.program(3, kind="sparse")
        assert sp.rows.shape == sp.values.shape  # (T, E) uniform padding
        params = {"p": jax.random.normal(jax.random.PRNGKey(0), (8, 7))}
        for r in range(3):
            a = jax.jit(lambda p, r=r: dense.apply(p, jnp.int32(r)))(params)
            b = jax.jit(lambda p, r=r: sp.apply(p, jnp.int32(r)))(params)
            np.testing.assert_allclose(
                np.asarray(a["p"]), np.asarray(b["p"]), atol=1e-6
            )

    def test_sparse_p_chunk_reaches_the_program(self):
        """The fused path must keep the documented gather-transient bound:
        the engine's sparse_p_chunk lands on the program and the chunked
        in-scan mix equals the unchunked one."""
        e = decavg.GossipEngine("er:n=8,p=0.5", seed=1, sparse_p_chunk=4)
        prog = e.program(2, kind="sparse")
        assert prog.p_chunk == 4
        auto = decavg.GossipEngine("er:n=8,p=0.5", seed=1, sparse_p_chunk="auto")
        assert isinstance(auto.program(2, kind="sparse").p_chunk, int)
        plain = decavg.GossipEngine("er:n=8,p=0.5", seed=1).program(2, kind="sparse")
        assert plain.p_chunk is None
        params = {"p": jax.random.normal(jax.random.PRNGKey(0), (8, 10))}
        a = jax.jit(lambda p: prog.apply(p, jnp.int32(0)))(params)
        b = jax.jit(lambda p: plain.apply(p, jnp.int32(0)))(params)
        np.testing.assert_allclose(np.asarray(a["p"]), np.asarray(b["p"]), atol=1e-6)

    def test_all_sparse_kinds_apply_match_dense(self):
        """One engine, four staged kinds: every period's in-scan mix agrees
        with the dense reference program (sparse/sparse_sharded exactly —
        same csr_from_graph values, exact-zero padding — pallas at 1e-6)."""
        e = decavg.GossipEngine("er:n=8,p=0.5@rewire=1", seed=7)
        dense = e.program(3, kind="dense")
        params = {"p": jax.random.normal(jax.random.PRNGKey(2), (8, 9))}
        tol = {"sparse": 5e-7, "sparse_pallas": 1e-6, "sparse_sharded": 5e-7}
        for kind, atol in tol.items():
            prog = e.program(3, kind=kind)
            assert prog.kind == kind and prog.num_periods == 3
            for r in range(3):
                a = jax.jit(lambda p, r=r: dense.apply(p, jnp.int32(r)))(params)
                b = jax.jit(lambda p, r=r, prog=prog: prog.apply(p, jnp.int32(r)))(params)
                np.testing.assert_allclose(
                    np.asarray(a["p"]), np.asarray(b["p"]), atol=atol
                )

    def test_stacked_layout_staging_invariants(self):
        """The period axis of every staged layout matches num_periods, and
        padding is uniform across periods (one shape for the whole scan)."""
        e = decavg.GossipEngine("er:n=16,p=0.3@rewire=1", seed=11)
        bell = e.program(3, kind="sparse_pallas")
        assert bell.bell_idx.shape[0] == 3 and bell.bell_val.shape[0] == 3
        assert bell.bell_val.shape[1:] == (
            bell.bell_idx.shape[1] * 8, bell.bell_idx.shape[2] * 8,
        )
        assert bell.w is None and bell.rows is None  # no dense/CSR staging
        sh = e.program(3, kind="sparse_sharded")
        assert sh.sh_values.shape[0] == 3
        assert sh.sh_rows.shape == sh.sh_cols.shape == sh.sh_values.shape
        assert sh.shards == sh.sh_halo.shape[1]
        assert len(sh.sh_ring_send) == len(sh.sh_ring_recv) == sh.shards - 1
        assert sh.mesh is not None and sh.halo_schedule == "auto"

    def test_pad_ratio_logged(self):
        """pad_ratio = staged operator slots per real W entry — 1.0 when
        nothing is padded (dense, single-period sparse), > 1 for blocked/
        sharded layouts, and finite always (ISSUE 6 satellite)."""
        static = decavg.GossipEngine("er:n=8,p=0.5", seed=0)
        assert static.program(2, kind="dense").pad_ratio == 1.0
        assert static.program(2, kind="sparse").pad_ratio == 1.0
        for kind in ("sparse", "sparse_pallas", "sparse_sharded"):
            r = decavg.GossipEngine(
                "er:n=8,p=0.4@rewire=1", seed=4
            ).program(3, kind=kind).pad_ratio
            assert np.isfinite(r) and r >= 1.0

    def test_program_validates_args(self):
        e = decavg.GossipEngine("ring:n=8")
        with pytest.raises(ValueError, match="rounds"):
            e.program(0)
        with pytest.raises(ValueError, match="kind"):
            e.program(2, kind="pallas")


class TestRoundKeyedSampler:
    def test_pure_and_deterministic(self, setup):
        ds, parts = setup
        loader = NodeLoader(ds.x_train, ds.y_train, parts, batch_size=8, seed=2)
        xa, ya = loader.sample_round(2, round=3)
        # interleave legacy stateful draws: must not disturb keyed ones
        loader.sample_round(2)
        xb, yb = loader.sample_round(2, round=3)
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)
        xc, _ = loader.sample_round(2, round=4)
        assert not np.array_equal(xa, xc)

    def test_device_pool_matches_host_gather(self, setup):
        """The staged bank + in-scan index rule reproduce the host batches."""
        ds, parts = setup
        loader = NodeLoader(ds.x_train, ds.y_train, parts, batch_size=8, seed=2)
        data = loader.device_data()
        xs, ys = loader.sample_round(2, round=5)
        idx = round_batch_indices(data.key, 5, 2, loader.batch, data.sizes)
        node = jnp.arange(loader.num_nodes)
        rows = data.parts[node[None, :, None], idx]  # (steps, N, B)
        np.testing.assert_array_equal(np.asarray(data.x[rows]), xs)
        np.testing.assert_array_equal(np.asarray(data.y[rows]), ys.astype(np.int32))

    def test_indices_respect_pool_sizes(self, setup):
        ds, parts = setup
        loader = NodeLoader(ds.x_train, ds.y_train, parts, batch_size=16, seed=0)
        data = loader.device_data()
        idx = np.asarray(round_batch_indices(data.key, 0, 4, 16, data.sizes))
        sizes = np.asarray(data.sizes)
        assert (idx >= 0).all()
        assert (idx < sizes[None, :, None]).all()

    def test_empty_node_rejected(self, setup):
        ds, parts = setup
        bad = [np.array([], dtype=np.int64)] + list(parts[1:])
        loader = NodeLoader(ds.x_train, ds.y_train, bad, batch_size=8, seed=0)
        with pytest.raises(ValueError, match="empty dataset"):
            loader.sample_round(1, round=0)
        with pytest.raises(ValueError, match="empty dataset"):
            loader.device_data()


class TestNoReJitPerPeriod:
    def test_dense_rewire_compiles_once(self, setup):
        """The round closure takes W as a traced argument: a 3-period
        @rewire run reuses ONE compiled program (the old code re-jitted —
        and recompiled — at every period boundary)."""
        tr = make_trainer(setup, topology="er:n=10,p=0.5@rewire=2")
        tr.run(6)
        assert tr._round_jit._cache_size() == 1
        tr.run(6)  # a second run revisits the periods: still one program
        assert tr._round_jit._cache_size() == 1

    def test_engine_backend_period_cache_reused(self, setup):
        """Backends mixing through engine-held static state get one jitted
        closure per period, cached across runs."""
        tr = make_trainer(setup, topology="er:n=10,p=0.5@rewire=2",
                          mix_impl="sparse_pallas")
        tr.run(4)  # periods 0 and 1
        assert set(tr._round_jit_cache) == {0, 1}
        jits = dict(tr._round_jit_cache)
        tr.run(4)
        assert tr._round_jit_cache == jits  # same objects: no re-jit


class TestCompressKnob:
    def test_full_k_equals_plain_decavg(self, setup):
        """k_frac=1 transmits the whole delta: CHOCO reduces exactly to
        W @ params, so the compressed run must match the baseline."""
        base = make_trainer(setup)
        base.run(4)
        comp = make_trainer(setup, compress=1.0)
        comp.run(4)
        assert_trees_close(base.params, comp.params, rtol=1e-5, atol=1e-6)

    def test_convergence_smoke(self, setup):
        """Top-k compressed gossip still learns and still spreads: accuracy
        climbs and consensus stays contracted vs isolated training."""
        ds, _ = setup
        tr = make_trainer(setup, topology="complete:n=10", compress=0.25)
        hist = tr.run(8, eval_every=7, x_test=ds.x_test, y_test=ds.y_test)
        assert hist[-1].mean_acc > max(0.2, hist[0].mean_acc + 0.05)
        assert np.isfinite(hist[-1].consensus).all()

    def test_fused_matches_loop_with_compress(self, setup):
        ds, _ = setup
        kw = dict(mix_impl="sparse", compress=0.25, gossip_every=2)
        loop = make_trainer(setup, **kw)
        ha = loop.run(5, eval_every=2, x_test=ds.x_test, y_test=ds.y_test)
        fused = make_trainer(setup, **kw)
        hb = fused.run_fused(5, eval_every=2, x_test=ds.x_test, y_test=ds.y_test)
        assert_trees_close(loop.params, fused.params, rtol=1e-6, atol=1e-6)
        assert_trees_close(
            loop.cstate.reference, fused.cstate.reference, rtol=1e-6, atol=1e-6
        )
        assert_histories_close(ha, hb)

    def test_rejects_bad_fraction(self, setup):
        with pytest.raises(ValueError, match="compress"):
            make_trainer(setup, compress=0.0)
        with pytest.raises(ValueError, match="compress"):
            make_trainer(setup, compress=1.5)


class TestRunnerRouting:
    def test_mlp_spec_routes_through_fused(self, setup, tmp_path, monkeypatch):
        from repro.experiments import runner
        from repro.experiments.spec import ExperimentSpec
        from repro.experiments.store import ResultsStore
        from repro.train.trainer import DecentralizedTrainer as DT

        calls = {"fused": 0, "loop": 0}
        orig_fused, orig_run = DT.run_fused, DT.run

        def spy_fused(self, *a, **k):
            calls["fused"] += 1
            return orig_fused(self, *a, **k)

        def spy_run(self, *a, **k):
            calls["loop"] += 1
            return orig_run(self, *a, **k)

        monkeypatch.setattr(DT, "run_fused", spy_fused)
        monkeypatch.setattr(DT, "run", spy_run)
        tiny = dict(rounds=2, eval_every=1, batch_size=8,
                    data={"train_per_class": 40, "test_per_class": 10})
        spec = ExperimentSpec(topology="ring:n=6", **tiny)
        out = runner.run_spec(spec, ResultsStore(str(tmp_path / "a.jsonl")))
        assert out["status"] == "completed"
        assert calls == {"fused": 1, "loop": 0}
        # the opt-out flag forces the Python loop (and changes the run id)
        opt_out = ExperimentSpec(topology="ring:n=6", model={"fused": False}, **tiny)
        assert opt_out.run_id != spec.run_id
        out = runner.run_spec(opt_out, ResultsStore(str(tmp_path / "b.jsonl")))
        assert out["status"] == "completed"
        assert calls == {"fused": 1, "loop": 1}

    def test_compress_spec_reaches_trainer(self, setup, tmp_path):
        from repro.experiments import runner
        from repro.experiments.spec import ExperimentSpec
        from repro.experiments.store import ResultsStore

        spec = ExperimentSpec(
            topology="ring:n=6", model={"kind": "mlp", "compress": 0.5},
            rounds=2, eval_every=1, batch_size=8,
            data={"train_per_class": 40, "test_per_class": 10},
        )
        out = runner.run_spec(spec, ResultsStore(str(tmp_path / "r.jsonl")))
        assert out["status"] == "completed"
        assert np.isfinite(out["final"]["mean_acc"])
