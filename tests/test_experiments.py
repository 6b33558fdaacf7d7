"""Experiment harness: spec identity/round-trip, store resume semantics,
runner streaming, analysis joins and the knowledge-spread orderings."""

import json
import os

import numpy as np
import pytest

from repro.experiments import analysis
from repro.experiments import presets
from repro.experiments import runner
from repro.experiments.spec import ExperimentSpec, expand_grid
from repro.experiments.store import ResultsStore

TINY = dict(
    rounds=2,
    eval_every=1,
    batch_size=8,
    data={"train_per_class": 40, "test_per_class": 10},
)


class TestSpec:
    def test_run_id_stable_and_content_addressed(self):
        a = ExperimentSpec(topology="ring:n=8", **TINY)
        b = ExperimentSpec(topology="ring:n=8", **TINY)
        assert a.run_id == b.run_id
        c = ExperimentSpec(topology="ring:n=8", lr=0.01, **TINY)
        assert c.run_id != a.run_id
        # tag is cosmetic: excluded from identity
        d = ExperimentSpec(topology="ring:n=8", tag="whatever", **TINY)
        assert d.run_id == a.run_id
        assert a.run_id.startswith("ring-iid-s0-")

    def test_json_round_trip(self):
        s = ExperimentSpec(
            topology="ba:n=16,m=2", partitioner="dirichlet",
            partitioner_params={"beta": 0.3}, seed=7, **TINY,
        )
        back = ExperimentSpec.from_json(json.loads(json.dumps(s.to_json())))
        assert back == s and back.run_id == s.run_id

    def test_from_json_rejects_unknown_fields(self):
        with pytest.raises(ValueError, match="unknown ExperimentSpec fields"):
            ExperimentSpec.from_json({"topology": "ring:n=8", "bogus": 1})

    def test_validation(self):
        with pytest.raises(ValueError, match="unknown partitioner"):
            ExperimentSpec(topology="ring:n=8", partitioner="nope")
        with pytest.raises(ValueError, match="rounds"):
            ExperimentSpec(topology="ring:n=8", rounds=0)
        with pytest.raises(ValueError, match="model kind"):
            ExperimentSpec(topology="ring:n=8", model={"kind": "gan"})

    def test_grid_expansion(self):
        specs = expand_grid(
            {"rounds": 3},
            topology=["ring:n=8", "star:n=8", "ba:n=8,m=2"],
            partitioner=["iid", "hub_focused"],
            seed=[0, 1],
        )
        assert len(specs) == 12
        assert len({s.run_id for s in specs}) == 12
        assert {s.family for s in specs} == {"ring", "star", "ba"}

    def test_presets_expand(self):
        for name in presets.PRESETS:
            specs = presets.get_preset(name)
            assert specs, name
            assert len({s.run_id for s in specs}) == len(specs)
        smoke = presets.get_preset("smoke")
        assert len({s.family for s in smoke}) >= 3  # >= 3 topology families
        parts = {s.partitioner for s in smoke}
        assert {"hub_focused", "edge_focused"} <= parts


class TestStore:
    def test_append_read_and_truncated_tail(self, tmp_path):
        st = ResultsStore(str(tmp_path / "r.jsonl"))
        st.run_start("a", {"x": 1})
        st.round("a", {"round": 0, "v": 1.0})
        with open(st.path, "a") as f:
            f.write('{"kind": "round", "run_id": "a", "rou')  # crashed writer
        recs = st.records()
        assert [r["kind"] for r in recs] == ["run_start", "round"]

    def test_resume_semantics(self, tmp_path):
        st = ResultsStore(str(tmp_path / "r.jsonl"))
        st.run_start("a", {})
        st.round("a", {"round": 0, "v": 1.0})
        assert st.completed() == set()  # no run_end: incomplete
        st.run_end("a", "failed", error="boom")
        assert st.completed() == set()  # failed doesn't count
        # second attempt supersedes the first's rounds
        st.run_start("a", {})
        st.round("a", {"round": 0, "v": 2.0})
        st.round("a", {"round": 1, "v": 3.0})
        st.run_end("a", "completed", final={"v": 3.0})
        assert st.completed() == {"a"}
        curve = st.curves("a")
        assert [r["v"] for r in curve] == [2.0, 3.0]
        assert st.finals()["a"]["final"] == {"v": 3.0}

    def test_latest_attempt_wins_even_over_older_completed(self, tmp_path):
        """completed()/finals()/curves() all describe the SAME attempt: a
        fresh re-run that fails supersedes an older completed attempt."""
        st = ResultsStore(str(tmp_path / "r.jsonl"))
        st.run_start("a", {})
        st.round("a", {"round": 0, "v": 1.0})
        st.run_end("a", "completed", final={"v": 1.0})
        st.run_start("a", {})  # --fresh re-run...
        st.round("a", {"round": 0, "v": 9.0})
        st.run_end("a", "failed", error="crash")  # ...that dies
        assert st.completed() == set()  # retried on next resume
        assert st.finals() == {}
        assert [r["v"] for r in st.curves("a")] == [9.0]
        # mid-flight (no run_end yet) is also not completed
        st.run_start("a", {})
        assert st.completed() == set() and st.curves("a") == []


@pytest.fixture(scope="module")
def tiny_sweep(tmp_path_factory):
    """Two completed tiny runs in one store (shared across tests)."""
    path = str(tmp_path_factory.mktemp("sweep") / "r.jsonl")
    specs = expand_grid(
        dict(TINY), topology=["ring:n=6", "star:n=6"], partitioner=["iid"], seed=[0]
    )
    summary = runner.run_sweep(specs, path)
    return specs, path, summary


class TestRunner:
    def test_streams_knowledge_spread_records(self, tiny_sweep):
        specs, path, summary = tiny_sweep
        assert summary["ran"] == 2 and not summary["failed"]
        st = ResultsStore(path)
        curve = st.curves(specs[0].run_id)
        assert len(curve) == TINY["rounds"]
        for key in ("mean_acc", "g1_acc", "g2_acc", "consensus_mean", "wall_s"):
            assert all(np.isfinite(r[key]) for r in curve), key
        final = st.finals()[specs[0].run_id]["final"]
        assert final["graph"]["nodes"] == 6
        assert "spectral_gap" in final["graph"]

    def test_rerun_is_idempotent(self, tiny_sweep):
        specs, path, _ = tiny_sweep
        before = os.path.getsize(path)
        summary = runner.run_sweep(specs, path)
        assert summary["ran"] == 0 and summary["skipped"] == 2
        assert os.path.getsize(path) == before  # nothing appended

    def test_failed_spec_recorded_and_survived(self, tmp_path):
        bad = ExperimentSpec(topology="ring:n=6", backend="sharded", **TINY)
        ok = ExperimentSpec(topology="ring:n=6", **TINY)
        summary = runner.run_sweep([bad, ok], str(tmp_path / "r.jsonl"))
        assert summary["failed"] == [bad.run_id]
        st = ResultsStore(str(tmp_path / "r.jsonl"))
        assert st.completed() == {ok.run_id}
        # the failed run is retried on resume, completed one is skipped
        summary2 = runner.run_sweep([bad, ok], st.path)
        assert summary2["skipped"] == 1 and summary2["failed"] == [bad.run_id]

    def test_matrix_kind_reaches_the_engine(self, tmp_path):
        """spec.matrix is part of the run identity, so it must actually be
        the mixing matrix used (mh = doubly stochastic, unlike decavg)."""
        spec = ExperimentSpec(topology="er:n=8,p=0.6", matrix="mh", **TINY)
        assert spec.run_id != ExperimentSpec(topology="er:n=8,p=0.6", **TINY).run_id
        from repro.data.synthetic import make_mnist_like
        from repro.data.loader import NodeLoader
        from repro.core import partition as P
        from repro.train.trainer import DecentralizedTrainer

        ds = make_mnist_like(train_per_class=40, test_per_class=10, seed=0)
        parts = P.iid(ds.y_train, 8, seed=0)
        loader = NodeLoader(ds.x_train, ds.y_train, parts, batch_size=8, seed=0)
        tr = DecentralizedTrainer("er:n=8,p=0.6", loader, matrix="mh", seed=0)
        w = np.asarray(tr.engine.w)
        np.testing.assert_allclose(w.sum(0), 1.0, atol=1e-6)  # doubly stochastic
        np.testing.assert_allclose(w.sum(1), 1.0, atol=1e-6)
        # and through the runner end-to-end
        st = ResultsStore(str(tmp_path / "r.jsonl"))
        out = runner.run_spec(spec, st)
        assert out["status"] == "completed"

    def test_sparse_p_chunk_reaches_the_engine(self, tmp_path):
        """large_n-shaped specs must actually bound the gather transient:
        model.sparse_p_chunk flows spec -> trainer -> GossipEngine."""
        from repro.data.loader import NodeLoader
        from repro.data.synthetic import make_mnist_like
        from repro.core import partition as P
        from repro.train.trainer import DecentralizedTrainer

        ds = make_mnist_like(train_per_class=40, test_per_class=10, seed=0)
        parts = P.iid(ds.y_train, 8, seed=0)
        loader = NodeLoader(ds.x_train, ds.y_train, parts, batch_size=8, seed=0)
        tr = DecentralizedTrainer("ring:n=8", loader, mix_impl="sparse",
                                  sparse_p_chunk="auto", seed=0)
        assert tr.engine.sparse_p_chunk == "auto"
        spec = ExperimentSpec(
            topology="ring:n=8", backend="sparse",
            model={"kind": "mlp", "hidden": [16], "sparse_p_chunk": 32}, **TINY,
        )
        out = runner.run_spec(spec, ResultsStore(str(tmp_path / "r.jsonl")))
        assert out["status"] == "completed"
        from repro.experiments.presets import get_preset

        assert all(
            s.model.get("sparse_p_chunk") == "auto" for s in get_preset("large_n")
        )

    def test_hub_vs_edge_partition_wiring(self):
        """Runner assigns G2 to hubs/leaves per the spec's partitioner."""
        from repro.core import topology as T
        from repro.core.partition import partition_summary
        from repro.data.synthetic import make_mnist_like

        ds = make_mnist_like(train_per_class=40, test_per_class=10, seed=0)
        g = T.make("ba:n=12,m=2", seed=3)
        spec = ExperimentSpec(topology="ba:n=12,m=2", partitioner="hub_focused",
                              seed=3, **TINY)
        parts = runner.build_partition(spec, g, ds.y_train)
        summ = partition_summary(ds.y_train, parts)
        holders = np.flatnonzero(summ[:, 5:].sum(axis=1) > 0)
        deg = g.degrees()
        assert deg[holders].min() >= np.sort(deg)[::-1][len(holders) - 1]


class TestBugfixRegressions:
    def test_graph_records_cover_every_schedule_period(self):
        """@regen/@rewire runs must not report period-0 graph properties as
        if they described the whole run (the old _graph_record-from-
        graph_at(0) bug)."""
        from repro.core import decavg as D

        e = D.GossipEngine("er:n=8,p=0.6@regen=2", seed=3)
        out = runner._graph_records(e, rounds=6)
        assert out["graph_num_periods"] == 3
        assert out["graph"]["period"] == 0
        assert [r["period"] for r in out["graph_periods"]] == [0, 1, 2]
        gaps = [r["spectral_gap"] for r in out["graph_periods"]]
        assert all(np.isfinite(g) for g in gaps)
        assert out["graph_mean"]["spectral_gap"] == pytest.approx(np.mean(gaps))
        assert "period" not in out["graph_mean"]
        # a static topology keeps the old single-record shape
        static = runner._graph_records(D.GossipEngine("ring:n=8"), rounds=6)
        assert static["graph_num_periods"] == 1
        assert "graph_periods" not in static and "graph_mean" not in static

    def test_summarize_prefers_period_mean_over_period0(self, tmp_path):
        st = ResultsStore(str(tmp_path / "r.jsonl"))
        st.run_start("x", {"topology": "er:n=8,p=0.5@regen=2",
                           "partitioner": "iid", "seed": 0})
        st.round("x", {"round": 0, "mean_acc": 0.5})
        st.run_end("x", "completed", final={
            "mean_acc": 0.5,
            "graph": {"nodes": 8, "spectral_gap": 0.9, "degree_mean": 4.0,
                      "period": 0},
            "graph_num_periods": 2,
            "graph_mean": {"spectral_gap": 0.6, "degree_mean": 3.5},
        })
        (row,) = analysis.summarize(st)
        assert row["spectral_gap"] == pytest.approx(0.6)  # mean, not period 0
        assert row["degree_mean"] == pytest.approx(3.5)
        assert row["nodes"] == 8 and row["topology_periods"] == 2

    def test_rewire_run_records_per_period_graphs_end_to_end(self, tmp_path):
        spec = ExperimentSpec(topology="er:n=6,p=0.6@regen=1", **TINY)
        out = runner.run_spec(spec, ResultsStore(str(tmp_path / "r.jsonl")))
        assert out["status"] == "completed"
        final = out["final"]
        assert final["graph_num_periods"] == TINY["rounds"]
        assert len(final["graph_periods"]) == TINY["rounds"]
        assert "spectral_gap" in final["graph_mean"]

    def test_consensus_distance_empty_pytree(self):
        from repro.train.metrics import consensus_distance

        out = np.asarray(consensus_distance({}))
        assert out.shape == (0,) and out.dtype == np.float32
        out = np.asarray(consensus_distance([]))
        assert out.shape == (0,)

    def test_stale_shards_salvaged_on_next_sweep(self, tmp_path):
        """A worker that died mid-run leaves its shard + the .shards dir
        behind; the next sweep must merge complete shards (skipped on
        resume), re-run partial ones, and drop the directory."""
        done_spec = ExperimentSpec(topology="ring:n=6", **TINY)
        partial_spec = ExperimentSpec(topology="star:n=6", **TINY)
        store_path = str(tmp_path / "r.jsonl")
        shard_dir = store_path + ".shards"
        os.makedirs(shard_dir)
        # complete shard: parent was killed after the worker finished but
        # before the merge
        done_shard = ResultsStore(os.path.join(shard_dir, f"{done_spec.run_id}.jsonl"))
        done_shard.run_start(done_spec.run_id, done_spec.to_json())
        done_shard.round(done_spec.run_id, {"round": 0, "mean_acc": 0.5})
        done_shard.run_end(done_spec.run_id, "completed", final={"mean_acc": 0.5})
        # stuck shard: worker died mid-run, no run_end
        stuck = ResultsStore(os.path.join(shard_dir, f"{partial_spec.run_id}.jsonl"))
        stuck.run_start(partial_spec.run_id, partial_spec.to_json())
        # stale = old: the startup salvage's age floor must not mistake these
        # for a concurrent sweep's in-flight shards
        for f in os.listdir(shard_dir):
            os.utime(os.path.join(shard_dir, f), (1, 1))
        summary = runner.run_sweep([done_spec, partial_spec], store_path)
        assert not os.path.exists(shard_dir)
        assert summary["skipped"] == 1  # salvaged complete shard counts
        assert summary["ran"] == 1 and not summary["failed"]  # partial re-ran
        st = ResultsStore(store_path)
        assert st.completed() == {done_spec.run_id, partial_spec.run_id}

    def test_salvage_tolerates_missing_dir(self, tmp_path):
        st = ResultsStore(str(tmp_path / "r.jsonl"))
        assert runner._salvage_shards(st, st.path + ".shards", False) == 0

    def test_salvage_age_floor_spares_inflight_shards(self, tmp_path):
        """A concurrent sweep's freshly-written shard must not be merged and
        deleted out from under its writer."""
        st = ResultsStore(str(tmp_path / "r.jsonl"))
        shard_dir = st.path + ".shards"
        os.makedirs(shard_dir)
        fresh = os.path.join(shard_dir, "live.jsonl")
        ResultsStore(fresh).run_start("live", {})
        assert runner._salvage_shards(st, shard_dir, False, min_age_s=60.0) == 0
        assert os.path.exists(fresh)  # left for its writer
        assert runner._salvage_shards(st, shard_dir, False) == 1  # age 0: take it
        assert not os.path.exists(shard_dir)

    def test_multiprocess_sweep_merges_and_cleans_up(self, tmp_path):
        specs = [
            ExperimentSpec(topology="ring:n=6", **TINY),
            ExperimentSpec(topology="star:n=6", **TINY),
        ]
        store_path = str(tmp_path / "r.jsonl")
        summary = runner.run_sweep(specs, store_path, processes=2)
        assert summary["ran"] == 2 and not summary["failed"]
        assert not os.path.exists(store_path + ".shards")
        st = ResultsStore(store_path)
        assert st.completed() == {s.run_id for s in specs}

    def test_multiprocess_sweep_refused_off_cpu(self, tmp_path, monkeypatch):
        """Worker processes would each open the accelerator: without
        JAX_PLATFORMS=cpu the pool is refused before anything spawns."""
        specs = [
            ExperimentSpec(topology="ring:n=6", **TINY),
            ExperimentSpec(topology="star:n=6", **TINY),
        ]
        store_path = str(tmp_path / "r.jsonl")
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            runner.run_sweep(specs, store_path, processes=2)
        assert not os.path.exists(store_path + ".shards")
        assert ResultsStore(store_path).completed() == set()

    def test_graph_records_sampled_above_period_cap(self, monkeypatch):
        """Hundreds of @regen=1 periods must not mean hundreds of post-run
        eigensolves: records are evenly sampled, true count preserved."""
        from repro.core import decavg as D

        monkeypatch.setattr(runner, "_MAX_GRAPH_PERIODS", 4)
        e = D.GossipEngine("er:n=8,p=0.6@regen=1", seed=0)
        out = runner._graph_records(e, rounds=10)
        assert out["graph_num_periods"] == 10
        assert out["graph_periods_sampled"] is True
        assert len(out["graph_periods"]) == 4
        periods = [r["period"] for r in out["graph_periods"]]
        assert periods[0] == 0 and periods[-1] == 9  # endpoints always kept
        assert "spectral_gap" in out["graph_mean"]


class TestAnalysis:
    def _fabricated_store(self, tmp_path) -> ResultsStore:
        """Hand-written records with a known hub > edge ordering."""
        st = ResultsStore(str(tmp_path / "fab.jsonl"))
        runs = [
            ("ba-hub_focused-s0-aaaaaaaa", "hub_focused", [0.10, 0.30, 0.50]),
            ("ba-edge_focused-s0-bbbbbbbb", "edge_focused", [0.10, 0.12, 0.15]),
        ]
        for rid, part, curve in runs:
            st.run_start(rid, {"topology": "ba:n=16,m=2", "partitioner": part,
                               "seed": 0, "backend": "dense"})
            for i, v in enumerate(curve):
                st.round(rid, {"round": i, "mean_acc": 0.2, "g2_acc_spread": v})
            st.run_end(rid, "completed", wall_s=1.0, final={
                "mean_acc": 0.2, "g2_acc_spread": curve[-1],
                "graph": {"nodes": 16, "spectral_gap": 0.4},
            })
        return st

    def test_summarize_and_hub_vs_leaf(self, tmp_path):
        st = self._fabricated_store(tmp_path)
        rows = analysis.summarize(st)
        assert len(rows) == 2
        table = analysis.hub_vs_leaf_table(rows)
        assert table["ba"]["hub_minus_edge"] == pytest.approx(0.35)
        checks = analysis.qualitative_checks(rows)
        assert checks["hub_beats_edge"] is True
        assert checks["hub_beats_edge_by_family"] == {"ba": True}
        assert checks["gossip_learns_g2"] is True

    def test_write_bench_and_render(self, tmp_path):
        st = self._fabricated_store(tmp_path)
        out = str(tmp_path / "BENCH_sweep.json")
        bench = analysis.write_bench(st, out, extra={"preset": "test"})
        on_disk = json.load(open(out))
        assert on_disk["runs"] == 2 and on_disk["preset"] == "test"
        assert on_disk["checks"]["hub_beats_edge"] is True
        text = analysis.render_tables(analysis.summarize(st))
        assert "hub vs leaf" in text and "ba" in text

    def test_real_tiny_store_summarizes(self, tiny_sweep):
        specs, path, _ = tiny_sweep
        rows = analysis.summarize(ResultsStore(path))
        assert {r["family"] for r in rows} == {"ring", "star"}
        for r in rows:
            assert r["spectral_gap"] is not None
            assert np.isfinite(r["final_consensus"])


class TestKnowledgeSpreadEndToEnd:
    """THE acceptance property: hub-held knowledge spreads better than
    leaf-held knowledge on a scale-free graph (paper Fig. 3, smoke scale)."""

    @pytest.mark.slow
    def test_hub_beats_edge_on_ba(self, tmp_path):
        base = dict(
            rounds=8, eval_every=1, lr=0.05, momentum=0.9, batch_size=8,
            data={"train_per_class": 300, "test_per_class": 50},
            topology="ba:n=16,m=2",
        )
        specs = [
            ExperimentSpec(partitioner="hub_focused", **base),
            ExperimentSpec(partitioner="edge_focused", **base),
        ]
        path = str(tmp_path / "r.jsonl")
        summary = runner.run_sweep(specs, path)
        assert not summary["failed"]
        rows = analysis.summarize(ResultsStore(path))
        checks = analysis.qualitative_checks(rows)
        assert checks["hub_beats_edge"] is True
        table = analysis.hub_vs_leaf_table(rows)
        assert table["ba"]["hub_minus_edge"] > 0.05


class TestTrainerHook:
    def test_on_round_streams_group_metrics(self):
        from repro.core import partition as P
        from repro.core import topology as T
        from repro.data.loader import NodeLoader
        from repro.data.synthetic import make_mnist_like
        from repro.train.trainer import DecentralizedTrainer

        ds = make_mnist_like(train_per_class=40, test_per_class=10, seed=0)
        g = T.make("ring:n=6")
        parts = P.iid(ds.y_train, 6, seed=0)
        loader = NodeLoader(ds.x_train, ds.y_train, parts, batch_size=8, seed=0)
        groups = np.array([0] * 5 + [1] * 5)
        tr = DecentralizedTrainer(g, loader, lr=0.05, seed=0, class_groups=groups)
        seen = []
        hist = tr.run(3, x_test=ds.x_test, y_test=ds.y_test,
                      on_round=lambda m: seen.append(m))
        assert [m.round for m in seen] == [0, 1, 2]
        for m in seen:
            assert m.group_acc.shape == (6, 2)
            assert m.consensus.shape == (6,)
            assert m.wall_s > 0
        assert len(hist) == len(seen) and all(h is s for h, s in zip(hist, seen))

    def test_gossip_every_zero_is_isolated(self):
        """gossip_every=0 never mixes: nodes with same init + same data seed
        but different batches drift apart and stay apart."""
        import jax

        from repro.core import partition as P
        from repro.core import topology as T
        from repro.data.loader import NodeLoader
        from repro.data.synthetic import make_mnist_like
        from repro.train.trainer import DecentralizedTrainer

        ds = make_mnist_like(train_per_class=40, test_per_class=10, seed=0)
        g = T.make("complete:n=4")
        parts = P.iid(ds.y_train, 4, seed=0)
        loader = NodeLoader(ds.x_train, ds.y_train, parts, batch_size=8, seed=0)
        iso = DecentralizedTrainer(g, loader, lr=0.05, gossip_every=0, seed=0)
        iso.run(2)
        from repro.train.metrics import consensus_distance

        # complete graph with gossip contracts consensus to ~0; isolated doesn't
        loader2 = NodeLoader(ds.x_train, ds.y_train, parts, batch_size=8, seed=0)
        mixed = DecentralizedTrainer(g, loader2, lr=0.05, gossip_every=1, seed=0)
        mixed.run(2)
        d_iso = float(np.asarray(consensus_distance(iso.params)).mean())
        d_mix = float(np.asarray(consensus_distance(mixed.params)).mean())
        assert d_mix < 1e-3  # complete-graph decavg averages everyone
        assert d_iso > 10 * max(d_mix, 1e-6)

    def test_auto_backend_resolves(self):
        from repro.core import partition as P
        from repro.data.loader import NodeLoader
        from repro.data.synthetic import make_mnist_like
        from repro.train.trainer import DecentralizedTrainer

        ds = make_mnist_like(train_per_class=20, test_per_class=10, seed=0)
        parts = P.iid(ds.y_train, 6, seed=0)
        loader = NodeLoader(ds.x_train, ds.y_train, parts, batch_size=8, seed=0)
        tr = DecentralizedTrainer("ring:n=6", loader, mix_impl="auto", seed=0)
        hist = tr.run(1, x_test=ds.x_test, y_test=ds.y_test)
        assert np.isfinite(hist[-1].mean_acc)
