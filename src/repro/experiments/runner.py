"""Executes ExperimentSpecs and streams per-round records to a ResultsStore.

Two executors, dispatched on ``spec.model["kind"]``:

- ``mlp`` (default): the paper-faithful path — synthetic MNIST-like data,
  graph-aware partitioners, ``DecentralizedTrainer``. Streams per round:
  per-node accuracy stats, G1/G2 class-group accuracy (overall, on the focus
  nodes holding G2 data, and on the *spread* nodes that never saw G2 — the
  paper's knowledge-spread quantity), consensus distance ||theta_i - theta_bar||
  and wall-clock. Runs through the fused single-``lax.scan`` trainer path
  (``run_fused``) whenever the resolved backend supports it; set
  ``model={"fused": False}`` to force the per-round Python loop.
- ``lm``: LLM cohorts via ``LMCohortTrainer`` — transformer members on
  domain-skewed token streams, AdamW/SGD + LR schedule, per-round
  ``domain_acc`` / ``g2_token_spread`` knowledge-spread metrics. Takes the
  same fused single-scan path by default (``model={"fused": False}`` opts
  out), defaults CHOCO ``compress=`` on for multi-megabyte members, and
  checkpoints ``(params, opt, step)`` with ``model={"resume": True}``
  restoring bit-identically. ``launch/train.py`` is a thin CLI wrapper
  building one such spec.

``run_sweep`` adds skip-completed resume (a spec whose run_id already has a
completed ``run_end`` in the store is skipped) and optional multi-process
fan-out over specs: each worker writes a private JSONL shard which the parent
merges into the main store, so the store never sees interleaved writers.
"""

from __future__ import annotations

import os
import time
import traceback
from typing import Any, Callable

import numpy as np

from repro.experiments.spec import ExperimentSpec
from repro.experiments.store import ResultsStore

__all__ = [
    "run_spec", "run_sweep", "build_mlp_trainer", "build_partition",
    "default_class_groups",
]

Emit = Callable[[dict[str, Any]], None]


# ---------------------------------------------------------------------------
# mlp executor (the paper's reproduction path)
# ---------------------------------------------------------------------------


def default_class_groups(num_classes: int) -> np.ndarray:
    """Paper split: lower half of the classes is G1 (everyone), upper half G2."""
    g = np.zeros(num_classes, dtype=np.int32)
    g[num_classes // 2 :] = 1
    return g


def build_partition(spec: ExperimentSpec, g, labels: np.ndarray) -> list[np.ndarray]:
    """Dispatch spec.partitioner over core/partition.py with the realized graph."""
    from repro.core import partition as P

    kw = dict(spec.partitioner_params)
    n = g.num_nodes
    if spec.partitioner == "iid":
        return P.iid(labels, n, seed=spec.seed, **kw)
    if spec.partitioner == "hub_focused":
        return P.hub_focused(labels, g, seed=spec.seed, **kw)
    if spec.partitioner == "edge_focused":
        return P.edge_focused(labels, g, seed=spec.seed, **kw)
    if spec.partitioner == "community":
        return P.community(labels, g, seed=spec.seed, **kw)
    if spec.partitioner == "dirichlet":
        kw.setdefault("beta", 0.5)
        return P.dirichlet(labels, n, seed=spec.seed, **kw)
    raise ValueError(f"unknown partitioner {spec.partitioner!r}")


def _graph_record(g, w: np.ndarray) -> dict[str, Any]:
    """graph_summary + spectral gap of the realized W (exact up to N=1024)."""
    from repro.core import mixing, topology

    rec = topology.graph_summary(g)
    rec["spectral_gap"] = (
        mixing.spectral_gap(np.asarray(w)) if g.num_nodes <= 1024 else None
    )
    return rec


_MAX_GRAPH_PERIODS = 32


def _graph_records(engine, rounds: int) -> dict[str, Any]:
    """Graph summaries for every schedule period the run realized.

    A ``@regen``/``@rewire`` run visits several graphs; summarizing only
    ``graph_at(0)`` would report period-0 modularity/spectral-gap as if they
    described the whole run. Returns ``graph`` (the period-0 record, labeled
    with ``period=0``) plus, for multi-period runs, ``graph_periods``
    (per-period records) and ``graph_mean`` (numeric fields averaged over
    the recorded periods — the value the analysis join regresses against).

    Each record costs a W rebuild plus (at N <= 1024) an O(N^3) spectral-gap
    eigensolve, so runs realizing more than ``_MAX_GRAPH_PERIODS`` periods
    (e.g. ``@regen=1`` over hundreds of rounds) are summarized on an evenly
    spaced sample of periods — ``graph_num_periods`` always reports the true
    count, and ``graph_periods_sampled`` flags the subsetting.
    """
    first_round: dict[int, int] = {}
    for r in range(max(int(rounds), 1)):
        p = engine.schedule.period_of(r)
        first_round.setdefault(p, r)
    periods = sorted(first_round)
    num_periods = len(periods)
    sampled = num_periods > _MAX_GRAPH_PERIODS
    if sampled:
        pick = np.linspace(0, num_periods - 1, _MAX_GRAPH_PERIODS).round()
        periods = [periods[int(i)] for i in np.unique(pick)]
    recs = []
    for p in periods:
        rec = _graph_record(engine.graph_at(first_round[p]), np.asarray(engine.w))
        rec["period"] = p
        recs.append(rec)
    out: dict[str, Any] = {"graph": recs[0], "graph_num_periods": num_periods}
    if len(recs) > 1:
        out["graph_periods"] = recs
        if sampled:
            out["graph_periods_sampled"] = True
        out["graph_mean"] = {
            k: float(np.mean([r[k] for r in recs]))
            for k, v in recs[0].items()
            if k != "period"
            and isinstance(v, (int, float)) and not isinstance(v, bool)
            and all(isinstance(r.get(k), (int, float)) for r in recs)
        }
    return out


def build_mlp_trainer(spec: ExperimentSpec):
    """The data, partition and ``DecentralizedTrainer`` an mlp spec runs.

    Returns ``(trainer, ds, holds_g2)``: the trainer at round 0, the
    synthetic dataset, and the per-node mask of nodes holding any G2 data.
    ``_run_mlp`` drives the trainer from here; callers that need the trained
    parameters themselves (not just the streamed metrics) build it the same
    way.
    """
    from repro.core import topology
    from repro.data.loader import NodeLoader
    from repro.data.synthetic import make_mnist_like
    from repro.train.trainer import DecentralizedTrainer

    ds = make_mnist_like(**spec.data)
    schedule = topology.make_schedule(spec.topology, seed=spec.seed)
    g0 = schedule.graph_at(0)
    parts = build_partition(spec, g0, ds.y_train)

    from repro.core.partition import partition_summary

    num_classes = ds.num_classes
    groups = default_class_groups(num_classes)
    summ = partition_summary(ds.y_train, parts)
    g2_cols = np.flatnonzero(groups == 1)
    holds_g2 = summ[:, g2_cols].sum(axis=1) > 0

    loader = NodeLoader(
        ds.x_train, ds.y_train, parts, batch_size=spec.batch_size, seed=spec.seed + 1
    )
    extra: dict[str, Any] = {}
    if "hidden" in spec.model:
        # Narrower member MLPs for large-N sweeps (the paper's 512-256-128
        # stack x 4096 nodes is GBs of node-stacked params).
        from repro.models.mlp import init_mlp

        hidden = tuple(spec.model["hidden"])
        in_dim = int(spec.model.get("in_dim", ds.x_train.shape[1]))
        extra["init_fn"] = lambda k: init_mlp(
            k, in_dim=in_dim, hidden=hidden, num_classes=num_classes
        )
    trainer = DecentralizedTrainer(
        schedule,
        loader,
        lr=spec.lr,
        momentum=spec.momentum,
        local_epochs=spec.local_epochs,
        mix_impl=spec.backend,
        matrix=spec.matrix,
        sparse_p_chunk=spec.model.get("sparse_p_chunk"),
        gossip_every=spec.gossip_every,
        compress=spec.model.get("compress"),
        faults=spec.faults,
        same_init=spec.same_init,
        seed=spec.seed,
        num_classes=num_classes,
        class_groups=groups,
        **extra,
    )
    return trainer, ds, holds_g2


def _run_mlp(spec: ExperimentSpec, emit: Emit, verbose: bool) -> dict[str, Any]:
    trainer, ds, holds_g2 = build_mlp_trainer(spec)
    focus_nodes = np.flatnonzero(holds_g2)
    spread_nodes = np.flatnonzero(~holds_g2)
    fault_trace = None
    if trainer.faulted:
        fault_trace = trainer.engine.fault_trace
        fault_trace.ensure(spec.rounds)
    last: dict[str, Any] = {}
    curve: list[tuple[int, float | None]] = []  # (round, g2_acc_spread) evals

    def on_round(m) -> None:
        rec: dict[str, Any] = {
            "round": m.round,
            "mean_acc": m.mean_acc,
            "std_acc": m.std_acc,
            "min_acc": float(m.per_node_acc.min()),
            "max_acc": float(m.per_node_acc.max()),
            "g1_acc": float(m.group_acc[:, 0].mean()),
            "g2_acc": float(m.group_acc[:, 1].mean()),
            "g2_acc_focus": (
                float(m.group_acc[focus_nodes, 1].mean()) if len(focus_nodes) else None
            ),
            "g2_acc_spread": (
                float(m.group_acc[spread_nodes, 1].mean()) if len(spread_nodes) else None
            ),
            "consensus_mean": float(m.consensus.mean()),
            "consensus_max": float(m.consensus.max()),
            "wall_s": round(m.wall_s, 4),
        }
        if fault_trace is not None:
            rec["alive_count"] = int(fault_trace.alive(m.round).sum())
        curve.append((m.round, rec["g2_acc_spread"]))
        last.clear()
        last.update(rec)
        emit(rec)
        if verbose:
            print(
                f"    round {m.round:4d}  acc {m.mean_acc:.4f}  "
                f"g2_spread {rec['g2_acc_spread']}  cons {rec['consensus_mean']:.3g}"
            )

    # Fused single-scan path by default for the backends that support it
    # (dense/sparse/sparse_pallas/sparse_sharded after "auto" resolution):
    # one device dispatch per eval instead of one per round — for
    # sparse_sharded the ring halo exchange runs inside the scan, so the
    # whole run is one compiled SPMD program per chunk. model={"fused":
    # False} opts a spec out (debugging, or backends the MixingProgram
    # can't stage).
    use_fused = bool(spec.model.get("fused", True)) and trainer.supports_fused
    run = trainer.run_fused if use_fused else trainer.run
    run(
        spec.rounds,
        eval_every=spec.eval_every,
        x_test=ds.x_test,
        y_test=ds.y_test,
        on_round=on_round,
    )

    final: dict[str, Any] = {
        **last,
        # Per-period summaries, computed after the run so @regen/@rewire
        # records cover every realized graph, not just graph_at(0).
        **_graph_records(trainer.engine, spec.rounds),
        "num_focus_nodes": int(len(focus_nodes)),
        "num_spread_nodes": int(len(spread_nodes)),
        # Routing provenance, CI-gated: the large_n smoke asserts its
        # sparse_sharded run actually took the fused path.
        "backend": trainer.mix_impl,
        "fused": use_fused,
    }
    if fault_trace is not None:
        from repro.core import faults as faults_mod

        alive_counts = [
            int(fault_trace.alive(r).sum()) for r in range(spec.rounds)
        ]
        events = faults_mod.churn_rounds(alive_counts, trainer.num_nodes)
        final["faults"] = spec.faults
        final["alive_min"] = min(alive_counts)
        final["alive_final"] = alive_counts[-1]
        final["churn_rounds"] = events
        final["recovery_rounds"] = (
            faults_mod.recovery_rounds(
                [r for r, _ in curve], [a for _, a in curve], events[0]
            )
            if events
            else None
        )
    # Community runs additionally record the paper's Table-1 confusion view.
    if trainer.graph.blocks is not None and trainer.graph.num_nodes <= 256:
        from repro.train.metrics import community_confusion

        cms = trainer.confusion(ds.x_test, ds.y_test)
        blocks = trainer.graph.blocks
        num_comms = int(blocks.max()) + 1
        comm_cm = np.asarray(
            community_confusion(cms, np.asarray(blocks), num_comms)
        )
        off_diag = comm_cm.copy()
        for b in range(num_comms):
            np.fill_diagonal(off_diag[b], 0.0)
        final["community_confusion_offdiag"] = [
            float(off_diag[b].sum()) for b in range(num_comms)
        ]
        if comm_cm.size <= 1000:
            final["community_confusion"] = comm_cm.round(4).tolist()
    return final


# ---------------------------------------------------------------------------
# lm executor (LLM-cohort loop; launch/train.py wraps this)
# ---------------------------------------------------------------------------


def _run_lm(spec: ExperimentSpec, emit: Emit, verbose: bool) -> dict[str, Any]:
    import dataclasses as _dc

    from repro.configs import base as cfgbase
    from repro.train.trainer import LMCohortTrainer

    m = spec.model
    cfg = cfgbase.get(m.get("arch", "llama3.2-1b"))
    if not m.get("full_scale", False):
        cfg = _dc.replace(cfg.reduced(), param_dtype="float32", optimizer=cfg.optimizer)
    n = int(m.get("nodes", 4))

    trainer = LMCohortTrainer(
        spec.topology,
        cfg,
        nodes=n,
        batch=int(m.get("batch", 4)),
        seq=int(m.get("seq", 128)),
        lr=spec.lr,
        schedule=m.get("schedule", "cosine"),
        backend=spec.backend,
        matrix=spec.matrix,
        gossip_every=spec.gossip_every,
        compress=m.get("compress", "auto"),
        faults=spec.faults,
        seed=spec.seed,
    )
    if verbose:
        print(
            f"arch={cfg.arch_id} members={trainer.member_params/1e6:.1f}M x {n} nodes "
            f"topology={trainer.graph.name} backend={trainer.mix_impl} "
            f"optimizer={cfg.optimizer} schedule={m.get('schedule', 'cosine')} "
            f"compress={trainer.compress}"
        )

    ckpt_every, ckpt_path = int(m.get("ckpt_every", 0)), m.get("ckpt_path", "")
    if m.get("resume") and ckpt_path:
        start = trainer.restore(ckpt_path)
        if verbose:
            print(f"resumed from {ckpt_path} at round {start}")

    last: dict[str, Any] = {}

    def on_round(rec: dict[str, Any]) -> None:
        last.clear()
        last.update(rec)
        emit(rec)

    # Fused MixingProgram-staged scan by default, mirroring _run_mlp:
    # one dispatch per eval/checkpoint boundary with the chunk's token slab
    # staged on device. model={"fused": False} opts out; backends outside
    # _LM_FUSED_BACKENDS (e.g. sparse_sharded) fall back to the loop.
    use_fused = bool(m.get("fused", True)) and trainer.supports_fused
    run = trainer.run_fused if use_fused else trainer.run
    run(
        spec.rounds,
        eval_every=spec.eval_every,
        on_round=on_round,
        ckpt_every=ckpt_every,
        ckpt_path=ckpt_path,
        verbose=verbose,
    )
    cons = trainer.consensus()
    final: dict[str, Any] = {
        **last,
        # (0,) for an empty pytree — no nodes, so no distance to report
        "consensus_mean": float(cons.mean()) if cons.size else 0.0,
        "consensus_max": float(cons.max()) if cons.size else 0.0,
        **_graph_records(trainer.engine, spec.rounds),
        "members_m": round(trainer.member_params / 1e6, 2),
        "backend": trainer.mix_impl,
        "fused": use_fused,
        "compress": trainer.compress,
    }
    if trainer.faulted:
        trace = trainer.engine.fault_trace
        alive_counts = [int(trace.alive(r).sum()) for r in range(spec.rounds)]
        final["faults"] = spec.faults
        final["alive_min"] = min(alive_counts)
        final["alive_final"] = alive_counts[-1]
    return final


_EXECUTORS = {"mlp": _run_mlp, "lm": _run_lm}


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def run_spec(
    spec: ExperimentSpec,
    store: ResultsStore,
    *,
    verbose: bool = False,
    raise_on_error: bool = True,
) -> dict[str, Any]:
    """Execute one spec, streaming records to ``store``. Returns the final
    summary (also written as the ``run_end`` record)."""
    rid = spec.run_id
    store.run_start(rid, spec.to_json())
    executor = _EXECUTORS[spec.model.get("kind", "mlp")]
    t0 = time.perf_counter()
    try:
        final = executor(spec, lambda rec: store.round(rid, rec), verbose)
    except Exception as e:  # noqa: BLE001 — sweep must survive one bad spec
        store.run_end(rid, "failed", error=f"{type(e).__name__}: {e}")
        if raise_on_error:
            raise
        if verbose:
            traceback.print_exc()
        return {"status": "failed", "run_id": rid, "error": str(e)}
    store.run_end(rid, "completed", wall_s=round(time.perf_counter() - t0, 4),
                  final=final)
    return {"status": "completed", "run_id": rid, "final": final}


def _worker(args: tuple[dict[str, Any], str, bool]) -> str:
    """Multi-process entry: run one spec into a private JSONL shard."""
    spec_json, shard_path, verbose = args
    spec = ExperimentSpec.from_json(spec_json)
    run_spec(spec, ResultsStore(shard_path), verbose=verbose, raise_on_error=False)
    return shard_path


def _merge_shard(store: ResultsStore, shard: str) -> None:
    with open(shard) as f:
        store.append_lines(f)
    os.remove(shard)


def _salvage_shards(
    store: ResultsStore, shard_dir: str, verbose: bool, *, min_age_s: float = 0.0
) -> int:
    """Merge + remove shard files a dead worker (or killed parent) left in
    ``shard_dir``, then drop the directory.

    Salvaged partial shards lack their ``run_end`` line, so resume re-runs
    them; complete shards whose merge was interrupted count as completed and
    are skipped. Called before a sweep (stale shards from a previous crash,
    with ``min_age_s`` so a *concurrent* sweep's in-flight shards are left
    alone) and after this sweep's own pool has shut down (age 0: its workers
    are gone, every surviving file is quiescent)."""
    if not os.path.isdir(shard_dir):
        return 0
    import glob

    salvaged = 0
    for shard in sorted(glob.glob(os.path.join(shard_dir, "*.jsonl"))):
        try:
            if min_age_s and time.time() - os.path.getmtime(shard) < min_age_s:  # lint: allow[D002] — shard age vs file mtime needs the wall clock
                continue  # likely still being written by a live sweep
            _merge_shard(store, shard)
            salvaged += 1
        except FileNotFoundError:
            continue  # another sweep salvaged it between glob and merge
    try:
        os.rmdir(shard_dir)
    except OSError:
        pass  # a concurrent sweep may still be writing here; leave it
    if verbose and salvaged:
        print(f"salvaged {salvaged} stale shard(s) from {shard_dir}")
    return salvaged


def run_sweep(
    specs: list[ExperimentSpec],
    store_path: str,
    *,
    resume: bool = True,
    processes: int = 1,
    verbose: bool = False,
) -> dict[str, Any]:
    """Run a list of specs against one results store.

    With ``resume`` (default), specs whose run_id already has a completed
    run_end are skipped — re-running a finished sweep is a no-op. With
    ``processes > 1``, specs fan out over a spawn-context process pool; each
    worker writes a private shard merged into the main store on completion.
    The pool runs only under ``JAX_PLATFORMS=cpu``: every worker opens its
    own JAX backend, and an accelerator belongs to one process at a time.
    """
    store = ResultsStore(store_path)
    shard_dir = store_path + ".shards"
    # A previous sweep's crash; the age floor spares a concurrent sweep's
    # in-flight shards (they are fsynced per record, so a genuinely stale
    # file stops aging the moment its writer dies).
    _salvage_shards(store, shard_dir, verbose, min_age_s=60.0)
    done = store.completed() if resume else set()
    todo = [s for s in specs if s.run_id not in done]
    skipped = len(specs) - len(todo)
    if verbose and skipped:
        print(f"resume: skipping {skipped} completed run(s)")

    statuses: list[dict[str, Any]] = []
    if processes <= 1 or len(todo) <= 1:
        for i, spec in enumerate(todo):
            if verbose:
                print(f"[{i + 1}/{len(todo)}] {spec.run_id}  ({spec.topology} "
                      f"x {spec.partitioner})")
            statuses.append(
                run_spec(spec, store, verbose=verbose, raise_on_error=False)
            )
    else:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            raise RuntimeError(
                f"run_sweep(processes={processes}) would start {processes} JAX "
                "processes, and an accelerator belongs to one process at a "
                "time: the workers would fail or hang waiting for the chip. "
                "Set JAX_PLATFORMS=cpu to fan out on the CPU, or use "
                "processes=1 to run every spec in this process."
            )
        import concurrent.futures as cf
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        os.makedirs(shard_dir, exist_ok=True)
        jobs = [
            (s.to_json(), os.path.join(shard_dir, f"{s.run_id}.jsonl"), verbose)
            for s in todo
        ]
        # ProcessPoolExecutor, not mp.Pool: a worker killed mid-run (OOM,
        # signal) raises BrokenProcessPool on the victim's future, whereas
        # Pool.imap_unordered silently respawns the worker and blocks on the
        # lost result forever — the sweep must fail that run, not deadlock.
        try:
            with cf.ProcessPoolExecutor(
                max_workers=min(processes, len(jobs)), mp_context=ctx
            ) as pool:
                futs = [pool.submit(_worker, j) for j in jobs]
                for fut in cf.as_completed(futs):
                    try:
                        _merge_shard(store, fut.result())
                    except Exception as e:  # noqa: BLE001 — keep draining;
                        # a broken pool fails the remaining futures fast and
                        # each shows up as a failed (re-runnable) run below.
                        if verbose:
                            print(f"worker failed: {type(e).__name__}: {e}")
        finally:
            # Salvage whatever OUR workers left behind (a killed worker's
            # partial shard) — only this sweep's own filenames; a concurrent
            # sweep's in-flight shards in the shared dir are not ours to take.
            for _, shard, _ in jobs:
                try:
                    _merge_shard(store, shard)
                except FileNotFoundError:
                    pass  # merged in the loop above
            try:
                os.rmdir(shard_dir)
            except OSError:
                pass  # non-empty: a concurrent sweep is still writing here
        finals = store.finals()
        statuses = [
            {"status": "completed" if s.run_id in finals else "failed",
             "run_id": s.run_id}
            for s in todo
        ]

    failed = [s["run_id"] for s in statuses if s["status"] != "completed"]
    return {
        "total": len(specs),
        "ran": len(todo),
        "skipped": skipped,
        "failed": failed,
        "store": store.path,
    }
