"""Fused-vs-loop training throughput: rounds/s over a full multi-round run.

The Python-loop trainer pays per round: host-side batch gather, a
device transfer, and a jit dispatch (plus a re-trace at every schedule
period before PR 5). ``run_fused`` compiles the whole run into lax.scan
chunks — one dispatch per eval (a single scan when no eval runs) with
batches sampled on device — so the gap between the two is pure
orchestration overhead, the quantity this benchmark pins.

Per row (the acceptance configs are N=100 dense / 200 rounds, and the
N=128 ring sparse_sharded row over 8 fake CPU devices in a subprocess):

  - loop_rounds_per_s / fused_rounds_per_s: whole-run throughput, timed on
    a second run after a warm-up run has paid all compiles.
  - speedup: fused / loop (CI guards >= 2x on the N=100 dense row and the
    sparse_sharded row).
  - max_abs_param_err: fused-vs-loop parameter agreement for the row's
    config (same seed, fresh trainers) — the speed claim is only worth
    reporting if the two paths still compute the same thing. Exactly 0.0
    for sparse / sparse_sharded (shared CSR staging and mix body);
    ~1e-3-scale for sparse_pallas after its row's 20 rounds, whose fused
    blocked kernel and loop scalar kernel sum tiles in different orders
    (~1e-7 per mix, compounded by the SGD rounds in between).

Emits BENCH_rounds.json at the repo root.

Baselines are machine-relative: a 2026-08 same-machine bisect of an apparent
sparse-row "regression" (2.1x -> 1.4x) found PR-era and current HEAD within
noise of each other — the historical figure came from a different runner.
When a row drifts, re-run the OLD commit on the CURRENT machine (git
worktree) before treating the delta as a code regression; CI floors (2x
dense/sharded, 1.2x sparse) are set below same-machine variance. The output
embeds a ``machine`` fingerprint (platform / CPU count / jax version) so a
committed re-baseline records where its numbers came from — never hand-edit
rows; regenerate the whole file with this script.

Run:  PYTHONPATH=src python benchmarks/bench_rounds.py [--rounds 200]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from repro.core import partition as P
from repro.core.machine import machine_fingerprint
from repro.data.loader import NodeLoader
from repro.data.synthetic import make_mnist_like
from repro.models.mlp import init_mlp
from repro.train.trainer import DecentralizedTrainer

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_rounds.json")

# Small members on purpose: the bench isolates per-round *orchestration*
# overhead (host sampling, transfer, dispatch), so per-round compute must not
# drown it. large_n-preset-sized members (hidden=[64]) shift both paths by
# the same compute constant; the fused win converges to 1x as members grow.
DIM = 32
HIDDEN = (32,)
BATCH = 16


# The sharded row runs in a subprocess (8 fake CPU devices need XLA_FLAGS
# set before jax imports) on the paper's canonical ring topology: a regular
# graph keeps the per-shard nnz balanced, so the stacked ShardedCSR pads to
# ~uniform width and the row isolates orchestration overhead rather than
# BA hub skew. halo_schedule stays "auto" (resolves to ring here).
SHARDED_N = 128
SHARDED_SHARDS = 8
SHARDED_ROUNDS = 100


def make_trainer(
    n: int, backend: str, ds, seed: int = 0, topology: str | None = None,
    faults: str | None = None,
) -> DecentralizedTrainer:
    parts = P.iid(ds.y_train, n, seed=seed)
    loader = NodeLoader(ds.x_train, ds.y_train, parts, batch_size=BATCH, seed=seed)
    return DecentralizedTrainer(
        topology or f"ba:n={n},m=2",
        loader,
        lr=0.05,
        momentum=0.9,
        mix_impl=backend,
        seed=seed,
        faults=faults,
        init_fn=lambda k: init_mlp(k, in_dim=DIM, hidden=HIDDEN, num_classes=10),
    )


def _time_run(run, rounds: int, reps: int = 3) -> float:
    """Best-of-``reps`` whole-run wall clock (after one compile warm-up).

    Best-of, not mean: transient CPU contention on shared runners only ever
    slows a run down, and it biases both paths identically.
    """
    run(rounds)  # warm-up: pays every compile in the path
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run(rounds)
        jax.block_until_ready(jax.tree.leaves(run.__self__.params))
        best = min(best, time.perf_counter() - t0)
    return best


def _param_err(n: int, backend: str, ds, rounds: int) -> float:
    """Fused-vs-loop divergence over the SAME round count the row reports."""
    a = make_trainer(n, backend, ds)
    a.run(rounds)
    b = make_trainer(n, backend, ds)
    b.run_fused(rounds)
    return max(
        float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
        for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params))
    )


def bench_one(n: int, backend: str, rounds: int, ds) -> dict:
    loop_s = _time_run(make_trainer(n, backend, ds).run, rounds)
    fused_s = _time_run(make_trainer(n, backend, ds).run_fused, rounds)
    row = {
        "n": n,
        "backend": backend,
        "rounds": rounds,
        "loop_rounds_per_s": round(rounds / loop_s, 1),
        "fused_rounds_per_s": round(rounds / fused_s, 1),
        "speedup": round(loop_s / fused_s, 2),
        "max_abs_param_err": _param_err(n, backend, ds, rounds),
    }
    print(
        f"n={n:4d} {backend:6s} loop {row['loop_rounds_per_s']:8.1f} r/s   "
        f"fused {row['fused_rounds_per_s']:8.1f} r/s   "
        f"speedup {row['speedup']:.2f}x   err {row['max_abs_param_err']:.2e}"
    )
    return row


def _sharded_worker() -> None:
    """Runs in a subprocess with 8 fake CPU devices; prints one JSON row.

    Fused and loop reps are interleaved (fused, loop, fused, loop, ...) so
    transient load hits both paths alike, and best-of is still the
    estimator. max_abs_param_err must be exactly 0.0: both paths run the
    same ``_sharded_mix_leaf`` body on the same staged ShardedCSR.
    """
    ds = make_mnist_like(train_per_class=200, test_per_class=50, dim=DIM, seed=0)
    topo = f"ring:n={SHARDED_N}"
    rounds = SHARDED_ROUNDS
    fused = make_trainer(SHARDED_N, "sparse_sharded", ds, topology=topo)
    loop = make_trainer(SHARDED_N, "sparse_sharded", ds, topology=topo)
    shards = fused.engine.program(rounds, kind="sparse_sharded").shards
    fused.run_fused(rounds)  # pays every compile
    loop.run(rounds)
    fused_s = loop_s = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        fused.run_fused(rounds)
        jax.block_until_ready(jax.tree.leaves(fused.params))
        fused_s = min(fused_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        loop.run(rounds)
        jax.block_until_ready(jax.tree.leaves(loop.params))
        loop_s = min(loop_s, time.perf_counter() - t0)
    a = make_trainer(SHARDED_N, "sparse_sharded", ds, topology=topo)
    a.run(rounds)
    b = make_trainer(SHARDED_N, "sparse_sharded", ds, topology=topo)
    b.run_fused(rounds)
    err = max(
        float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
        for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params))
    )
    row = {
        "n": SHARDED_N,
        "backend": "sparse_sharded",
        "topology": topo,
        "shards": shards,
        "halo_schedule": "auto",
        "rounds": rounds,
        "loop_rounds_per_s": round(rounds / loop_s, 1),
        "fused_rounds_per_s": round(rounds / fused_s, 1),
        "speedup": round(loop_s / fused_s, 2),
        "max_abs_param_err": err,
    }
    print(json.dumps(row))


# The faulted fused row's fault spec: all three clause kinds active so the
# row pays every mask (per-round renormalization, dead-node where, straggler
# ring buffer) — the worst case the CI overhead guard (<= 1.4x fault-free)
# is meant to bound.
FAULT_SPEC = "churn:p_leave=0.05,p_join=0.5;straggler:frac=0.2,delay=3;drop:p_edge=0.1"


def bench_faulted(n: int, rounds: int, ds) -> dict:
    """Fused dense row under a full fault schedule, vs its fault-free twin.

    ``fault_overhead`` = fault-free fused rounds/s over faulted fused
    rounds/s (>= 1.0 means masking costs throughput; CI guards <= 1.4x).
    The two fused rates are measured INTERLEAVED (clean, faulted, clean,
    ...) rather than reusing the dense row timed minutes earlier: shared
    runners drift over a multi-minute bench run, and a rate ratio is only
    meaningful between adjacent measurements (same estimator as the
    sharded worker's fused/loop interleave).
    """
    loop_s = _time_run(
        make_trainer(n, "dense", ds, faults=FAULT_SPEC).run, rounds
    )
    faulted = make_trainer(n, "dense", ds, faults=FAULT_SPEC)
    clean = make_trainer(n, "dense", ds)
    faulted.run_fused(rounds)  # warm-up: pays every compile in each path
    clean.run_fused(rounds)
    fused_s = clean_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        clean.run_fused(rounds)
        jax.block_until_ready(jax.tree.leaves(clean.params))
        clean_s = min(clean_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        faulted.run_fused(rounds)
        jax.block_until_ready(jax.tree.leaves(faulted.params))
        fused_s = min(fused_s, time.perf_counter() - t0)
    a = make_trainer(n, "dense", ds, faults=FAULT_SPEC)
    a.run(rounds)
    b = make_trainer(n, "dense", ds, faults=FAULT_SPEC)
    b.run_fused(rounds)
    err = max(
        float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
        for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params))
    )
    row = {
        "n": n,
        "backend": "dense",
        "faults": FAULT_SPEC,
        "rounds": rounds,
        "loop_rounds_per_s": round(rounds / loop_s, 1),
        "fused_rounds_per_s": round(rounds / fused_s, 1),
        "speedup": round(loop_s / fused_s, 2),
        "fault_overhead": round(fused_s / clean_s, 3),
        "max_abs_param_err": err,
    }
    print(
        f"n={n:4d} dense+faults loop {row['loop_rounds_per_s']:8.1f} r/s   "
        f"fused {row['fused_rounds_per_s']:8.1f} r/s   "
        f"overhead {row['fault_overhead']:.3f}x   err {row['max_abs_param_err']:.2e}"
    )
    return row


def bench_sharded() -> dict:
    """The sparse_sharded row, via a subprocess with an 8-device mesh."""
    env = dict(os.environ)
    # Fake CPU devices, and never the accelerator: this parent may already
    # hold the chip, which a second process cannot open.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={SHARDED_SHARDS}"
    ).strip()
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker-sharded"],
        capture_output=True,
        text=True,
        env=env,
        timeout=1200,
    )
    if r.returncode != 0:
        raise RuntimeError(f"sharded bench worker failed:\n{r.stderr[-2000:]}")
    row = json.loads(r.stdout.strip().splitlines()[-1])
    print(
        f"n={row['n']:4d} {row['backend']:6s} "
        f"loop {row['loop_rounds_per_s']:8.1f} r/s   "
        f"fused {row['fused_rounds_per_s']:8.1f} r/s   "
        f"speedup {row['speedup']:.2f}x   err {row['max_abs_param_err']:.2e}"
        f"   ({row['topology']}, {row['shards']} shards)"
    )
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--out", default=OUT_PATH)
    ap.add_argument(
        "--worker-sharded", action="store_true", help=argparse.SUPPRESS
    )
    args = ap.parse_args()
    if args.worker_sharded:
        _sharded_worker()
        return

    ds = make_mnist_like(train_per_class=200, test_per_class=50, dim=DIM, seed=0)
    rows = [
        # the acceptance row: N=100 dense at the full round count
        bench_one(100, "dense", args.rounds, ds),
        # informational: the sparse program at larger N, fewer rounds
        bench_one(256, "sparse", max(args.rounds // 2, 10), ds),
        # the Pallas blocked-ELL program (interpret mode on CPU, so small
        # and short — the point is the per-round dispatch gap, which the
        # interpreted kernel makes enormous in absolute terms)
        bench_one(64, "sparse_pallas", max(args.rounds // 10, 5), ds),
        # the sharded acceptance row: CI guards >= 2x and err == 0.0
        bench_sharded(),
        # full fault schedule on the dense acceptance config: CI guards
        # fault_overhead <= 1.4x the fault-free fused rate
        bench_faulted(100, args.rounds, ds),
    ]
    out = {
        "bench": "fused vs loop training rounds/s (benchmarks/bench_rounds.py)",
        "device": str(jax.devices()[0]),
        "machine": machine_fingerprint(),
        "config": {
            "topology": "ba:m=2 (rows with a 'topology' key override it)",
            "dim": DIM, "hidden": list(HIDDEN),
            "batch": BATCH, "lr": 0.05, "momentum": 0.9, "eval": "none (pure training)",
        },
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"wrote {os.path.abspath(args.out)}")


if __name__ == "__main__":
    main()
