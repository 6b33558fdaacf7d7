"""Decentralized LLM-cohort training driver — a thin CLI over the experiment
harness (repro/experiments/runner.py, model kind "lm").

The CLI builds one ExperimentSpec and hands it to ``runner.run_spec``: the
training loop, per-step JSONL streaming and the run-id bookkeeping all live
in the harness, so single runs land in the same results-store format as
sweeps (``--store``, default results/train_runs.jsonl).

Two modes:
- default (CPU-runnable): reduced member models, real data, real DecAvg
  steps — the full training loop with checkpointing and the WSD/cosine
  schedules. This is what CI and the examples exercise.
- ``--lower-only``: build the FULL-scale step for the production mesh and
  stop after .lower().compile() (delegates the heavy lifting to dryrun.py's
  builders) — use launch/dryrun.py for the complete sweep.

Run:  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --steps 50
"""

from __future__ import annotations

import argparse

from repro.core import decavg, machine
from repro.experiments import runner
from repro.experiments.spec import ExperimentSpec
from repro.experiments.store import ResultsStore


def _parse_compress(value: str):
    """--compress flag: 'auto' (default), 'none'/'off', or a top-k fraction."""
    if value == "auto":
        return "auto"
    if value in ("none", "off"):
        return None
    return float(value)


def build_spec(args: argparse.Namespace) -> ExperimentSpec:
    """One LM-cohort ExperimentSpec from the CLI flags.

    Non-default execution knobs (compress/fused/resume) are only added to
    the model dict when set — combined with canonical()'s default-stripping
    this keeps pre-existing run ids (and store resume semantics) stable.
    """
    model = {
        "kind": "lm",
        "arch": args.arch,
        "nodes": args.nodes,
        "batch": args.batch,
        "seq": args.seq,
        "schedule": args.schedule,
        "full_scale": bool(args.full_scale),
        "ckpt_every": args.ckpt_every,
        "ckpt_path": args.ckpt_path,
    }
    compress = _parse_compress(args.compress)
    if compress != "auto":
        model["compress"] = compress
    if not args.fused:
        model["fused"] = False
    if args.resume:
        model["resume"] = True
    return ExperimentSpec(
        topology=args.topology,
        partitioner="iid",  # LM cohorts share the token stream (tokens.py)
        backend=args.mix_backend,
        rounds=args.steps,
        eval_every=20,
        lr=args.lr,
        gossip_every=args.gossip_every,
        faults=args.faults,
        seed=args.seed,
        model=model,
        tag="launch.train",
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--topology", default="ring",
                    help="topology registry spec, e.g. 'ring', 'ba:n=8,m=2', "
                         "'er:p=0.3@regen=10' (n defaults to --nodes; "
                         "see core/topology.py for the grammar)")
    ap.add_argument("--mix-backend", default="auto",
                    choices=["auto"] + list(decavg.GossipEngine.BACKENDS),
                    help="gossip backend (auto: sparse at large N, else dense)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine", choices=["const", "cosine", "wsd"])
    ap.add_argument("--gossip-every", type=int, default=1)
    ap.add_argument("--compress", default="auto",
                    help="CHOCO top-k gossip fraction in (0,1], 'none'/'off', "
                         "or 'auto' (on for members above ~1 MB of pytree)")
    ap.add_argument("--no-fused", dest="fused", action="store_false",
                    help="force the per-round Python loop instead of the "
                         "fused lax.scan path")
    ap.add_argument("--faults", default=None,
                    help="fault-injection spec (core/faults.py grammar)")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-path", default="results/train_ckpt.npz")
    ap.add_argument("--resume", action="store_true",
                    help="restore (params, opt, step) from --ckpt-path and "
                         "continue bit-identically from the saved round")
    ap.add_argument("--full-scale", action="store_true",
                    help="use the unreduced arch config (requires TPU-scale memory)")
    ap.add_argument("--store", default="results/train_runs.jsonl",
                    help="results JSONL (same schema as the sweep store)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    machine.use_compile_cache()
    spec = build_spec(args)
    result = runner.run_spec(spec, ResultsStore(args.store), verbose=True)
    final = result["final"]
    spread = final.get("g2_token_spread")
    spread_s = f"  g2_spread {spread:.4f}" if spread is not None else ""
    print(
        f"done in {final['wall_s']:.0f}s  loss {final['loss']:.4f}  "
        f"consensus {final['consensus_mean']:.3g}{spread_s}  "
        f"-> {args.store} ({result['run_id']})"
    )


if __name__ == "__main__":
    main()
