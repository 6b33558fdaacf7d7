"""Sweep CLI: run a preset (or a spec-grid JSON file) through the runner.

    python -m repro.experiments.sweep --preset smoke
    python -m repro.experiments.sweep --preset paper --processes 4
    python -m repro.experiments.sweep --specs my_grid.json --store results/my.jsonl

Re-running the same command is idempotent: completed runs (matched by the
spec content hash) are skipped; pass --fresh to re-run everything. After the
runs, the analysis join prints the headline tables and writes the
machine-readable summary (--bench-out, default BENCH_sweep.json).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from repro.core import machine
from repro.experiments import analysis, presets, runner
from repro.experiments.spec import ExperimentSpec
from repro.experiments.store import ResultsStore


def _load_specs(args: argparse.Namespace) -> list[ExperimentSpec]:
    if args.specs:
        with open(args.specs) as f:
            return [ExperimentSpec.from_json(d) for d in json.load(f)]
    return presets.get_preset(args.preset)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.experiments.sweep", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--preset", default="smoke", choices=sorted(presets.PRESETS),
                    help="experiment matrix to run (default: smoke)")
    ap.add_argument("--specs", default="",
                    help="JSON file with a list of ExperimentSpec dicts "
                         "(overrides --preset)")
    ap.add_argument("--store", default="",
                    help="results JSONL path (default: results/sweep_<preset>.jsonl)")
    ap.add_argument("--processes", type=int, default=1,
                    help="fan specs out over N worker processes")
    ap.add_argument("--fresh", action="store_true",
                    help="ignore completed runs in the store (no resume)")
    ap.add_argument("--bench-out", default="BENCH_sweep.json",
                    help="machine-readable summary path ('' to skip)")
    ap.add_argument("--list", action="store_true",
                    help="print the expanded run list and exit")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    specs = _load_specs(args)
    if args.list:
        for s in specs:
            print(f"{s.run_id}  {s.topology}  {s.partitioner}  seed={s.seed}")
        return 0

    machine.use_compile_cache()
    # Custom spec files get their own store + label, never the preset's.
    matrix_name = (
        os.path.splitext(os.path.basename(args.specs))[0] if args.specs
        else args.preset
    )
    store_path = args.store or f"results/sweep_{matrix_name}.jsonl"
    verbose = not args.quiet
    summary = runner.run_sweep(
        specs, store_path, resume=not args.fresh,
        processes=args.processes, verbose=verbose,
    )
    print(
        f"sweep done: {summary['ran']} ran, {summary['skipped']} skipped "
        f"(resume), {len(summary['failed'])} failed -> {summary['store']}"
    )
    for rid in summary["failed"]:
        print(f"  FAILED: {rid}")

    store = ResultsStore(store_path)
    rows = analysis.summarize(store)
    if verbose:
        print()
        print(analysis.render_tables(rows))
    if args.bench_out:
        bench = analysis.write_bench(
            store, args.bench_out, rows=rows, extra={"preset": matrix_name}
        )
        print(f"\nwrote {args.bench_out} ({bench['runs']} runs)")
    return 1 if summary["failed"] else 0


if __name__ == "__main__":
    sys.exit(main())
