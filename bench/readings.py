"""Readings that set a cell's limits: the program, the control and the faults.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 [--control] \
        [--faults half_batch,answer_altered,no_exchange] [--program-precision P]

For each seed, in one process: the cell's warm-up call through the program
(exactly as ``run.py`` makes it, no window), its numbers against the plain
reference (the lower readings); with ``--control``, the reference computed
at the next precision down (``high``) in the program's place (the upper
readings); with ``--faults``, the reference with each planted fault in the
program's place. ``--program-precision`` runs the program at another
matmul precision than its configuration states, to show what that costs.
Prints one JSON line per seed and reading, then a summary line.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", action="store_true")
    p.add_argument("--faults", default="")
    p.add_argument("--program-precision", default=None)
    args = p.parse_args(argv)
    files = run.cell_files(args.workload)
    cfg = dict(files["config"])
    run.check_devices(int(files["cell"]["chips"]), files["peaks"])
    run.use_compile_cache()
    system = run.load_module("systems", cfg["system"])
    if args.program_precision:
        cfg["matmul_precision"] = args.program_precision
    summary: dict = {}

    def record(kind: str, seed: int, numbers: dict, seconds: float) -> None:
        print(json.dumps({"kind": kind, "seed": seed, "numbers": numbers,
                          "seconds": seconds}), flush=True)
        agg = summary.setdefault(kind, {})
        for k, v in numbers.items():
            lo, hi = agg.get(k, (v, v))
            agg[k] = (min(lo, v), max(hi, v))

    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        cell = system.Cell(cfg, files["traffic"], seed)
        obs = cell.first_call(run.annotate)
        cell.free()
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        ref = cell.reference()
        record("program", seed, system.compare(obs, ref),
               [t_prog, time.perf_counter() - t])
        if args.control:
            t = time.perf_counter()
            ctl = cell.reference(precision="high")
            record("control", seed, system.compare(ctl, ref),
                   time.perf_counter() - t)
        for variant in filter(None, args.faults.split(",")):
            t = time.perf_counter()
            bad = cell.reference(variant=variant)
            record(f"fault:{variant}", seed, system.compare(bad, ref),
                   time.perf_counter() - t)
        del cell
    print(json.dumps({"summary": {k: {n: list(v) for n, v in agg.items()}
                                  for k, agg in summary.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
