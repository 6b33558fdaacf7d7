"""Run one benchmark cell on the chips of this machine; print one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data the harness finds by name:
``BENCHMARK.json`` (at the root of the checkout) names the cell's
configuration, traffic and chips; ``bench/configs/<config>.json`` holds the
configuration, whose ``system`` names the module under ``bench/systems/``
that builds and drives the program; ``bench/traffic/<traffic>.json`` the
traffic; ``bench/workloads/<cell>.json`` the limits of the numbers that
decide ``correct``; ``bench/metrics/<metric>.py`` one reader per metric;
``bench/peaks.json`` the chip's peaks by ``device_kind``.

A run: set-up (inputs and weights from ``--seed``, the program built, one
warm-up call that compiles every program the window uses) is timed as
``setup_s``; the window repeats the cell's call for ``--seconds`` seconds and
fails the run if anything compiles inside it; ``--trace 1`` then traces a
few more calls with the profiler; last, with the program's state freed, the
plain reference checks what the warm-up call produced. With ``--trace 0``
the line carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics. Exits non-zero, printing no result, off a TPU, on a chip
missing from the peaks table, or on fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"  # traces; a fixed path inside the checkout
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


class Refused(SystemExit):
    """The machine or the request does not fit the cell: exit 2, no result."""

    def __init__(self, msg: str):
        print(f"bench: {msg}", file=sys.stderr)
        super().__init__(2)


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no {kind} module {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_files(workload: str) -> dict:
    """Everything the harness reads about one cell, found by name."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"unknown workload {workload!r}; one of {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {
        "cell": cell,
        "config": json.loads((ROOT / cfg_entry["file"]).read_text()),
        "traffic": json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads((BENCH / "workloads" / f"{workload}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
        "peaks": json.loads((BENCH / "peaks.json").read_text()),
    }


def check_devices(chips: int, peaks: dict) -> list:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise Refused(f"JAX found no TPU (first device: {d.platform!r}); nothing is measured off the chip")
    if d.device_kind not in peaks:
        raise Refused(f"no peaks for device kind {d.device_kind!r} in bench/peaks.json")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def use_compile_cache() -> None:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set, else at
    ``.jax_cache`` in the checkout: a fixed path, part of the cache's key."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Counts programs lowered while active (any new trace or shape)."""

    _EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _secs, **_kw) -> None:
        if self.active and name == self._EVENT:
            self.count += 1

    @contextlib.contextmanager
    def watch(self):
        self.active = True
        try:
            yield self
        finally:
            self.active = False


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def memory_peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)


def traced_segment(cell, calls: int, counter: CompileCounter) -> dict:
    """Trace ``calls`` more calls; returns the trace reduced to events."""
    import jax

    from devtrace import events_from_xplane

    tdir = OUT / "trace"
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(str(tdir))
    rounds = 0
    with counter.watch():
        with annotate("bench.trace_window"):
            for _ in range(calls):
                with annotate("bench.call"):
                    rounds += cell.call(annotate)
            cell.block()
    jax.profiler.stop_trace()
    (path,) = tdir.glob("plugins/profile/*/*.xplane.pb")
    events = events_from_xplane(str(path), rounds)
    shutil.rmtree(tdir, ignore_errors=True)
    return events


def run(args, *, files: dict | None = None) -> dict:
    """One run of a cell; tests hand in ``files`` of their own."""
    files = files or cell_files(args.workload)
    cell_spec, cfg = files["cell"], files["config"]
    chips = int(cell_spec["chips"])
    import jax

    devs = check_devices(chips, files["peaks"])
    peaks = files["peaks"][devs[0].device_kind]
    use_compile_cache()
    devs = devs[:chips]
    counter = CompileCounter()
    system = load_module("systems", cfg["system"])
    with annotate("bench.setup.build"):
        cell = system.Cell(cfg, files["traffic"], args.seed)
    with annotate("bench.setup.first_call"):
        obs = cell.first_call(annotate)
    setup_s = time.perf_counter() - _T0

    rounds = 0
    with counter.watch():
        t0 = time.perf_counter()
        while True:
            with annotate("bench.call"):
                rounds += cell.call(annotate)
            if time.perf_counter() - t0 >= args.seconds:
                break
        cell.block()
        window_s = time.perf_counter() - t0
    mem = memory_peak_bytes(devs)
    trace = None
    if args.trace:
        trace = traced_segment(cell, int(files["limits"].get("trace_calls", 1)), counter)
    counts = cell.counts()

    cell.free()
    numbers = system.compare(obs, cell.reference())
    checks = {"window_compiles": {"value": counter.count, "limit": 0}}
    for name, value in numbers.items():
        checks[name] = {"value": value, "limit": files["limits"]["limits"].get(name)}
    correct = all(c["limit"] is not None and c["value"] <= c["limit"] for c in checks.values())

    ctx = {
        "setup_s": setup_s, "window_s": window_s, "rounds": rounds,
        "memory_peak_bytes": mem, "chips": chips, "peaks": peaks,
        "counts": counts, "trace": trace,
    }
    metrics = {}
    for m in files["per_layer" if args.trace else "end_to_end"]:
        value = load_module("metrics", m["name"]).compute(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": rounds, "failed": 0,
           "metrics": metrics, "device": device}
    if trace is not None:
        from devtrace import busy_ns, idle_gaps, top_device_ops

        lo, hi = trace["window"]
        busy = [busy_ns(trace, k) for k in trace["devices"]]
        device["busy_s"] = sum(busy) / max(len(busy), 1) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {"device_ops": top_device_ops(trace),
                            "idle_gaps": idle_gaps(trace, min(trace["devices"], default="0"))}
    out["checks"] = checks
    return out


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    out = run(parse_args(argv))
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
