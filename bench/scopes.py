"""Device time by the program's named scopes, and idle time by its host spans.

The program names each part of a DecAvg round with a ``jax.named_scope``
(``decavg.batch``, ``decavg.local_grad``, ``decavg.sgd_update``,
``decavg.fault_mask``, ``decavg.mix``, ``decavg.halo_exchange``,
``decavg.eval``; ``src/repro/obs.py``), and each part of a ``run_fused``
call with a host span (``trainer.stage``, ``trainer.dispatch``,
``trainer.fetch``). Two reductions read them:

- ``scoped_ops_from_xplane`` gives each device op of a profiler trace with
  the innermost ``decavg.*`` scope of its ``op_name`` (``unscoped`` where it
  has none), and ``scope_ns`` sums them per scope over a window;
- ``span_idle_share`` is the share of a window in which a chip ran no op
  while the host was inside one span, from the dict ``devtrace`` builds.

A TPU v5e trace names each op by its HLO text with no metadata and no
``op_name`` stat, and holds no HLO module for programs compiled before the
trace began. So the scope comes from the compiled modules' own text
(``DecentralizedTrainer.fused_chunk_hlo``): an op is found there by its
name, result type and opcode, which the trace prints as the compiler does.

Per round, from a trace of ``--rounds`` rounds of a cell (run on the chip
that wrote the trace, so the chunk programs compile for it):

    python3 bench/scopes.py <trace.xplane.pb> --workload <cell> --seed <n> --rounds <n>

over the trace's ``bench.trace_window``.
"""

from __future__ import annotations

import argparse
import re
import sys

from devtrace import _CONTAINER, _DEVICE_PLANE, _OPS_LINE, busy_intervals, events_from_xplane, op_name

UNSCOPED = "unscoped"
_SCOPE = re.compile(r"(?:^|/)(decavg\.[a-z_]+)(?=/|$)")
# ``%fusion.12 = f32[100,512]{1,0:T(8,128)} fusion(`` -> the part before the
# operands, which the compiled text and the trace print alike.
_KEY = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+ = .*?\s[a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(name_path: str) -> str:
    """The innermost ``decavg.*`` component of an ``op_name`` path."""
    found = _SCOPE.findall(name_path or "")
    return found[-1] if found else UNSCOPED


def hlo_scopes(hlo_texts) -> dict[str, str]:
    """Scope of every instruction of compiled HLO modules, keyed by its name,
    result type and opcode. Where two modules disagree, the first wins."""
    out: dict[str, str] = {}
    for text in hlo_texts:
        for line in text.splitlines():
            key = _KEY.match(line)
            if key:
                path = _OP_NAME.search(line)
                out.setdefault(key.group(1), scope_of(path.group(1) if path else ""))
    return out


def scoped_ops_from_xplane(path: str, hlo_texts) -> dict[str, list]:
    """``{device: [[scope, start_ns, dur_ns], ...]}`` for every device op of
    the trace, sorted by start, each op's scope looked up in ``hlo_texts``
    (the compiled text of every program the trace ran); ops of other
    programs are ``unscoped``. Loops and conds, whose time is their body's,
    are left out, so the ops of one chip do not overlap."""
    from jax.profiler import ProfileData

    scopes = hlo_scopes(hlo_texts)
    out: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        evs = out.setdefault(m.group(1), [])
        for line in plane.lines:
            if line.name != _OPS_LINE:
                continue
            for e in line.events:
                if _CONTAINER.match(op_name(e.name)):
                    continue
                key = _KEY.match(e.name)
                scope = scopes.get(key.group(1), UNSCOPED) if key else UNSCOPED
                evs.append([scope, int(e.start_ns), int(e.duration_ns)])
        evs.sort(key=lambda e: e[1])
    return out


def scope_ns(events, window) -> dict[str, int]:
    """Device ns per scope of one chip's ``[scope, start_ns, dur_ns]`` ops,
    each clipped to ``window`` ``[lo, hi)``."""
    lo, hi = window
    out: dict[str, int] = {}
    for scope, s, d in events:
        ns = min(s + d, hi) - max(s, lo)
        if ns > 0:
            out[scope] = out.get(scope, 0) + ns
    return out


def _overlap_ns(a: list, b: list) -> int:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def span_idle_share(trace: dict | None, span: str) -> float | None:
    """Percent of the window in which a chip ran no op while the host was
    inside ``span``, mean over the chips. ``None`` where the trace holds no
    ``trainer.`` span at all (a program without them)."""
    if not trace or not trace["devices"] or not any(h[0].startswith("trainer.") for h in trace["host"]):
        return None
    lo, hi = trace["window"]
    inside = busy_intervals([h for h in trace["host"] if h[0] == span], lo, hi)
    length = sum(e - s for s, e in inside)
    shares = [
        (length - _overlap_ns(inside, busy_intervals(evs, lo, hi))) / (hi - lo)
        for evs in trace["devices"].values()
    ]
    return 100.0 * sum(shares) / len(shares)


def cell_hlo_texts(workload: str, seed: int) -> list[str]:
    """Compiled text of every chunk program one call of the cell runs."""
    import run

    files = run.cell_files(workload)
    cell = run.load_module("systems", files["config"]["system"]).Cell(
        files["config"], files["traffic"], seed)
    texts = cell.trainer.fused_chunk_hlo(
        cell.rounds, eval_every=cell.eval_every, x_test=cell.x_test, y_test=cell.y_test)
    return list(texts.values())


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("xplane")
    p.add_argument("--workload", required=True, help="the cell the trace ran")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rounds", type=int, required=True, help="rounds the trace ran")
    args = p.parse_args(argv)
    devices = scoped_ops_from_xplane(args.xplane, cell_hlo_texts(args.workload, args.seed))
    if not devices:
        print("no device ops in the trace", file=sys.stderr)
        return 1
    window = events_from_xplane(args.xplane, args.rounds)["window"]
    total: dict[str, int] = {}
    for evs in devices.values():
        for k, v in scope_ns(evs, window).items():
            total[k] = total.get(k, 0) + v
    busy = sum(total.values())
    for k, v in sorted(total.items(), key=lambda kv: -kv[1]):
        ms = v / len(devices) / args.rounds / 1e6
        print(f"{k:24s} {ms:10.4f} ms/round {100 * v / busy:6.2f}% of op time")
    return 0


if __name__ == "__main__":
    sys.exit(main())
