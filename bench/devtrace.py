"""From a profiler trace to the few numbers the benchmark reads.

``events_from_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote
into a plain, JSON-ready dict:

    {"window": [start_ns, end_ns],       # the traced segment
     "rounds": <rounds run in it>,
     "devices": {"0": [[name, start_ns, dur_ns], ...], ...},  # XLA ops
     "host": [[name, start_ns, dur_ns], ...]}                 # host spans

The reducers below work on that dict alone, so they can be checked on a
small trace kept with the tests.
"""

from __future__ import annotations

import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
# Ops that only hold other ops (a scan's loop, a cond): their time is
# their body's, which the trace lists op by op.
_CONTAINER = re.compile(r"^(while|conditional|call)\b")


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def events_from_xplane(path: str, rounds: int, window_span: str = "bench.trace_window") -> dict:
    """Device ops and host spans of a trace; the window is the host span
    named ``window_span``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            evs = devices.setdefault(m.group(1), [])
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    evs.extend(
                        [op_name(e.name), int(e.start_ns), int(e.duration_ns)]
                        for e in line.events
                    )
        elif plane.name == "/host:CPU":
            # Annotations land on the Python tracer's line or on the thread's
            # own; runtime threads add what the host runtime was doing.
            for line in plane.lines:
                host.extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events
                )
    for evs in (*devices.values(), host):
        evs.sort(key=lambda e: e[1])
    (span,) = [h for h in host if h[0] == window_span]
    return {"window": [span[1], span[1] + span[2]], "rounds": int(rounds),
            "devices": devices, "host": host}


def busy_intervals(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """Union of the events' [start, end) intervals, clipped to [lo, hi)."""
    spans = sorted(
        (max(s, lo), min(s + d, hi)) for _, s, d in events if s < hi and s + d > lo
    )
    out: list[tuple[int, int]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(trace: dict, device: str) -> int:
    lo, hi = trace["window"]
    return sum(e - s for s, e in busy_intervals(trace["devices"][device], lo, hi))


def idle_gaps(trace: dict, device: str = "0", top: int = 10) -> list[list]:
    """The longest idle gaps on one device, each named by what the host was
    doing at its midpoint: the innermost ``bench.`` span, then the innermost
    Python frame (``$``-named events), as ``bench.call > $array.py:631 _value``."""
    lo, hi = trace["window"]
    busy = busy_intervals(trace["devices"][device], lo, hi)
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) // 2
        holders = [h for h in trace["host"] if h[1] <= mid < h[1] + h[2]]
        spans = [h for h in holders if h[0].startswith("bench.")]
        span = min(spans, key=lambda h: h[2])[0] if spans else "no bench span"
        frames = [h for h in holders if h[0].startswith("$")]
        frame = min(frames, key=lambda h: h[2])[0] if frames else span
        out.append([span if frame == span else f"{span} > {frame}", (e - s) / 1e9])
    return out


def top_device_ops(trace: dict, top: int = 10) -> list[list]:
    """Device ops by summed duration, averaged over the traced chips; ops are
    grouped by name with the trailing ``.<n>`` instance number dropped, and
    loops and conds, whose time is their body's, are left out."""
    lo, hi = trace["window"]
    total: dict[str, int] = {}
    for evs in trace["devices"].values():
        for name, s, d in evs:
            if lo <= s < hi and not _CONTAINER.match(name):
                key = re.sub(r"\.\d+$", "", name)
                total[key] = total.get(key, 0) + d
    chips = max(len(trace["devices"]), 1)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / chips / 1e9] for k, v in ranked]
