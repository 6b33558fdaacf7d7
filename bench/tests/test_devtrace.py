"""The trace reducers give hand-checked numbers."""

import importlib.util
import json
from pathlib import Path

import pytest

import devtrace

DATA = Path(__file__).parent / "data"


def _metric(name):
    spec = importlib.util.spec_from_file_location(name, DATA.parents[1] / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute


# Two devices over a 100 ns window, two rounds.
#   device 0: fusion [0, 30) and all-gather [20, 50) overlap -> busy [0, 50);
#             collective-permute-start [70, 80) -> busy 60 ns, idle 40%.
#             A fusion at [95, 120) is clipped to [95, 100): busy 65, idle 35%.
#   device 1: one op [10, 20): busy 10, idle 90%.
HAND = {
    "window": [0, 100],
    "rounds": 2,
    "devices": {
        "0": [["fusion.1", 0, 30], ["all-gather.2", 20, 30],
              ["collective-permute-start.3", 70, 10], ["fusion.1", 95, 25]],
        "1": [["copy.4", 10, 10]],
    },
    "host": [["bench.trace_window", 0, 100], ["bench.call", 0, 90],
             ["bench.on_round", 55, 10]],
}


def test_hand_built_trace():
    assert devtrace.busy_ns(HAND, "0") == 65
    assert devtrace.busy_ns(HAND, "1") == 10
    ctx = {"trace": HAND}
    assert _metric("device_idle_share")(ctx) == pytest.approx(100 * (0.35 + 0.90) / 2)
    # Idle gaps of device 0: [50, 70) during on_round, [80, 95) in the call.
    assert devtrace.idle_gaps(HAND, "0") == [["bench.on_round", 20e-9], ["bench.call", 15e-9]]
    assert devtrace.top_device_ops(HAND)[0] == ["fusion", (30 + 25) / 2 / 1e9]


def test_no_trace_reads_nothing():
    assert _metric("device_idle_share")({"trace": None}) is None


def test_chip_trace_slice():
    """6 ms of a traced `paper_n100_eval2` call on a TPU v5 lite (one chip),
    reduced by ``events_from_xplane``: 95 device ops. By hand (one boolean
    per nanosecond): 2,665,754 ns busy, so 55.57% idle; the longest gap,
    3,332,418 ns, falls while the host waits for a chunk's accuracies to
    come back (``np.asarray`` -> ``_value``)."""
    tr = json.loads((DATA / "trace_paper_n100_eval2_slice.json").read_text())
    assert tr["window"][1] - tr["window"][0] == 6_000_000
    assert devtrace.busy_ns(tr, "0") == 2_665_754
    assert _metric("device_idle_share")({"trace": tr}) == pytest.approx(100 * (1 - 2_665_754 / 6e6))
    assert devtrace.idle_gaps(tr, "0", top=1) == [["bench.call > $array.py:631 _value", 0.003332418]]


def test_four_chip_trace_slice():
    """1 ms of a traced call of 1792 gossip nodes sharded over four TPU v5
    lite chips, from 0.2 ms before chip 0's first ring step. By hand (one
    boolean per nanosecond): busy 999,967 / 999,966 / 999,970 / 999,955 ns,
    so the idle share is the mean over chips, (33 + 34 + 30 + 45) / 4 ns =
    0.00355%."""
    tr = json.loads((DATA / "trace_n1792_sharded4_slice.json").read_text())
    assert tr["window"][1] - tr["window"][0] == 1_000_000 and tr["rounds"] == 1
    busy = {d: devtrace.busy_ns(tr, d) for d in "0123"}
    assert busy == {"0": 999_967, "1": 999_966, "2": 999_970, "3": 999_955}
    ctx = {"trace": tr}
    assert _metric("device_idle_share")(ctx) == pytest.approx(100 * 35.5 / 1e6)
