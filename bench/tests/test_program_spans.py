"""The readers of the program's own spans and counters, and the scope
reduction, give hand-checked numbers."""

import collections
import importlib.util
import json
import sys
from pathlib import Path

import pytest

import devtrace
import scopes

DATA = Path(__file__).parent / "data"
METRICS = Path(__file__).resolve().parents[1] / "metrics"
SPANS = ("stage", "dispatch", "fetch")


def _metric(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute


# Two chips over a 100 ns window.
#   chip 0 busy [0, 20), [30, 50), [70, 90): idle [20, 30), [50, 70), [90, 100)
#   chip 1 busy [10, 60):                    idle [0, 10), [60, 100)
# Host spans, with what each chip idles inside them:
#   stage    [0, 15)                    chip 0: 0   chip 1: 10  -> 5%
#   dispatch [15, 35)                   chip 0: 10  chip 1: 0   -> 5%
#   fetch    [40, 75) (holding a nested duplicate [45, 55)) and [85, 130),
#            clipped to [85, 100)       chip 0: 20 + 10  chip 1: 15 + 15 -> 30%
# Sum 40%, under the idle share (40% + 50%) / 2 = 45%.
HAND = {
    "window": [0, 100],
    "rounds": 2,
    "devices": {
        "0": [["fusion.1", 0, 20], ["fusion.2", 30, 20], ["copy.3", 70, 20]],
        "1": [["fusion.1", 10, 50]],
    },
    "host": [
        ["bench.trace_window", 0, 100], ["bench.call", 0, 100],
        ["trainer.stage", 0, 15], ["$trainer.py:790 run_fused", 0, 100],
        ["trainer.dispatch", 15, 20], ["trainer.fetch", 40, 35],
        ["trainer.fetch", 45, 10], ["trainer.fetch", 85, 45],
    ],
}
HAND_SHARES = {"stage": 5.0, "dispatch": 5.0, "fetch": 30.0}


@pytest.mark.parametrize("span", SPANS)
def test_span_idle_share_by_hand(span):
    got = _metric(f"{span}_idle_share")({"trace": HAND})
    assert got == pytest.approx(HAND_SHARES[span])


def test_span_shares_sum_under_idle_share():
    total = sum(_metric(f"{s}_idle_share")({"trace": HAND}) for s in SPANS)
    assert total == pytest.approx(40.0)
    assert total <= _metric("device_idle_share")({"trace": HAND})


@pytest.mark.parametrize("span", SPANS)
@pytest.mark.parametrize("trace", [
    None,
    # A program without spans: the window and the harness's spans only.
    {**HAND, "host": [h for h in HAND["host"] if not h[0].startswith("trainer.")]},
    # Spans but no traced chip.
    {**HAND, "devices": {}},
], ids=["no_trace", "no_spans", "no_chips"])
def test_span_idle_share_absent(span, trace):
    assert _metric(f"{span}_idle_share")({"trace": trace}) is None


@pytest.mark.parametrize("counts, want", [
    ({"trainer.rounds": 40, "trainer.d2h_transfers": 63}, 1.575),
    ({"trainer.rounds": 80, "trainer.d2h_transfers": 12}, 0.15),
    ({"trainer.rounds": 40}, 0.0),
    ({}, None),
], ids=["eval2", "eval40", "no_fetch", "no_rounds"])
def test_d2h_transfers_per_round(monkeypatch, counts, want):
    from repro import obs

    monkeypatch.setattr(obs, "_COUNTS", collections.Counter(counts))
    assert _metric("d2h_transfers_per_round")({"trace": None}) == want


def test_d2h_transfers_per_round_without_counters(monkeypatch):
    """A program with no ``repro.obs`` module reads as absent."""
    import repro

    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _metric("d2h_transfers_per_round")({"trace": None}) is None


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_fused_chunk)/while/body/closed_call/decavg.local_grad/vmap(jvp())/dot_general",
     "decavg.local_grad"),
    ("jit(_fused_chunk)/while/body/decavg.mix/decavg.halo_exchange/ppermute", "decavg.halo_exchange"),
    ("jit(_fused_chunk)/decavg.eval/vmap()/decavg.eval/reduce_sum", "decavg.eval"),
    ("jit(_fused_chunk)/while/body/closed_call/while", "unscoped"),
    ("jit(f)/my_decavg.mix/add", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_scope_ns_by_hand():
    """Ops clipped to the window [10, 100): the batch op [0, 20) keeps 10 ns,
    the eval op [90, 130) keeps 10; the op after the window drops out."""
    ops = [["decavg.batch", 0, 20], ["decavg.local_grad", 20, 30], ["decavg.mix", 50, 15],
           ["unscoped", 65, 5], ["decavg.local_grad", 70, 20], ["decavg.eval", 90, 40],
           ["decavg.mix", 150, 10]]
    assert scopes.scope_ns(ops, (10, 100)) == {
        "decavg.batch": 10, "decavg.local_grad": 50, "decavg.mix": 15,
        "unscoped": 5, "decavg.eval": 10,
    }


def test_scope_ns_on_chip_slice():
    """3 ms of a traced ``paper_n100_eval2`` call on a TPU v5 lite, scoped by
    ``scoped_ops_from_xplane`` through the chunk programs' compiled text: a
    chunk's last local step, its mix and the start of its eval. By hand (one
    boolean per nanosecond): the local step's first fusion starts 787,221 ns
    before the window and keeps 199,952 ns of it, the eval's last op runs
    152,963 ns past it. The weight updates are fused into the weight-gradient
    matmuls, so ``decavg.sgd_update`` keeps only the bias updates' 267 ns."""
    tr = json.loads((DATA / "scoped_ops_paper_n100_eval2_slice.json").read_text())
    got = scopes.scope_ns(tr["ops"], tr["window"])
    assert got == {
        "decavg.batch": 85_594, "decavg.local_grad": 1_831_470, "decavg.sgd_update": 267,
        "decavg.mix": 712_234, "decavg.eval": 190_510, "unscoped": 172_170,
    }
    # The ops of one chip never overlap, so the scopes sum to its busy time.
    busy = sum(e - s for s, e in devtrace.busy_intervals(tr["ops"], *tr["window"]))
    assert sum(got.values()) == busy == 2_992_245


# Two instructions of a chunk program compiled for a TPU v5e (trimmed), and
# the names the chip's trace gives the same ops: operands print with their
# types there, and there is no metadata.
_HLO = """HloModule jit__fused_chunk, is_scheduled=true
  %multiply_subtract_fusion.17 = (f32[100,784,512]{2,1,0:T(8,128)}, f32[100,784,512]{2,1,0:T(8,128)}) fusion(%get-tuple-element.914, %bitcast.134), kind=kOutput, calls=%fused_computation.7.clone.clone, metadata={op_name="jit(_fused_chunk)/while/body/closed_call/while/body/closed_call/decavg.local_grad/vmap(transpose(jvp()))/dot_general" stack_frame_id=42}
  %copy.131 = f32[100,10]{1,0:T(8,128)S(1)} copy(%get-tuple-element.990), metadata={op_name="jit(_fused_chunk)/while/body/closed_call/while" stack_frame_id=25}
  ROOT %sub.3 = f32[100,10]{1,0:T(8,128)} subtract(%a, %b), metadata={op_name="jit(_fused_chunk)/decavg.sgd_update/sub"}
"""
_TRACE_NAMES = {
    "%multiply_subtract_fusion.17 = (f32[100,784,512]{2,1,0:T(8,128)}, f32[100,784,512]{2,1,0:T(8,128)}) "
    "fusion(f32[100,784,512]{2,1,0:T(8,128)} %get-tuple-element.565, f32[100,32,784]{2,1,0:T(8,128)S(1)} "
    "%bitcast.119), kind=kOutput, calls=%fused_computation.7.clone.clone": "decavg.local_grad",
    "%copy.131 = f32[100,10]{1,0:T(8,128)S(1)} copy(f32[100,10]{0,1:T(8,128)S(1)} %get-tuple-element.990)":
        "unscoped",
    "%sub.3 = f32[100,10]{1,0:T(8,128)} subtract(f32[100,10]{1,0:T(8,128)} %a, f32[100,10]{1,0:T(8,128)} %b)":
        "decavg.sgd_update",
}


@pytest.mark.parametrize("trace_name", sorted(_TRACE_NAMES))
def test_hlo_scopes_find_traced_op(trace_name):
    table = scopes.hlo_scopes([_HLO])
    key = scopes._KEY.match(trace_name).group(1)
    assert table[key] == _TRACE_NAMES[trace_name]
