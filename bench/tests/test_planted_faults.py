"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole harness (all but the look for a chip) on a small
copy of a real cell, at the paper's widths and under the cell's own limits,
with one fault planted in the program: the step returns its state
unchanged, half of each batch is left out of the loss, or an answer is
altered where it is produced. The node-sharded path that a four-chip cell
would take is checked too, on four virtual devices with the exchange
between them left out. The same runs with nothing planted are correct.
"""

import numpy as np
import pytest

from helpers import run_small, run_small_on_4_devices

ONE_CHIP = "paper_n100_eval2"


def _failed(out: dict) -> list[str]:
    return [k for k, c in out["checks"].items() if not c["value"] <= c["limit"]]


def test_sound_run_is_correct():
    out = run_small(ONE_CHIP, nodes=8)
    assert out["correct"], out["checks"]


def test_step_returning_its_state_unchanged(monkeypatch):
    from repro.optim import sgd

    monkeypatch.setattr(sgd, "update", lambda grads, state, params, **kw: (params, state))
    out = run_small(ONE_CHIP, nodes=8)
    assert not out["correct"]
    assert {"grad_gap", "change_gap"} <= set(_failed(out))


def test_half_of_each_batch_left_out(monkeypatch):
    from repro.train import trainer

    xent = trainer.softmax_xent
    monkeypatch.setattr(
        trainer, "softmax_xent",
        lambda logits, labels: xent(logits[: logits.shape[0] // 2], labels[: labels.shape[0] // 2]),
    )
    out = run_small(ONE_CHIP, nodes=8)
    assert not out["correct"]
    assert "grad_gap" in _failed(out)


def test_answer_altered_where_it_is_produced(monkeypatch):
    from repro.train import trainer

    acc = trainer.accuracy
    monkeypatch.setattr(trainer, "accuracy", lambda logits, y: acc(logits, (y + 1) % 10))
    out = run_small(ONE_CHIP, nodes=8)
    assert not out["correct"]
    assert "acc_gap" in _failed(out)


# Each chip keeps its own rows: the ring's transfers arrive as zeros and an
# all-gather returns the local slab in every slot.
_NO_EXCHANGE = (
    "jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)\n"
    "jax.lax.all_gather = lambda x, axis_name, axis=0, tiled=False: "
    "jnp.concatenate([x] * 4, axis=axis)"
)


@pytest.mark.parametrize("patch,correct", [("", True), (_NO_EXCHANGE, False)])
def test_exchange_between_chips_left_out(patch, correct):
    out = run_small_on_4_devices(ONE_CHIP, nodes=16, patch=patch)
    assert out["correct"] is correct, out["checks"]
    if not correct:
        assert "change_gap" in _failed(out)
    assert np.isfinite([c["value"] for c in out["checks"].values()]).all()
