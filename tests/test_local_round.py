"""The local-round kernel (``kernels/local_round.py``) against XLA's scan.

Off the chip the kernel runs in the Pallas interpreter. It must land where
``DecentralizedTrainer._local_steps`` (a ``lax.scan`` over ``_sgd_step``)
lands after the same steps, to float32 round-off; the trainer must pick it
only where it can run it; and ``trainer.local_kernel_rounds`` must count
the rounds that ran in it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import partition as P
from repro.data.loader import NodeLoader
from repro.data.synthetic import make_mnist_like
from repro.kernels import local_round, ops
from repro.models.mlp import init_mlp, mlp_forward
from repro.optim import sgd
from repro.train.trainer import DecentralizedTrainer

PAPER = (784, 512, 256, 128, 10)


def make_trainer(dims, nodes, *, batch=8, train_per_class=16, **kw):
    ds = make_mnist_like(
        train_per_class=train_per_class, test_per_class=4, dim=dims[0],
        num_classes=dims[-1], seed=0,
    )
    loader = NodeLoader(
        ds.x_train, ds.y_train, P.iid(ds.y_train, nodes, seed=1),
        batch_size=batch, seed=2,
    )
    init = lambda k: init_mlp(k, in_dim=dims[0], hidden=dims[1:-1], num_classes=dims[-1])
    tr = DecentralizedTrainer(
        f"ring:n={nodes}", loader, lr=0.05, momentum=0.9, seed=0,
        init_fn=init, num_classes=dims[-1], **kw,
    )
    return tr, ds


@pytest.mark.parametrize("dims, nodes, batch", [
    (PAPER, 3, 32),
    ((25, 37, 7), 2, 12),
], ids=["paper_widths", "two_layers_odd_widths"])
def test_kernel_matches_xla_scan(dims, nodes, batch):
    steps, bank = 3, 40
    tr, ds = make_trainer(dims, nodes, batch=batch)
    rng = np.random.default_rng(0)
    params = jax.tree.map(
        lambda a: a + jnp.asarray(rng.normal(scale=0.05, size=a.shape), a.dtype), tr.params)
    opt = sgd.SGDState(jax.tree.map(
        lambda a: jnp.asarray(rng.normal(scale=0.05, size=a.shape), a.dtype), params))
    x = jnp.asarray(ds.x_train[:bank])
    y = jnp.asarray(ds.y_train[:bank].astype(np.int32))
    # Rows drawn from a small bank, so batches repeat rows within and across steps.
    rows = jnp.asarray(rng.integers(0, bank, (nodes, steps, batch)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        xs, ys = x[rows].swapaxes(0, 1), y[rows].swapaxes(0, 1)
        want_p, want_o = tr._local_steps(params, opt, xs, ys)
        got_p, got_m = local_round.local_round(
            x, rows, y[rows], params, opt.momentum, lr=0.05, mu=0.9, interpret=True)
    for want, got in ((want_p, got_p), (want_o.momentum, got_m)):
        assert jax.tree.structure(want) == jax.tree.structure(got)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5, atol=1e-6)


def _wide(params):
    """Shapes of a node-stacked MLP whose state is over the VMEM budget."""
    n = params["layers"][0]["w"].shape[0]
    return jax.eval_shape(lambda: jax.vmap(
        lambda k: init_mlp(k, in_dim=784, hidden=(2048, 2048)))(jax.random.split(
            jax.random.PRNGKey(0), n)))


@pytest.mark.parametrize("case, takes", [
    ("paper_mlp", True),
    ("custom_forward", False),
    ("bf16_state", False),
    ("faulted", False),
    ("compressed", False),
    ("over_vmem_budget", False),
    ("precision_high", False),
    ("sharded_program", False),
    ("cpu_backend", False),
])
def test_kernel_selection(monkeypatch, case, takes):
    """The trainer takes the kernel for the paper MLP on a TPU at
    ``highest``, and declines it for every other case it can observe."""
    if case != "cpu_backend":
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
    kw = {
        "custom_forward": {"forward_fn": lambda p, x: mlp_forward(p, x)},
        "faulted": {"faults": "churn:p_leave=0.3,p_join=0.5"},
        "compressed": {"compress": 0.25},
    }.get(case, {})
    tr, _ = make_trainer(PAPER, 4, **kw)
    params, opt = tr.params, tr.opt_state
    if case == "bf16_state":
        params, opt = jax.tree.map(lambda a: a.astype(jnp.bfloat16), (params, opt))
    if case == "over_vmem_budget":
        params = _wide(params)
        opt = sgd.SGDState(params)
    kind = "sparse_sharded" if case == "sharded_program" else "dense"
    x = tr.loader.device_data().x
    if case == "over_vmem_budget":
        x = jax.ShapeDtypeStruct(x.shape, x.dtype)
    precision = "high" if case == "precision_high" else "highest"
    with jax.default_matmul_precision(precision):
        assert tr._local_kernel_ok(kind, params, opt, x) is takes


def test_vmem_budget_holds_the_paper_mlp_and_not_a_wide_one():
    paper = local_round.vmem_bytes(PAPER, nodes=100, batch=32)
    wide = local_round.vmem_bytes((784, 2048, 2048, 10), nodes=100, batch=32)
    assert paper <= local_round.VMEM_BUDGET < wide


@pytest.mark.parametrize("takes", [True, False], ids=["kernel", "xla_scan"])
def test_local_kernel_rounds_counts_each_chunk(monkeypatch, takes):
    """``run_fused`` adds a chunk's length to ``trainer.local_kernel_rounds``
    when the kernel ran it, and nothing otherwise; the kernel's run lands
    where XLA's does."""
    dims = (32, 24, 10)
    ref, ds = make_trainer(dims, 4)
    ref.run_fused(5, eval_every=2, x_test=ds.x_test, y_test=ds.y_test)
    tr, _ = make_trainer(dims, 4)
    if takes:
        monkeypatch.setattr(DecentralizedTrainer, "_local_kernel_ok", lambda *a: True)
    counted = []
    count = obs.count
    monkeypatch.setattr(obs, "count", lambda name, n=1: (
        counted.append((name, n)), count(name, n)))
    tr.run_fused(5, eval_every=2, x_test=ds.x_test, y_test=ds.y_test)
    kernel_chunks = [n for name, n in counted if name == "trainer.local_kernel_rounds"]
    chunks = [n for name, n in counted if name == "trainer.rounds"]
    assert chunks == [1, 2, 2]  # chunks end after rounds 0, 2 and 4
    assert kernel_chunks == (chunks if takes else [])
    for a, b in zip(jax.tree.leaves((ref.params, ref.opt_state)),
                    jax.tree.leaves((tr.params, tr.opt_state))):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("classes", [10, 3, 17])
def test_class_sum_adds_in_xlas_order(classes):
    """``_class_sum`` adds as XLA's program adds a class axis it lays along
    sublanes: the 8-wide groups in turn, then ``((x0+x4)+(x2+x6)) +
    ((x1+x5)+(x3+x7))`` — the same float32 additions in the same order."""
    rng = np.random.default_rng(classes)
    e = rng.exponential(size=(16, classes)).astype(np.float32)
    padded = np.zeros((16, -(-classes // 8) * 8), np.float32)
    padded[:, :classes] = e
    x = padded[:, 0:8]
    for k in range(8, padded.shape[1], 8):
        x = x + padded[:, k:k + 8]
    want = ((x[:, 0] + x[:, 4]) + (x[:, 2] + x[:, 6])) + ((x[:, 1] + x[:, 5]) + (x[:, 3] + x[:, 7]))
    got = np.asarray(local_round._class_sum(jnp.asarray(e)))
    np.testing.assert_array_equal(got[:, 0], want)


def test_split_truncates_to_bf16_width_and_keeps_the_raw_remainder():
    """XLA's split of a float32 operand: ``hi``, ``mid`` and ``lo`` carry
    no bits below bfloat16's, ``r`` is the exact remainder after ``hi``, and
    the three parts rebuild the operand to its last bit or so."""
    x = np.random.default_rng(0).normal(size=(8, 128)).astype(np.float32)
    parts = {k: np.asarray(v) for k, v in local_round._split(jnp.asarray(x)).items()}
    for k in ("hi", "mid", "lo"):
        assert not np.any(parts[k].view(np.uint32) & 0xFFFF)
    np.testing.assert_array_equal(parts["hi"] + parts["r"], x)
    np.testing.assert_array_equal(parts["mid"], np.asarray(local_round._trunc(jnp.asarray(parts["r"]))))
    rebuilt = parts["hi"].astype(np.float64) + parts["mid"] + parts["lo"]
    np.testing.assert_allclose(rebuilt, x, rtol=2.0**-22, atol=0)
