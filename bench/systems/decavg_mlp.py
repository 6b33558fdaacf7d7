"""DecAvg training of an MLP cohort: the program's ``DecentralizedTrainer``.

A cell of this system builds the benchmark's inputs from the seed
(``inputs.py``), hands them to the program's normal entry — a
``NodeLoader`` plus a ``DecentralizedTrainer``, built the way
``repro.experiments.runner.build_mlp_trainer`` builds them — and drives
``DecentralizedTrainer.run_fused``: one call is one run of
``rounds_per_run`` rounds at the traffic's ``eval_every``.

The first call is the warm-up. It compiles every chunk length the window
uses, and the state it leaves after round 0 — the round's 9 local steps,
its mix and its eval — is what ``compare`` holds against the plain
reference (``decavg_mlp_ref.py``) once the window has closed.
"""

from __future__ import annotations

import numpy as np

import inputs
from systems.decavg_mlp_ref import Reference, leaf_list, stacked_norms

# The paper's split: the lower half of the classes is G1 (every node holds
# them), the upper half G2 (only the focus nodes do).
_NUM_CLASSES = 10


def eval_rounds(rounds: int, eval_every: int) -> list[int]:
    """Rounds after which ``run_fused`` evaluates (its own cadence)."""
    return [r for r in range(rounds) if r % eval_every == 0 or r == rounds - 1]


class Cell:
    """One cell: a configuration under a traffic mix, built from a seed."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        import jax

        jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
        self.cfg, self.traffic = cfg, traffic
        s = inputs.derive_seed(seed)
        n = int(cfg["nodes"])
        topo = cfg["topology"]
        if topo["family"] != "ba":
            raise ValueError(f"unknown topology family {topo['family']!r}")
        graph_seed = s if topo.get("graph_seed") is None else int(topo["graph_seed"])
        adj = inputs.barabasi_albert(n, int(topo["m"]), seed=graph_seed)
        x_tr, y_tr, x_te, y_te = inputs.make_mnist_like(**cfg["data"], seed=s)
        if cfg["partitioner"] != "hub_focused":
            raise ValueError(f"unknown partitioner {cfg['partitioner']!r}")
        parts = inputs.hub_focused(y_tr, adj, seed=s)
        sizes = np.array([len(p) for p in parts], np.int64)
        g2 = np.arange(_NUM_CLASSES) >= _NUM_CLASSES // 2
        holds_g2 = np.array([bool(g2[y_tr[p]].any()) for p in parts])
        dims = [int(cfg["model"]["in_dim"]), *map(int, cfg["model"]["hidden"]),
                int(cfg["model"]["num_classes"])]
        self.rounds = int(traffic["rounds_per_run"])
        self.eval_every = int(traffic["eval_every"])
        batch = int(cfg["batch_size"])
        faults = traffic.get("faults")
        pools = np.zeros((n, int(sizes.max())), np.int32)
        for i, p in enumerate(parts):
            pools[i, : len(p)] = p
        self.adj, self.sizes, self.dims, self.batch = adj, sizes, dims, batch
        self.ref_inputs = {
            "x_train": x_tr, "y_train": y_tr, "x_test": x_te, "y_test": y_te,
            "pools": pools, "sizes": sizes, "w": inputs.decavg_matrix(adj, sizes),
            "alive": None if not faults else inputs.churn_alive(
                adj, 1, seed=s, **inputs.parse_churn(faults)),
            "spread": ~holds_g2, "g2": g2, "dims": dims,
            "lr": float(cfg["lr"]), "mu": float(cfg["momentum"]), "batch": batch,
            # One pass of the median node's data per round, at least one step.
            "steps": max(1, int(np.median(sizes)) // batch) * int(cfg["local_epochs"]),
            "init_seed": s, "loader_seed": s + 1, "shards": int(cfg["chips"]),
        }
        self.spread = ~holds_g2
        self.x_test, self.y_test = x_te, y_te

        from repro.core.topology import Graph
        from repro.data.loader import NodeLoader
        from repro.models.mlp import init_mlp
        from repro.train.trainer import DecentralizedTrainer

        loader = NodeLoader(x_tr, y_tr, parts, batch_size=batch, seed=s + 1)
        hidden = tuple(dims[1:-1])
        self.trainer = DecentralizedTrainer(
            Graph(adj=adj, name=f"ba(n={n},m={topo['m']})"),
            loader,
            lr=float(cfg["lr"]),
            momentum=float(cfg["momentum"]),
            local_epochs=int(cfg["local_epochs"]),
            mix_impl=cfg["backend"],
            matrix="decavg",
            sparse_p_chunk=cfg.get("sparse_p_chunk"),
            gossip_every=int(traffic.get("gossip_every", 1)),
            faults=faults,
            same_init=True,
            seed=s,
            num_classes=_NUM_CLASSES,
            class_groups=g2.astype(np.int32),
            init_fn=lambda k: init_mlp(
                k, in_dim=dims[0], hidden=hidden, num_classes=dims[-1]
            ),
        )
        self.last_mean_acc = float("nan")

    # -- driving the program ---------------------------------------------------

    def _run(self, on_round) -> None:
        self.trainer.run_fused(
            self.rounds, eval_every=self.eval_every, x_test=self.x_test,
            y_test=self.y_test, on_round=on_round,
        )

    def first_call(self, annotate) -> dict:
        """The warm-up call, observed after round 0."""
        import jax

        origin = leaf_list(inputs.init_mlp(
            jax.random.PRNGKey(self.ref_inputs["init_seed"]), self.dims))
        obs: dict = {"change": {}, "acc": {}, "g2_spread": {}}

        def on_round(m) -> None:
            with annotate("bench.on_round"):
                if m.round != 0:
                    return
                layers = _program_layers(self.trainer.params)
                obs["mom"] = stacked_norms(
                    leaf_list(_program_layers(self.trainer.opt_state.momentum)))
                obs["change"][m.round] = stacked_norms(leaf_list(layers), origin)
                obs["acc"][m.round] = np.asarray(m.per_node_acc, np.float64)
                obs["g2_spread"][m.round] = float(m.group_acc[self.spread, 1].mean())

        self._run(on_round)
        self.block()
        return obs

    def call(self, annotate) -> int:
        """One timed call; returns the rounds it ran."""

        def on_round(m) -> None:
            with annotate("bench.on_round"):
                self.last_mean_acc = m.mean_acc

        self._run(on_round)
        return self.rounds

    def block(self) -> None:
        import jax

        jax.block_until_ready(self.trainer.params)

    def free(self) -> None:
        """Drop the program's state so the reference has the chips."""
        import gc

        tr = self.trainer
        tr.params = tr.opt_state = tr.cstate = None
        tr.loader._device_data = None
        del self.trainer, tr
        gc.collect()

    # -- counts ---------------------------------------------------------------------

    def counts(self) -> dict:
        from counts import halo_rows, mlp_params, nnz

        p = mlp_params(self.dims)
        n, chips = int(self.cfg["nodes"]), int(self.cfg["chips"])
        steps = self.ref_inputs["steps"]
        out = {
            "params_per_node": p,
            "nnz": nnz(self.adj),
            # Local forward and backward passes, plus the mix's multiply-adds.
            "flops_per_round": 6 * p * self.batch * steps * n + 2 * nnz(self.adj) * p,
            # Each node's parameters and momentum, read once and written once.
            "bytes_per_round_per_chip": 16 * (n // chips) * p,
        }
        if chips > 1:
            halo, remote = halo_rows(self.adj, chips)
            out["halo_rows"], out["remote_rows"] = halo, remote
            out["bytes_per_round_per_chip"] += 4 * remote * p
        return out

    # -- the comparison that decides `correct` ------------------------------------

    def reference(self, *, precision: str = "highest", variant: str | None = None) -> dict:
        import jax
        from jax.sharding import Mesh

        chips = int(self.cfg["chips"])
        mesh = None if chips == 1 else Mesh(np.array(jax.devices()[:chips]), ("nodes",))
        ref = Reference(self.ref_inputs, precision=precision, variant=variant, mesh=mesh)
        return ref.run(1, {0})


def _program_layers(tree):
    """The program's ``{"layers": ({"w", "b"}, ...)}`` as ((w, b), ...)."""
    return tuple((layer["w"], layer["b"]) for layer in tree["layers"])


def _leaf_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Worst leaf's gap between norms, against that leaf's reference norm or
    the median leaf's, whichever is larger. Leaves whose reference norm is
    under a thousandth of the median leaf's are left out: they move by
    rounding alone."""
    med = float(np.median(want))
    use = want >= 1e-3 * med
    den = np.maximum(want, med)
    return float(np.max(np.abs(got - want)[use] / den[use]))


def compare(obs: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``, from the state after round 0:
    ``grad_gap``, the round's gradients as the optimizer holds them (the
    momentum); ``change_gap``, the parameters' change; ``acc_gap``, each
    node's test accuracy; ``spread_gap``, the mean G2 accuracy of the nodes
    that hold no G2 class. No later state is compared: on some seeds a ReLU
    that round-off tips the other way already moves the momentum by up to
    3e-4 within round 0 (TPU v5e, float32 at ``highest``), and by round 2
    such flips have grown to the size of the control's own gap (PERF.md),
    so a later state could only fail sound runs.
    """
    return {
        "grad_gap": _leaf_gap(obs["mom"], ref["mom"]),
        "change_gap": _leaf_gap(obs["change"][0], ref["change"][0]),
        "acc_gap": float(np.max(np.abs(obs["acc"][0] - ref["acc"][0]))),
        "spread_gap": abs(obs["g2_spread"][0] - ref["g2_spread"][0]),
    }
