"""The benchmark's own input generators, copied from the program.

Every input a cell feeds the system comes from here, drawn from the run's
seed, so a later change to the program cannot change what the benchmark
feeds it. Each function is a line-for-line copy of the program's generator
of the same name (``bench/tests/test_inputs.py`` checks that they still
agree):

- ``make_mnist_like``  <- ``repro.data.synthetic.make_mnist_like``
- ``barabasi_albert``  <- ``repro.core.topology.barabasi_albert`` (returns
  the boolean adjacency matrix rather than a ``Graph``)
- ``hub_focused``      <- ``repro.core.partition.hub_focused``

plus the plain arithmetic the reference needs and the program computes on
its own: the paper's Eq. 1 mixing matrix (``decavg_matrix``), churn masks
(``churn_alive``, the ``churn`` clause of ``repro.core.faults.FaultTrace``),
the round-keyed batch sampler (``round_batch_indices``) and the MLP's
initial weights (``init_mlp``).
"""

from __future__ import annotations

import numpy as np

_PROTO_SEED = 1234567
_FAULT_STREAM = 0xFA017


def derive_seed(seed: int) -> int:
    """A 31-bit seed from any whole number: JAX keys keep only 32 bits."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)[0]) >> 1


# ---------------------------------------------------------------------------
# data: repro.data.synthetic.make_mnist_like
# ---------------------------------------------------------------------------


def _prototypes(num_classes, dim, rank, contrast, style):
    rng = np.random.default_rng(_PROTO_SEED)
    base = rng.normal(size=(num_classes, dim))
    kernel = np.exp(-0.5 * (np.arange(-10, 11) / 4.0) ** 2)
    kernel /= kernel.sum()
    smooth = np.stack([np.convolve(b, kernel, mode="same") for b in base])
    protos = (smooth - smooth.min()) / (smooth.max() - smooth.min())
    protos = 0.5 + contrast * (protos - 0.5)
    styles = rng.normal(size=(num_classes, dim, rank)) * style
    return protos.astype(np.float32), styles.astype(np.float32)


def make_mnist_like(
    *, train_per_class=500, test_per_class=100, dim=784, num_classes=10,
    rank=8, noise=0.25, contrast=0.4, style=0.25, seed=0,
):
    """(x_train, y_train, x_test, y_test): 784-dim inputs in [0, 1]."""
    protos, styles = _prototypes(num_classes, dim, rank, contrast, style)
    rng = np.random.default_rng(seed)

    def sample(per_class):
        xs, ys = [], []
        for c in range(num_classes):
            z = rng.normal(size=(per_class, rank)).astype(np.float32)
            eps = rng.normal(scale=noise, size=(per_class, dim)).astype(np.float32)
            x = protos[c][None] + z @ styles[c].T + eps
            xs.append(np.clip(x, 0.0, 1.0))
            ys.append(np.full(per_class, c, dtype=np.int64))
        x = np.concatenate(xs)
        y = np.concatenate(ys)
        perm = rng.permutation(len(y))
        return x[perm], y[perm]

    x_tr, y_tr = sample(train_per_class)
    x_te, y_te = sample(test_per_class)
    return x_tr, y_tr, x_te, y_te


# ---------------------------------------------------------------------------
# graph: repro.core.topology.barabasi_albert
# ---------------------------------------------------------------------------


def barabasi_albert(n: int, m: int, *, seed: int) -> np.ndarray:
    """(n, n) symmetric boolean adjacency of a BA preferential-attachment graph."""
    if m < 1 or m >= n:
        raise ValueError(f"need 1 <= m < n, got m={m}, n={n}")
    rng = np.random.default_rng(seed)
    adj = np.zeros((n, n), dtype=np.bool_)
    for i in range(1, m + 1):
        adj[0, i] = adj[i, 0] = True
    urn: list[int] = []
    for i in range(m + 1):
        urn.extend([i] * int(adj[i].sum()))
    for new in range(m + 1, n):
        targets: set[int] = set()
        while len(targets) < m:
            targets.add(int(urn[rng.integers(len(urn))]))
        for t in targets:
            adj[new, t] = adj[t, new] = True
            urn.extend([new, t])
    return adj


# ---------------------------------------------------------------------------
# partition: repro.core.partition.hub_focused
# ---------------------------------------------------------------------------


def _split_class_evenly(idx, recipients, rng):
    idx = idx.copy()
    rng.shuffle(idx)
    k = len(recipients)
    share = len(idx) // k
    return {node: idx[i * share : (i + 1) * share] for i, node in enumerate(recipients)}


def _select_extreme_degree_nodes(adj, frac, *, highest, seed):
    rng = np.random.default_rng(seed)
    n = adj.shape[0]
    quota = max(1, int(round(frac * n)))
    deg = adj.sum(axis=1)
    order = np.argsort(-deg if highest else deg, kind="stable")
    chosen: list[int] = []
    i = 0
    while len(chosen) < quota:
        d = deg[order[i]]
        tier = [int(v) for v in order[i:] if deg[v] == d]
        if len(chosen) + len(tier) <= quota:
            chosen.extend(tier)
        else:
            need = quota - len(chosen)
            chosen.extend(rng.choice(tier, size=need, replace=False).tolist())
        i += len(tier)
    return np.asarray(sorted(chosen), dtype=np.int64)


def hub_focused(labels, adj, *, seed, g1_classes=(0, 1, 2, 3, 4),
                g2_classes=(5, 6, 7, 8, 9), frac=0.10):
    """Per-node index arrays: G1 classes to everyone, G2 to the top 10% hubs."""
    rng = np.random.default_rng(seed)
    n = adj.shape[0]
    focus = _select_extreme_degree_nodes(adj, frac, highest=True, seed=seed + 1)
    per_node: list[list[np.ndarray]] = [[] for _ in range(n)]
    all_nodes = list(range(n))
    focus_nodes = [int(v) for v in focus]
    for c in g1_classes:
        for node, share in _split_class_evenly(np.flatnonzero(labels == c), all_nodes, rng).items():
            per_node[node].append(share)
    for c in g2_classes:
        for node, share in _split_class_evenly(np.flatnonzero(labels == c), focus_nodes, rng).items():
            per_node[node].append(share)
    return [np.sort(np.concatenate(p)) if p else np.empty(0, np.int64) for p in per_node]


# ---------------------------------------------------------------------------
# arithmetic the reference needs
# ---------------------------------------------------------------------------


def decavg_matrix(adj: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """Paper Eq. 1: W_ij = |D_j| / sum of |D_k| over i's closed neighbourhood."""
    omega = adj.astype(np.float64)
    np.fill_diagonal(omega, 1.0)
    w = omega * np.asarray(sizes, np.float64)[None, :]
    return w / w.sum(axis=1, keepdims=True)


def churn_alive(adj, rounds, *, seed, p_leave, p_join, frac=0.25, start=0,
                target="uniform"):
    """(rounds, N) alive masks of one ``churn`` fault clause.

    Row r is aliveness after round r's transitions: alive nodes in the
    candidate pool leave with probability ``p_leave``, dead nodes rejoin
    with ``p_join``. ``hubs`` / ``leaves`` pools are the top / bottom
    ``ceil(frac * N)`` nodes by degree (ties by node id).
    """
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), _FAULT_STREAM]))
    n = adj.shape[0]
    deg = adj.sum(axis=1).astype(np.int64)
    pool = np.ones(n, bool)
    if target != "uniform":
        k = max(1, int(np.ceil(float(frac) * n)))
        key = -deg if target == "hubs" else deg
        pool = np.zeros(n, bool)
        pool[np.lexsort((np.arange(n), key))[:k]] = True
    alive = np.ones(n, bool)
    out = np.zeros((rounds, n), bool)
    for r in range(rounds):
        u_leave = rng.random(n)
        u_join = rng.random(n)
        if r >= start:
            leave = alive & pool & (u_leave < p_leave)
            join = ~alive & (u_join < p_join)
            alive = (alive & ~leave) | join
        out[r] = alive
    return out


def parse_churn(spec: str) -> dict:
    """``churn:p_leave=..,p_join=..[,frac=..,start=..]@targeted=hubs`` -> kwargs."""
    body, _, mod = spec.partition("@")
    kind, _, params = body.partition(":")
    if kind.strip() != "churn" or ";" in spec:
        raise ValueError(f"the reference models one churn clause, got {spec!r}")
    out: dict = {"target": "uniform"}
    for kv in filter(None, (s.strip() for s in params.split(","))):
        k, _, v = kv.partition("=")
        out[k.strip()] = int(v) if k.strip() == "start" else float(v)
    if mod:
        k, _, v = mod.partition("=")
        if k.strip() != "targeted":
            raise ValueError(f"unknown modifier in {spec!r}")
        out["target"] = v.strip()
    return out


def round_batch_indices(key, r, steps, batch, sizes):
    """(steps, N, B) with-replacement pool positions for round ``r``."""
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(key, r)
    raw = jax.random.randint(
        k, (steps, sizes.shape[0], batch), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32
    )
    return raw % sizes[None, :, None]


def init_mlp(key, dims):
    """He-initialised MLP weights: a tuple of (w, b) per layer."""
    import jax
    import jax.numpy as jnp

    out = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        w = jax.random.normal(jax.random.fold_in(key, i), (a, b)) * (2.0 / a) ** 0.5
        out.append((w.astype(jnp.float32), jnp.zeros((b,), jnp.float32)))
    return tuple(out)
