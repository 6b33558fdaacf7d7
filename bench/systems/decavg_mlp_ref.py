"""Plain reference for DecAvg training of the paper's MLP over a cohort.

The same rounds the program runs, written out directly in ``jax.numpy``
float32 with nothing of the program imported: each node takes its local
SGD-with-momentum steps on its own batches (forward and backward pass of a
ReLU MLP written by hand, mean softmax cross-entropy), dead nodes keep
their state, then every node replaces its weights by the Eq. 1 average
over its closed neighbourhood, renormalised over the nodes still alive.
Every matrix product goes through ``_mm`` at the stated ``precision``:

- ``"highest"``: ``lax.Precision.HIGHEST`` (float32 products);
- ``"high"``: three bfloat16 passes, the control. On a TPU that is
  ``lax.Precision.HIGH``; other backends ignore the flag and compute in
  float32, so there the operands are split into bfloat16 high and low
  halves and the three products written out. (Written out on a TPU, the
  split is simplified away: XLA may keep excess precision through the
  float32 -> bfloat16 -> float32 round trip, which leaves one pass.)

``variant`` plants one of the faults the comparison must catch:
``"half_batch"`` (the loss averaged over the first half of each batch),
``"no_exchange"`` (mixing weights of nodes on other shards left out, as if
the exchange between chips never happened) and ``"answer_altered"`` (each
node's accuracy reported for the next node).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

VARIANTS = (None, "half_batch", "no_exchange", "answer_altered")


def _mm(a, b, precision):
    if precision == "highest":
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    if jax.default_backend() == "tpu":
        return jnp.matmul(a, b, precision=lax.Precision.HIGH)
    ah = a.astype(jnp.bfloat16)
    al = (a - ah.astype(jnp.float32)).astype(jnp.bfloat16)
    bh = b.astype(jnp.bfloat16)
    bl = (b - bh.astype(jnp.float32)).astype(jnp.bfloat16)

    def one(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return one(ah, bh) + (one(ah, bl) + one(al, bh))


def _forward(layers, x, precision):
    """Pre-activations of every layer; the last one is the logits."""
    zs = []
    h = x
    for i, (w, b) in enumerate(layers):
        z = _mm(h, w, precision) + b
        zs.append(z)
        h = jnp.maximum(z, 0.0) if i < len(layers) - 1 else z
    return zs


def _node_step(layers, mom, x, y, *, lr, mu, precision, half):
    """One SGD-with-momentum step of one node, by hand-written backprop."""
    zs = _forward(layers, x, precision)
    b = x.shape[0]
    num_classes = zs[-1].shape[-1]
    d = jax.nn.softmax(zs[-1], axis=-1) - jax.nn.one_hot(y, num_classes, dtype=jnp.float32)
    if half:
        keep = (jnp.arange(b) < b // 2).astype(jnp.float32)[:, None]
        d = d * keep / (b // 2)
    else:
        d = d / b
    grads = [None] * len(layers)
    for i in reversed(range(len(layers))):
        h_in = x if i == 0 else jnp.maximum(zs[i - 1], 0.0)
        grads[i] = (_mm(h_in.T, d, precision), d.sum(axis=0))
        if i > 0:
            d = _mm(d, layers[i][0].T, precision) * (zs[i - 1] > 0)
    new_mom = tuple(
        (mu * mw + gw, mu * mb + gb) for (mw, mb), (gw, gb) in zip(mom, grads)
    )
    new_layers = tuple(
        (w - lr * mw, bb - lr * mb) for (w, bb), (mw, mb) in zip(layers, new_mom)
    )
    return new_layers, new_mom


def leaf_list(layers):
    """Leaves in a fixed order: w0, b0, w1, b1, ..."""
    return [leaf for pair in layers for leaf in pair]


@jax.jit
def _norms(leaves, origin):
    """Per-leaf L2 norm over all nodes of ``leaf - origin`` (origin broadcast)."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(l - o[None]))) for l, o in zip(leaves, origin)
    ])


def stacked_norms(leaves, origin=None) -> np.ndarray:
    if origin is None:
        origin = [jnp.zeros(l.shape[1:], l.dtype) for l in leaves]
    return np.asarray(_norms(list(leaves), list(origin)), np.float64)


class Reference:
    """The reference cohort, built from the benchmark's own inputs.

    ``inp`` holds numpy arrays: ``x_train``/``y_train``/``x_test``/
    ``y_test``, ``pools`` (N, M) bank rows per node, ``sizes`` (N,),
    ``w`` (N, N) Eq. 1 matrix, ``alive`` (rounds, N) or None, ``spread``
    (N,) bool nodes holding no G2 class, ``g2`` (C,) bool G2 classes, and
    scalars ``dims``, ``lr``, ``mu``, ``batch``, ``steps``, ``init_seed``,
    ``loader_seed``, ``shards``. ``mesh`` (one axis) shards the node axis.
    """

    def __init__(self, inp: dict, *, precision: str = "highest",
                 variant: str | None = None, mesh=None):
        if variant not in VARIANTS:
            raise ValueError(f"unknown variant {variant!r}")
        self.inp, self.precision, self.variant, self.mesh = inp, precision, variant, mesh
        n = inp["w"].shape[0]
        w = np.asarray(inp["w"], np.float64)
        if variant == "no_exchange":
            own = np.arange(n) // (n // inp["shards"])
            w = np.where(own[:, None] == own[None, :], w, 0.0)
        self.n = n
        rep = self._rep
        self.d = {
            "w": rep(jnp.asarray(w, jnp.float32)),
            "alive": None if inp["alive"] is None else rep(jnp.asarray(inp["alive"])),
            "x": rep(jnp.asarray(inp["x_train"], jnp.float32)),
            "y": rep(jnp.asarray(inp["y_train"], jnp.int32)),
            "x_test": rep(jnp.asarray(inp["x_test"], jnp.float32)),
            "y_test": rep(jnp.asarray(inp["y_test"], jnp.int32)),
            "g2_test": rep(jnp.asarray(np.asarray(inp["g2"])[inp["y_test"]])),
            "pools": rep(jnp.asarray(inp["pools"], jnp.int32)),
            "sizes": rep(jnp.asarray(inp["sizes"], jnp.int32)),
            "key": jax.random.PRNGKey(inp["loader_seed"]),
        }
        self.p0 = self._init()
        self._round = jax.jit(self._round_fn)
        self._eval = jax.jit(self._eval_fn)
        self._stack = jax.jit(
            lambda p: jax.tree.map(
                lambda l: self._nodes(jnp.broadcast_to(l, (n,) + l.shape)), p
            )
        )

    # -- placement -----------------------------------------------------------

    def _rep(self, x):
        if self.mesh is None:
            return x
        return jax.device_put(x, NamedSharding(self.mesh, P()))

    def _nodes(self, x):
        """Constrain a node-stacked array to the node-sharded layout."""
        if self.mesh is None:
            return x
        axis = self.mesh.axis_names[0]
        spec = P(axis, *([None] * (x.ndim - 1)))
        return lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def _cols(self, x):
        """(N, K) -> sharded over K, so the node contraction is local."""
        if self.mesh is None:
            return x
        axis = self.mesh.axis_names[0]
        return lax.with_sharding_constraint(x, NamedSharding(self.mesh, P(None, axis)))

    def _init(self):
        from inputs import init_mlp  # the benchmark's copy, not the program's

        return init_mlp(jax.random.PRNGKey(self.inp["init_seed"]), self.inp["dims"])

    # -- one round -------------------------------------------------------------

    def _round_fn(self, d, layers, mom, r):
        inp = self.inp
        steps, batch = inp["steps"], inp["batch"]
        idx_all = _sample(d["key"], r, steps, batch, d["sizes"])
        node = jnp.arange(self.n)
        p_in, m_in = layers, mom
        step = functools.partial(
            _node_step, lr=inp["lr"], mu=inp["mu"], precision=self.precision,
            half=self.variant == "half_batch",
        )
        for s in range(steps):
            rows = d["pools"][node[:, None], idx_all[s]]
            layers, mom = jax.vmap(step)(layers, mom, d["x"][rows], d["y"][rows])
        w = d["w"]
        ok = jnp.ones((self.n,), bool)
        if d["alive"] is not None:
            alive = d["alive"][r]

            def sel(a, b):
                return jnp.where(alive.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)

            layers = jax.tree.map(sel, layers, p_in)
            mom = jax.tree.map(sel, mom, m_in)
            wk = w * (alive[:, None] & alive[None, :])
            rowsum = wk.sum(axis=1)
            ok = (rowsum > 0) & alive
            w = wk / jnp.where(rowsum > 0, rowsum, 1.0)[:, None]

        def mix(leaf):
            flat = self._cols(leaf.reshape(self.n, -1))
            out = jnp.where(ok[:, None], _mm(w, flat, self.precision), flat)
            return self._nodes(out.reshape(leaf.shape))

        layers = jax.tree.map(mix, layers)
        return layers, jax.tree.map(self._nodes, mom)

    def _eval_fn(self, d, layers):
        def node(lay):
            logits = _forward(lay, d["x_test"], self.precision)[-1]
            correct = (jnp.argmax(logits, axis=-1) == d["y_test"]).astype(jnp.float32)
            g2 = d["g2_test"].astype(jnp.float32)
            return correct.mean(), (correct * g2).sum() / jnp.maximum(g2.sum(), 1.0)

        return jax.vmap(node)(layers)

    # -- a run -------------------------------------------------------------------

    def run(self, rounds: int, observe) -> dict:
        """Observations after round 0 and after each round in ``observe``."""
        layers = self._stack(self.p0)
        mom = jax.tree.map(jnp.zeros_like, layers)
        out: dict = {"change": {}, "acc": {}, "g2_spread": {}}
        spread = np.asarray(self.inp["spread"], bool)
        origin = leaf_list(self.p0)
        for r in range(rounds):
            layers, mom = self._round(self.d, layers, mom, jnp.int32(r))
            if r == 0:
                out["mom"] = stacked_norms(leaf_list(mom))
            if r in observe:
                out["change"][r] = stacked_norms(leaf_list(layers), origin)
                acc, g2 = (np.asarray(a, np.float64) for a in self._eval(self.d, layers))
                if self.variant == "answer_altered":
                    acc = np.roll(acc, 1)
                out["acc"][r] = acc
                out["g2_spread"][r] = float(g2[spread].mean()) if spread.any() else 0.0
        return out


def _sample(key, r, steps, batch, sizes):
    from inputs import round_batch_indices

    return round_batch_indices(key, r, steps, batch, sizes)
