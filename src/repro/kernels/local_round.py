"""Pallas TPU kernel: one DecAvg round's local SGD steps for a stacked MLP.

XLA runs a round's ``steps`` local steps as a scan, and each step reads and
writes every node's parameters and momentum in HBM: the weight-gradient
matmul with the momentum update fused into it streams the whole state once
a step. This kernel keeps one node's state in VMEM for the whole round and
moves it through HBM once.

The grid is ``(N, steps)``: node-major, one local step per grid point. A
node's weights and their momentum are blocks indexed by the node alone, so
the Pallas pipeline loads them when the node starts, keeps them resident
across its steps, writes them back (in place, through
``input_output_aliases``) when the next node starts, and overlaps both
transfers with the neighbouring node's compute. The biases and their
momentum are small; they stay whole in VMEM for the call, one row a node.

Each step computes, for the node's batch of ``B`` bank rows:

- the forward pass, ``h = relu(h @ w + b)`` and linear logits;
- the mean softmax cross-entropy's gradient, ``(softmax - onehot) / B``,
  formed as XLA's program forms it (below);
- the backward pass by hand, masking with ``pre-activation > 0`` as
  ``jax.nn.relu``'s gradient does;
- ``m = mu * m + g`` and ``p = p - lr * m``, as ``optim/sgd.update`` does.

The batch is never gathered in HBM. The bank rows of every node and step
are scalar-prefetched into SMEM; each of the ``B`` rows is fetched as the
aligned 8-row slab that holds it (a block of the unsliced image bank whose
index map reads the row from SMEM), and the step picks the row out of its
slab. Mosaic copies no single row out of the bank's (8, 128)-tiled HBM
layout, so a step reads eight times its batch's bytes. The pipeline
fetches the next step's slabs, across node boundaries too, while the
current step computes. Labels ride in SMEM beside the rows.

Every product is computed as XLA's program for the same step computes it
on a TPU at ``jax_default_matmul_precision="highest"``, so that the kernel
rounds as that program does, bit for bit. XLA splits each float32 operand
into ``hi``, ``mid`` and ``lo`` parts of bfloat16 width by truncation (and
keeps the raw remainder ``r`` after ``hi``) and sums six single-pass MXU
products, ``lo*hi, hi*lo, r*r, mid*hi, hi*mid, hi*hi`` (left operand
first), in that order. Over a contraction deeper than one 128-wide MXU
pass it sums pass by pass, each over the whole contraction; Mosaic's own
``HIGHEST`` dot sums chunk by chunk, so ``_dot`` writes the deep products
out as single-pass dots. XLA forms the logits and the last weight's
gradient transposed (the 10-class operand streamed through the MXU, the
batch operand held in it), and ``_dot`` streams the same operand. These
are the choices XLA's TPU compiler made for the paper MLP's fused step
(jax 0.9, v5e). The softmax follows the program too: the class sum in
the order XLA sums that axis, ``(1/B) / sum`` times ``exp``, and the last
bias's gradient as a lane sum. Only ``highest`` is
expressed, so ``fits`` declines every other setting and the caller keeps
XLA's scan.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fits", "local_round", "vmem_bytes"]

SUBLANES = 8  # f32 rows in one tile: the row granularity of a bank fetch
_LANES = 128

# What the kernel may ask of a TensorCore's VMEM (v5e and v6e hold 128 MiB,
# v5p 64 MiB) and of its SMEM for the prefetched rows and labels (1 MiB on
# v5e). The paper MLP at batch 32 and N = 100 asks for 34.5 MiB and 225 KiB.
VMEM_BUDGET = 56 * 2**20
SMEM_BUDGET = 512 * 2**10
# Headroom over the counted buffers for Mosaic's own temporaries.
_VMEM_SLACK = 8 * 2**20


def _tile_bytes(rows: int, cols: int) -> int:
    """Bytes of an f32 (rows, cols) array in VMEM, padded to whole tiles."""
    return 4 * (-(-rows // SUBLANES) * SUBLANES) * (-(-cols // _LANES) * _LANES)


def vmem_bytes(dims, *, nodes: int, batch: int) -> int:
    """The VMEM limit the kernel asks for: layer widths ``dims`` (input
    first), ``nodes`` stacked nodes, ``batch`` rows a step. Every pipelined
    buffer is counted twice (double buffering); the state is counted in and
    out, parameters and momentum; then the slabs of one step's batch, the
    step's activations, and the largest weight's four split parts with its
    gradient and momentum temporaries."""
    pairs = list(zip(dims[:-1], dims[1:]))
    weights = sum(_tile_bytes(a, b) for a, b in pairs)
    biases = sum(_tile_bytes(nodes, b) for _, b in pairs)
    slabs = batch * _tile_bytes(SUBLANES, dims[0])
    acts = sum(_tile_bytes(batch, d) for d in dims)
    temps = 6 * max(_tile_bytes(a, b) for a, b in pairs)
    return 2 * (4 * weights + 4 * biases + slabs) + 3 * acts + temps + _VMEM_SLACK


def _dims(params) -> list[int] | None:
    """Layer widths of an ``{"layers": ({"w", "b"}, ...)}`` node-stacked
    MLP, or None for any other tree."""
    layers = params.get("layers") if isinstance(params, dict) else None
    if not isinstance(layers, tuple) or not layers:
        return None
    dims = []
    for layer in layers:
        if not isinstance(layer, dict) or set(layer) != {"w", "b"}:
            return None
        w, b = layer["w"], layer["b"]
        if w.ndim != 3 or b.ndim != 2 or b.shape[1] != w.shape[2]:
            return None
        if dims and dims[-1] != w.shape[1]:
            return None
        dims = dims or [w.shape[1]]
        dims.append(w.shape[2])
    return dims


def fits(params, momentum, x, *, steps: int, batch: int) -> bool:
    """Whether ``local_round`` can run these node-stacked MLP parameters and
    momentum on the image bank ``x`` at ``steps`` steps of ``batch`` rows:
    the tree is an MLP's, parameters, momentum and bank are float32, the
    matmul precision is ``highest``, the bank's rows come in whole slabs,
    and the footprint fits the VMEM and SMEM budgets."""
    dims = _dims(params)
    if dims is None or jax.tree.structure(momentum) != jax.tree.structure(params):
        return False
    if any(a.dtype != jnp.float32 or a.shape != b.shape
           for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(momentum))):
        return False
    if x.dtype != jnp.float32 or x.ndim != 2 or x.shape[1] != dims[0]:
        return False
    if _precision() is None or x.shape[0] % SUBLANES:
        return False
    nodes = params["layers"][0]["w"].shape[0]
    return (
        vmem_bytes(dims, nodes=nodes, batch=batch) <= VMEM_BUDGET
        and 2 * 4 * nodes * steps * batch <= SMEM_BUDGET
    )


def _precision():
    """The dot precision for ``jax_default_matmul_precision``, or None when
    the kernel cannot match what XLA computes at that setting."""
    if jax.config.jax_default_matmul_precision in ("highest", "float32"):
        return jax.lax.Precision.HIGHEST
    return None


# XLA's six single-pass products of a float32 ``highest`` dot, in the order
# it sums them: (left operand's part, right operand's part).
_PASSES = (("lo", "hi"), ("hi", "lo"), ("r", "r"), ("mid", "hi"), ("hi", "mid"), ("hi", "hi"))


def _trunc(x):
    """``x`` cut to bfloat16 width by dropping its low 16 bits."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _split(x) -> dict:
    hi = _trunc(x)
    r = x - hi
    mid = _trunc(r)
    return {"hi": hi, "r": r, "mid": mid, "lo": _trunc(r - mid)}


def _dot(a, b, contract, *, swapped: bool = False):
    """``a`` (2-D) contracted on axis ``contract[0]`` with ``b`` on axis
    ``contract[1]``, at ``highest``, summed as XLA sums it: XLA's six passes
    written out, each over the whole contraction in turn, 128 deep at a
    time. Within one 128-deep chunk that is the order of Mosaic's own
    ``HIGHEST`` dot, which then serves. ``swapped`` says that XLA's product
    has ``b`` as its left operand: the passes pair the parts the other way
    round, and the kernel streams ``a`` through the MXU as XLA streams its
    right operand."""
    ca, cb = contract
    dims = (((ca,), (cb,)), ((), ()))
    depth = a.shape[ca]
    if depth <= _LANES and not swapped:
        return jax.lax.dot_general(
            a, b, dims, precision=_precision(), preferred_element_type=jnp.float32)

    def chunk(x, axis, k):
        return x[:, k:k + _LANES] if axis else x[k:k + _LANES, :]

    pa, pb = _split(a), _split(b)
    passes = [(qb, qa) for qa, qb in _PASSES] if swapped else _PASSES
    acc = None
    for qa, qb in passes:
        for k in range(0, depth, _LANES):
            p = jax.lax.dot_general(
                chunk(pa[qa], ca, k), chunk(pb[qb], cb, k), dims,
                precision=jax.lax.Precision.DEFAULT, preferred_element_type=jnp.float32)
            acc = p if acc is None else acc + p
    return acc


def _class_sum(e):
    """Row sums of ``e`` (B, C) in XLA's order for its class axis, which it
    lays along sublanes: 8-wide groups added in turn, then halves of 4, 2
    and 1."""
    groups = -(-e.shape[1] // SUBLANES) * SUBLANES
    if groups > e.shape[1]:
        e = jnp.concatenate(
            [e, jnp.zeros((e.shape[0], groups - e.shape[1]), e.dtype)], axis=1)
    x = e[:, :SUBLANES]
    for k in range(SUBLANES, groups, SUBLANES):
        x = x + e[:, k:k + SUBLANES]
    for h in (4, 2, 1):
        x = x[:, :h] + x[:, h:2 * h]
    return x


def _kernel(rows_ref, labels_ref, *refs, layers, steps, batch, lr, mu):
    """Grid point ``(i, s)``: node ``i``'s local step ``s``.

    Refs: ``rows_ref``/``labels_ref`` (N * steps * B,) int32 in SMEM, bank
    rows and labels node-major; ``batch`` (8, D) slabs of the bank, slab
    ``r`` holding row ``rows[i, s, r]``; the node's parameters and momentum
    in, then out, each as ``w0, b0, w1, b1, ...`` (weights the node's
    block, biases the whole (N, d) array).
    """
    slabs, refs = refs[:batch], refs[batch:]
    k = 2 * layers
    p_in, m_in, p_out, m_out = (refs[j * k:(j + 1) * k] for j in range(4))
    i, s = pl.program_id(0), pl.program_id(1)
    node = pl.ds(i, 1)

    @pl.when(s == 0)
    def _load():
        for j, (src, dst) in enumerate(zip(p_in + m_in, p_out + m_out)):
            if j % 2:  # a bias: this node's row
                dst[node, :] = src[node, :]
            else:
                dst[...] = src[...]

    base = (i * steps + s) * batch
    h = jnp.concatenate(
        [slab[pl.ds(rows_ref[base + r] % SUBLANES, 1), :]
         for r, slab in enumerate(slabs)],
        axis=0,
    )

    acts, pre = [h], []
    for l in range(layers):
        w, b = p_out[2 * l][...], p_out[2 * l + 1][node, :]
        if l < layers - 1:
            z = _dot(h, w, (1, 0)) + b
            h = jnp.maximum(z, 0.0)
        else:  # XLA forms the logits transposed, streaming the weights
            z = _dot(w, h, (0, 1), swapped=True).T + b
            h = z
        pre.append(z)
        acts.append(h)

    classes = h.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (batch, classes), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (batch, classes), 1)
    onehot = row < 0
    for r in range(batch):
        onehot = onehot | ((row == r) & (col == labels_ref[base + r]))
    e = jnp.exp(h - jnp.max(h, axis=1, keepdims=True))
    scale = (1.0 / batch) / _class_sum(e)
    d = jnp.where(onehot, -1.0 / batch, 0.0) + scale * e

    for l in reversed(range(layers)):
        w, b, mw, mb = p_out[2 * l], p_out[2 * l + 1], m_out[2 * l], m_out[2 * l + 1]
        if l:
            da = _dot(d, w[...], (1, 1))
        if l == layers - 1:  # XLA forms this gradient transposed too
            g = _dot(d, acts[l], (0, 0), swapped=True).T
        else:
            g = _dot(acts[l], d, (0, 0))
        m = mu * mw[...] + g
        mw[...] = m
        w[...] = w[...] - lr * m
        if l == layers - 1:  # the logits' bias: XLA sums it across lanes
            g = jnp.sum(d.T, axis=1, keepdims=True).T
        else:
            g = jnp.sum(d, axis=0, keepdims=True)
        m = mu * mb[node, :] + g
        mb[node, :] = m
        b[node, :] = b[node, :] - lr * m
        if l:
            d = jnp.where(pre[l - 1] > 0, da, 0.0)


def _flat(tree) -> list:
    return [a for layer in tree["layers"] for a in (layer["w"], layer["b"])]


def _tree(flat) -> dict:
    return {"layers": tuple(
        {"w": flat[j], "b": flat[j + 1]} for j in range(0, len(flat), 2)
    )}


@functools.partial(jax.jit, static_argnames=("lr", "mu", "interpret"))
def local_round(x, rows, labels, params, momentum, *, lr: float, mu: float,
                interpret: bool | None = None):
    """Every node's local steps of one round, in place.

    ``x`` (T, D) float32 image bank, T a multiple of 8; ``rows`` and
    ``labels`` (N, steps, B) int32, node ``n``'s bank rows and their labels
    at each step; ``params`` and ``momentum`` the node-stacked
    ``{"layers": ({"w", "b"}, ...)}`` trees (``fits`` says which the kernel
    takes). Returns the updated ``(params, momentum)``, the same
    arithmetic as ``steps`` SGD steps of ``jax.grad`` of the mean softmax
    cross-entropy and ``optim/sgd.update`` at weight decay 0.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    nodes, steps, batch = rows.shape
    dims = _dims(params)
    leaves = _flat(params) + _flat(momentum)

    def state_spec(a):
        if a.ndim == 2:  # (N, d) biases: whole, resident for the call
            return pl.BlockSpec(a.shape, lambda i, s, *_: (0, 0))
        return pl.BlockSpec((None, *a.shape[1:]), lambda i, s, *_: (i, 0, 0))

    def slab_spec(r):
        return pl.BlockSpec(
            (SUBLANES, x.shape[1]),
            lambda i, s, rows_ref, labels_ref: (
                rows_ref[(i * steps + s) * batch + r] // SUBLANES, 0),
        )

    state = [state_spec(a) for a in leaves]
    out = pl.pallas_call(
        functools.partial(_kernel, layers=len(dims) - 1, steps=steps,
                          batch=batch, lr=lr, mu=mu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nodes, steps),
            in_specs=[slab_spec(r) for r in range(batch)] + state,
            out_specs=state,
        ),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in leaves],
        input_output_aliases={2 + batch + j: j for j in range(len(leaves))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(dims, nodes=nodes, batch=batch),
        ),
        cost_estimate=pl.CostEstimate(
            flops=6 * nodes * steps * batch * sum(a * b for a, b in zip(dims, dims[1:])),
            transcendentals=nodes * steps * batch * dims[-1],
            bytes_accessed=4 * (2 * sum(math.prod(a.shape) for a in leaves)
                                + nodes * steps * batch * SUBLANES * dims[0]),
        ),
        interpret=interpret,
    )(rows.reshape(-1), labels.reshape(-1), *([x] * batch), *leaves)
    k = len(leaves) // 2
    return _tree(out[:k]), _tree(out[k:])
