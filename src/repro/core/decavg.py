"""DecAvg: one communication round of decentralized averaging (paper Eq. 1).

All per-node model state is *node-stacked*: every leaf of the parameter
pytree carries a leading ``node`` axis of size N. One communication round is
then the linear map ``P <- W @ P`` applied leaf-wise, where W is the
(N, N) row-stochastic mixing matrix from core/mixing.py.

Three execution paths, all numerically equivalent (tests assert allclose):

1. ``mix_dense``      — XLA einsum per leaf. The default on any backend.
2. ``mix_pallas``     — Pallas blocked-matmul kernel (kernels/gossip_mix.py)
                        per flattened leaf; MXU-tiled for TPU, validated in
                        interpret mode on CPU.
3. ``mix_sharded``    — explicit shard_map collective schedule for a node
                        axis sharded across a mesh axis; two schedules:
                        "allgather" (gather all nodes, multiply locally) and
                        "reduce_scatter" (scatter W-weighted contributions).
                        The RS schedule keeps peak memory at O(P·N/shards)
                        instead of O(P·N) — this is the form used at LLM
                        cohort scale.

Plus the sparse large-N paths (core/sparse.py): CSR segment-sum, the Pallas
blocked-ELL kernel, and ``mix_sharded_sparse`` — the CSR round with the node
axis sharded over a mesh axis (per-shard row ranges, compact halo buffers
for cross-shard neighbors, assembled by an allgather or ring-ppermute
``halo_schedule``). All O(E·P) per round instead of O(N²·P); the sharded
variant additionally splits the work S ways, and the ring schedule bounds
per-device wire to O(H·P).

``GossipEngine`` is the one front door over all of them: it owns the
topology (static graph or TopologySchedule), builds + caches the mixing
matrix per schedule period, capability-checks the requested backend, and
applies the per-round gossip cadence (``gossip_every`` / identity rounds)
that call sites used to reimplement inline. For fused runs,
``GossipEngine.program(rounds)`` materializes *all* schedule periods up
front as a ``MixingProgram`` (stacked dense W, uniformly padded stacked
CSR, stacked blocked-ELL tiles, or stacked per-shard ``ShardedCSR``
metadata) whose per-round operator is selected by index inside a
``lax.scan`` body — no per-period re-jit (train/trainer.py ``run_fused``).
For the sharded kind the ring/allgather halo exchange itself runs inside
the scan body under ``shard_map``, so a whole multi-host run is one
compiled SPMD program.

Precision contract: the sparse and shard_map paths accumulate in float32
regardless of parameter dtype, then cast back. The dense einsum path
(``mix_dense``/``_mix_leaf``) instead accumulates in the *leaf dtype* — an
f32 ``preferred_element_type`` would materialize a param-sized f32 temporary
per leaf (GBs/device at LLM scale), and the MXU accumulates bf16 dots in f32
internally anyway; tests/test_decavg.py pins the resulting bf16-vs-f32
tolerance. Run in f32 (the paper's sims do) when bit-level dense/sparse
agreement matters.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = [
    "GossipEngine",
    "MixingProgram",
    "mix_dense",
    "mix_pallas",
    "mix_sharded",
    "mix_sharded_sparse",
    "mix_sharded_sparse_faulted",
    "mix_permute",
    "gossip_error",
]

PyTree = Any


def _mix_leaf(w: jax.Array, leaf: jax.Array) -> jax.Array:
    """(N,N) x (N, ...) contraction over the node axis, f32 accumulation.

    No reshape: flattening (N, V, d) to (N, V*d) would merge a sharded dim
    and force GSPMD into a full rematerialization (replicating every node's
    params on every device — observed as an 80 GB/device dry-run). The
    dot_general below contracts the node axis in place, so inner-dim
    shardings propagate and a sharded node axis lowers to collectives only
    on the node dimension.
    """
    n = w.shape[0]
    if leaf.shape[0] != n:
        raise ValueError(f"leaf leading axis {leaf.shape[0]} != num_nodes {n}")
    # Output in the leaf dtype: an f32 preferred_element_type materializes a
    # param-sized f32 temporary per leaf (GBs/device at 100B+ scale). The
    # MXU accumulates bf16 dots in f32 internally regardless; for very wide
    # graphs (N=100 paper sims run in f32 anyway) precision is preserved by
    # the f32 leaf dtype itself.
    out = jax.lax.dot_general(
        w.astype(jnp.float32).astype(leaf.dtype),
        leaf,
        (((1,), (0,)), ((), ())),
        preferred_element_type=leaf.dtype,
    )
    return out


def mix_dense(w: jax.Array, params: PyTree) -> PyTree:
    """DecAvg round via per-leaf einsum (paper-faithful reference path)."""
    return jax.tree.map(functools.partial(_mix_leaf, w), params)


def mix_pallas(w: jax.Array, params: PyTree, *, interpret: bool | None = None) -> PyTree:
    """DecAvg round via the Pallas gossip_mix kernel (per flattened leaf)."""
    from repro.kernels import ops  # local import: kernels are optional at import time

    def mix(leaf: jax.Array) -> jax.Array:
        n = w.shape[0]
        flat = leaf.reshape(n, -1)
        out = ops.gossip_mix(w, flat, interpret=interpret)
        return out.reshape(leaf.shape).astype(leaf.dtype)

    return jax.tree.map(mix, params)


def mix_sharded(
    w: jax.Array,
    params: PyTree,
    *,
    mesh: jax.sharding.Mesh,
    node_axis: str | tuple[str, ...] = "data",
    schedule: Literal["allgather", "reduce_scatter"] = "reduce_scatter",
) -> PyTree:
    """DecAvg round with the node axis sharded over ``node_axis`` of ``mesh``.

    W is replicated (it is tiny: N^2 floats). Per-leaf inner sharding is
    preserved by passing everything through shard_map with generic specs on
    the trailing dims (we only touch axis 0).

    - allgather:      gather the full node axis, multiply my W-row-block.
      Moves P·(S-1)/S bytes in, peak memory O(P·N).
    - reduce_scatter: multiply my W-column-block by my params (my nodes'
      contributions to everyone), then reduce-scatter over the node axis.
      Moves the same bytes out, peak memory O(P·N/S). Preferred at scale.
    """
    axes = (node_axis,) if isinstance(node_axis, str) else tuple(node_axis)
    shards = 1
    for a in axes:
        shards *= mesh.shape[a]
    n = w.shape[0]
    if n % shards:
        raise ValueError(f"num_nodes {n} not divisible by node shards {shards}")

    def body(w_full: jax.Array, leaf: jax.Array) -> jax.Array:
        # leaf: (n/shards, ...) local block of the node axis.
        idx = jax.lax.axis_index(axes)
        blk = n // shards
        flat = leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)
        wf = w_full.astype(jnp.float32)
        if schedule == "allgather":
            full = jax.lax.all_gather(flat, axes, axis=0, tiled=True)  # (n, p)
            rows = jax.lax.dynamic_slice_in_dim(wf, idx * blk, blk, axis=0)
            out = rows @ full
        else:
            cols = jax.lax.dynamic_slice_in_dim(wf, idx * blk, blk, axis=1)  # (n, blk)
            contrib = cols @ flat  # (n, p): my nodes' contribution to everyone
            out = jax.lax.psum_scatter(contrib, axes, scatter_dimension=0, tiled=True)
        return out.reshape(leaf.shape).astype(leaf.dtype)

    def mix_one(leaf: jax.Array) -> jax.Array:
        spec = P(axes, *([None] * (leaf.ndim - 1)))
        return jax.shard_map(
            functools.partial(body),
            mesh=mesh,
            in_specs=(P(), spec),
            out_specs=spec,
        )(w, leaf)

    return jax.tree.map(mix_one, params)


def _mix_leaves_concatenated(params: PyTree, n: int, mix_cat) -> PyTree:
    """Run ``mix_cat`` ONCE on all leaves' features side by side.

    Mixing is linear over the node axis and columns are independent, so
    concatenating every leaf's flattened features into one (n, P_total) f32
    matrix computes bit-identical results to mixing leaf by leaf — while
    paying the halo exchange (ring ppermutes or allgather) and the
    replicated->sharded boundary movement once per ROUND instead of once per
    leaf. For an MLP that cuts the sharded path's collective count 4x.
    """
    leaves, treedef = jax.tree.flatten(params)
    for leaf in leaves:
        if leaf.shape[0] != n:
            raise ValueError(f"leaf leading axis {leaf.shape[0]} != num_nodes {n}")
    flats = [l.reshape(n, -1).astype(jnp.float32) for l in leaves]
    cat = flats[0] if len(flats) == 1 else jnp.concatenate(flats, axis=1)
    out = mix_cat(cat)
    if len(flats) == 1:
        outs = [out]
    else:
        splits = np.cumsum([f.shape[1] for f in flats])[:-1]
        outs = jnp.split(out, splits, axis=1)
    return jax.tree.unflatten(
        treedef,
        [o.reshape(l.shape).astype(l.dtype) for o, l in zip(outs, leaves)],
    )


def _mix_leaves_concatenated2(params: PyTree, pub: PyTree, n: int, mix_cat2) -> PyTree:
    """Two-tree variant of ``_mix_leaves_concatenated`` for faulted mixing:
    flattens ``params`` (current) and ``pub`` (published snapshots) into
    identically laid out (n, P_total) f32 matrices and runs ``mix_cat2``
    once over both — the faulted round needs both because stragglers gossip
    stale snapshots while the diagonal self-term stays fresh."""
    leaves, treedef = jax.tree.flatten(params)
    pleaves = jax.tree.leaves(pub)
    for leaf in leaves:
        if leaf.shape[0] != n:
            raise ValueError(f"leaf leading axis {leaf.shape[0]} != num_nodes {n}")
    flats = [l.reshape(n, -1).astype(jnp.float32) for l in leaves]
    pflats = [l.reshape(n, -1).astype(jnp.float32) for l in pleaves]
    cat = flats[0] if len(flats) == 1 else jnp.concatenate(flats, axis=1)
    pcat = pflats[0] if len(pflats) == 1 else jnp.concatenate(pflats, axis=1)
    out = mix_cat2(cat, pcat)
    if len(flats) == 1:
        outs = [out]
    else:
        splits = np.cumsum([f.shape[1] for f in flats])[:-1]
        outs = jnp.split(out, splits, axis=1)
    return jax.tree.unflatten(
        treedef,
        [o.reshape(l.shape).astype(l.dtype) for o, l in zip(outs, leaves)],
    )


def _sharded_mix_leaf(
    halo, rows, cols, values, local_src, local_dst, ring_send, ring_recv,
    leaf, *, axes, shards, blk, h, ring, p_chunk,
):
    """Per-device body of one sharded sparse DecAvg round on ONE leaf.

    Runs inside a ``shard_map`` over ``axes``: ``leaf`` is this device's
    (blk, ...) slab of the node axis; the layout arrays arrive replicated
    with a leading (S, ...) axis and are indexed by the device's shard
    position. Shared by ``mix_sharded_sparse`` (one shard_map per call) and
    ``MixingProgram.apply_local`` (the fused trainer's whole-scan shard_map).
    """
    idx = jax.lax.axis_index(axes)
    flat = leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)  # (blk, p)
    with jax.named_scope("decavg.halo_exchange"):
        if ring:
            # Halo buffer with one scratch row at slot H: padded local/ring
            # destinations point there and are discarded by the slice below.
            buf = jnp.zeros((h + 1, flat.shape[1]), jnp.float32)
            ls = jax.lax.dynamic_index_in_dim(local_src, idx, 0, keepdims=False)
            ld = jax.lax.dynamic_index_in_dim(local_dst, idx, 0, keepdims=False)
            buf = buf.at[ld].set(flat[ls])
            for d, (sidx, rslot) in enumerate(zip(ring_send, ring_recv), 1):
                if sidx.shape[1] == 0:
                    continue  # no shard pair exchanges at this distance
                send = jax.lax.dynamic_index_in_dim(sidx, idx, 0, keepdims=False)
                got = jax.lax.ppermute(
                    flat[send], axes,
                    [(s, (s + d) % shards) for s in range(shards)],
                )
                slot = jax.lax.dynamic_index_in_dim(rslot, idx, 0, keepdims=False)
                buf = buf.at[slot].set(got)
            buf = buf[:h]  # (H, p); cols only ever reference [0, H)
        else:
            full = jax.lax.all_gather(flat, axes, axis=0, tiled=True)  # (n, p)
            need = jax.lax.dynamic_index_in_dim(halo, idx, 0, keepdims=False)
            buf = full[need]  # (H, p): only rows this shard references
    r = jax.lax.dynamic_index_in_dim(rows, idx, 0, keepdims=False)
    c = jax.lax.dynamic_index_in_dim(cols, idx, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(values, idx, 0, keepdims=False)

    def seg(hbuf: jax.Array) -> jax.Array:
        gathered = hbuf[c] * v[:, None]  # (E, pc)
        return jax.ops.segment_sum(
            gathered, r, num_segments=blk, indices_are_sorted=True
        )

    p = flat.shape[1]
    if p_chunk is not None and p_chunk < p:
        pad = (-p) % p_chunk
        if pad:
            buf = jnp.pad(buf, ((0, 0), (0, pad)))
        chunks = buf.reshape(buf.shape[0], -1, p_chunk).transpose(1, 0, 2)
        out = jax.lax.map(seg, chunks)  # serialized: bounds the transient
        out = out.transpose(1, 0, 2).reshape(blk, -1)[:, :p]
    else:
        out = seg(buf)
    return out.reshape(leaf.shape).astype(leaf.dtype)


@functools.partial(
    jax.jit, static_argnames=("mesh", "node_axis", "p_chunk", "halo_schedule")
)
def mix_sharded_sparse(
    shcsr,
    params: PyTree,
    *,
    mesh: jax.sharding.Mesh,
    node_axis: str | tuple[str, ...] = "data",
    p_chunk: int | None = None,
    halo_schedule: Literal["allgather", "ring", "auto"] = "allgather",
) -> PyTree:
    """Sparse DecAvg round with the node axis sharded over ``node_axis``.

    ``shcsr`` is a ``core.sparse.ShardedCSR``: each shard owns a contiguous
    row range of W and stores its entries with halo-local column ids. The
    round per device is

      1. assemble the shard's *halo* — the compact set of source rows its
         W entries actually reference — into an (H, p) buffer,
      2. gather + segment-sum over the shard's nnz entries, O(nnz_s * p).

    Step 1 runs one of two ``halo_schedule``s (numerically identical):

    - "allgather": all_gather the node axis of P, slice the halo rows.
      One collective, O(N * p) wire per device.
    - "ring": S-1 ``ppermute`` steps over the shard ring; step d moves
      exactly the rows each shard needs from its distance-d peer
      (``shcsr.ring_send/ring_recv``), own rows are copied locally. Steps
      with no traffic anywhere compile away, so wire per device is
      O(H * p) — the sparse topology becomes the communication schedule,
      not just the compute schedule.
    - "auto": ring when its modeled wire (``shcsr.ring_width``) undercuts
      the allgather's N - N/S rows, else allgather.

    Compute and W memory are sparse either way (O(nnz/S * P) work per
    device, O(E) total W bytes vs the dense sharded path's O(N^2/S * P)
    matmul and O(N^2) W).

    ``p_chunk`` bounds the per-device gather transient to O(nnz_s * p_chunk)
    (serialized feature-axis chunks, as in ``sparse.mix_sparse``) — use for
    very large per-leaf P at large N.
    """
    axes = (node_axis,) if isinstance(node_axis, str) else tuple(node_axis)
    shards = 1
    for a in axes:
        shards *= mesh.shape[a]
    if shcsr.shards != shards:
        raise ValueError(
            f"ShardedCSR built for {shcsr.shards} shards but mesh axis "
            f"{axes} has {shards}"
        )
    n = shcsr.shape[0]
    blk = shcsr.rows_per_shard
    h = shcsr.halo_width
    if halo_schedule == "auto":
        halo_schedule = "ring" if shcsr.ring_width < n - blk else "allgather"
    if halo_schedule not in ("allgather", "ring"):
        raise ValueError(
            f"halo_schedule must be 'allgather', 'ring' or 'auto', "
            f"got {halo_schedule!r}"
        )
    ring = halo_schedule == "ring"
    body = functools.partial(
        _sharded_mix_leaf, axes=axes, shards=shards, blk=blk, h=h,
        ring=ring, p_chunk=p_chunk,
    )

    def mix_cat(cat: jax.Array) -> jax.Array:
        spec = P(axes, None)
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(), P(), P(), P(), spec),
            out_specs=spec,
        )(shcsr.halo, shcsr.rows, shcsr.cols, shcsr.values,
          shcsr.local_src, shcsr.local_dst, shcsr.ring_send, shcsr.ring_recv,
          cat)

    return _mix_leaves_concatenated(params, n, mix_cat)


def _sharded_mix_leaf_faulted(
    halo, rows, cols, values, keep, alive, local_src, local_dst,
    ring_send, ring_recv, cur, pub, *, axes, shards, blk, h, ring,
):
    """Faulted twin of ``_sharded_mix_leaf``: one shard's renormalized mix.

    Two data slabs instead of one: ``cur`` (this shard's current params)
    and ``pub`` (its *published* snapshots — stale for stragglers). The
    halo exchange moves published rows; ``keep`` arrives as the round's
    (S, E) entry mask and ``alive`` as the replicated (N,) node mask. The
    round per shard is ``segment_sum(pub_halo * W_renorm) + diag * (cur -
    pub)`` with dead / empty rows passing ``cur`` through bit-unchanged —
    identical semantics to ``faults.mix_faulted_csr`` on global ids, so
    loop and fused faulted sharded runs agree exactly.
    """
    from repro.core import faults as _faults

    idx = jax.lax.axis_index(axes)
    curf = cur.reshape(cur.shape[0], -1).astype(jnp.float32)  # (blk, p)
    pubf = pub.reshape(pub.shape[0], -1).astype(jnp.float32)
    halo_s = jax.lax.dynamic_index_in_dim(halo, idx, 0, keepdims=False)
    with jax.named_scope("decavg.halo_exchange"):
        if ring:
            buf = jnp.zeros((h + 1, pubf.shape[1]), jnp.float32)
            ls = jax.lax.dynamic_index_in_dim(local_src, idx, 0, keepdims=False)
            ld = jax.lax.dynamic_index_in_dim(local_dst, idx, 0, keepdims=False)
            buf = buf.at[ld].set(pubf[ls])
            for d, (sidx, rslot) in enumerate(zip(ring_send, ring_recv), 1):
                if sidx.shape[1] == 0:
                    continue
                send = jax.lax.dynamic_index_in_dim(sidx, idx, 0, keepdims=False)
                got = jax.lax.ppermute(
                    pubf[send], axes,
                    [(s, (s + d) % shards) for s in range(shards)],
                )
                slot = jax.lax.dynamic_index_in_dim(rslot, idx, 0, keepdims=False)
                buf = buf.at[slot].set(got)
            buf = buf[:h]
        else:
            full = jax.lax.all_gather(pubf, axes, axis=0, tiled=True)  # (n, p)
            buf = full[halo_s]
    r = jax.lax.dynamic_index_in_dim(rows, idx, 0, keepdims=False)
    c = jax.lax.dynamic_index_in_dim(cols, idx, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(values, idx, 0, keepdims=False)
    k = jax.lax.dynamic_index_in_dim(keep, idx, 0, keepdims=False)
    vn, ok = _faults.renorm_values(v, k, r, blk)
    # Diagonal coefficient per local row: entries whose global source is
    # the destination itself (padded slots carry v == 0, so a spurious
    # halo-pad match contributes nothing).
    is_diag = halo_s[c] == idx * blk + r
    dcoef = jax.ops.segment_sum(
        jnp.where(is_diag, vn, 0.0), r, num_segments=blk,
        indices_are_sorted=True,
    )
    # Off-diagonal rewrite (cf. faults.mix_faulted_csr): stale publishes
    # flow through non-self entries only, the fresh self term is added
    # directly — one fewer params-sized elementwise pass per round.
    vn_od = jnp.where(is_diag, 0.0, vn)
    out = jax.ops.segment_sum(
        buf[c] * vn_od[:, None], r, num_segments=blk, indices_are_sorted=True
    )
    out = out + dcoef[:, None] * curf
    alive_s = jax.lax.dynamic_slice_in_dim(alive, idx * blk, blk)
    okr = ok & alive_s
    out = jnp.where(okr[:, None], out, curf)
    return out.reshape(cur.shape).astype(cur.dtype)


@functools.partial(
    jax.jit, static_argnames=("mesh", "node_axis", "halo_schedule")
)
def mix_sharded_sparse_faulted(
    shcsr,
    params: PyTree,
    pub: PyTree,
    keep: jax.Array,
    alive: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    node_axis: str | tuple[str, ...] = "data",
    halo_schedule: Literal["allgather", "ring", "auto"] = "allgather",
) -> PyTree:
    """One faulted sharded sparse DecAvg round (cf. ``mix_sharded_sparse``).

    ``keep`` is the round's (S, E) per-shard entry mask and ``alive`` the
    (N,) node mask (both replicated — they are tiny next to P). ``pub`` is
    the published-snapshot pytree (pass ``params`` when no stragglers).
    Feature-axis chunking is not supported under faults (the engine rejects
    the combination): the renormalization is per-entry, so the chunked
    serialization would recompute it per chunk for no transient win.
    """
    axes = (node_axis,) if isinstance(node_axis, str) else tuple(node_axis)
    shards = 1
    for a in axes:
        shards *= mesh.shape[a]
    if shcsr.shards != shards:
        raise ValueError(
            f"ShardedCSR built for {shcsr.shards} shards but mesh axis "
            f"{axes} has {shards}"
        )
    n = shcsr.shape[0]
    blk = shcsr.rows_per_shard
    h = shcsr.halo_width
    if halo_schedule == "auto":
        halo_schedule = "ring" if shcsr.ring_width < n - blk else "allgather"
    ring = halo_schedule == "ring"
    body = functools.partial(
        _sharded_mix_leaf_faulted, axes=axes, shards=shards, blk=blk, h=h,
        ring=ring,
    )

    def mix_cat2(cat: jax.Array, pcat: jax.Array) -> jax.Array:
        spec = P(axes, None)
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(),) * 10 + (spec, spec),
            out_specs=spec,
        )(shcsr.halo, shcsr.rows, shcsr.cols, shcsr.values, keep, alive,
          shcsr.local_src, shcsr.local_dst, shcsr.ring_send, shcsr.ring_recv,
          cat, pcat)

    return _mix_leaves_concatenated2(params, pub, n, mix_cat2)


def mix_permute(
    w: jax.Array | Any,
    params: PyTree,
    colors: list[list[tuple[int, int]]],
    *,
    mesh: jax.sharding.Mesh,
    node_axis: str = "data",
) -> PyTree:
    """Sparse topology-aware DecAvg round via edge-colored ppermutes.

    Requires num_nodes == mesh.shape[node_axis] (one node per device row).
    Each color class (a matching, from mixing.edge_coloring) becomes ONE
    ``ppermute``; wire volume per device is O(degree) member-shards instead
    of the dense einsum's O(N) all-gather — the paper's sparse topology IS
    the collective schedule. Numerically identical to ``mix_dense`` with the
    same W (tests assert allclose); W entries off the graph support are
    ignored by construction.
    """
    k = mesh.shape[node_axis]
    if w.shape[0] != k:
        raise ValueError(
            f"mix_permute needs num_nodes == |{node_axis}| ({k}), got {w.shape[0]}"
        )
    # W may be a tracer (it is a train_step input): build the per-color
    # coefficient vectors with jnp gathers, not host numpy.
    wf = jnp.asarray(w, jnp.float32)
    self_coef = jnp.diagonal(wf)  # (K,)
    color_coefs = []
    for pairs in colors:
        srcs = np.array([s for s, _ in pairs], np.int32)
        dsts = np.array([d for _, d in pairs], np.int32)
        vec = jnp.zeros((k,), jnp.float32).at[dsts].set(wf[dsts, srcs])
        color_coefs.append(vec)

    def body(leaf: jax.Array) -> jax.Array:
        # leaf: (1, ...) — this device row's node shard.
        i = jax.lax.axis_index(node_axis)
        xf = leaf.astype(jnp.float32)
        acc = xf * self_coef[i]
        for pairs, vec in zip(colors, color_coefs):
            y = jax.lax.ppermute(xf, node_axis, pairs)
            acc = acc + y * vec[i]
        return acc.astype(leaf.dtype)

    def mix_one(leaf: jax.Array) -> jax.Array:
        spec = P(node_axis, *([None] * (leaf.ndim - 1)))
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=spec,
            out_specs=spec,
            axis_names=frozenset({node_axis}),
        )(leaf)

    return jax.tree.map(mix_one, params)


# ---------------------------------------------------------------------------
# MixingProgram: all schedule periods staged up front for a fused lax.scan
# ---------------------------------------------------------------------------


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=(
        "w", "rows", "cols", "values", "period_idx", "gossip_mask",
        "pad_ratio", "bell_idx", "bell_val",
        "sh_halo", "sh_rows", "sh_cols", "sh_values",
        "sh_local_src", "sh_local_dst", "sh_ring_send", "sh_ring_recv",
        "f_alive", "f_keep", "f_delay",
    ),
    meta_fields=(
        "kind", "n", "num_periods", "cadence", "p_chunk",
        "interpret", "mesh", "node_axis", "shards", "halo_schedule",
        "faulted", "delay_max",
    ),
)
@dataclasses.dataclass(frozen=True)
class MixingProgram:
    """Every schedule period of a run, materialized as stacked operators.

    The Python training loop rebuilds (and re-traces against) one mixing
    matrix per schedule period. A fused run cannot: the whole multi-round
    program is a single ``lax.scan``, so *all* periods must exist on device
    before the scan starts and the body must select the current period by
    index. ``GossipEngine.program(rounds)`` builds one of these:

    - kind "dense":  ``w`` is (T, N, N) — the body gathers ``w[period_idx[r]]``
      and runs the ordinary per-leaf contraction.
    - kind "sparse": per-period CSRs padded to a uniform nnz and stacked as
      (T, E) ``rows``/``cols``/``values``. Padding entries carry weight 0 and
      point at row N-1 / column 0 (appended after the sorted real entries, so
      segment ids stay sorted) — they add exact zeros.
    - kind "sparse_pallas": per-period blocked-ELL tiles padded to a common
      block count (``sparse.stack_block_ell``) as ``bell_idx`` (T, NB, KB) +
      ``bell_val`` (T, NB*8, KB*8); the body indexes the period axis and
      invokes the 8-row-blocked kernel (``interpret`` resolved at staging).
    - kind "sparse_sharded": per-period ``ShardedCSR`` metadata padded to
      common widths (``sparse.stack_shard_csr``) as ``sh_*`` arrays with a
      leading period axis. The fused trainer wraps its whole round scan in
      ONE ``shard_map`` over ``node_axis`` and calls ``apply_local`` per
      round: the S-1 ``ppermute`` ring steps (or the allgather) execute
      *inside* the fused scan, with ``halo_schedule`` ("auto" resolves once
      from the stacked widths, common to all periods) and ``p_chunk``
      semantics preserved. ``apply`` remains the self-contained (shard_map
      per call) form, used by the loop-parity tests.

    ``period_idx`` maps the global round index to the stacked period slot;
    ``gossip_mask`` carries the ``gossip_every`` cadence. ``cadence`` is the
    trace-time shortcut: "always" skips the ``lax.cond`` entirely
    (gossip_every == 1), "never" makes ``mix_at`` the identity
    (gossip_every == 0), "mask" selects per round inside the scan body.

    ``pad_ratio`` is the staging-overhead diagnostic: stacked operator slots
    per real W entry (1.0 = no padding waste; dense kind reports 1.0). A
    ``@regen`` schedule whose periods vary widely in edge count pads every
    period to the widest one — a large ratio makes that visible instead of
    silently wasting device memory.

    Registered as a pytree so it passes through ``jax.jit`` as data: a fused
    chunk retraces on a new *shape* (different T/E/rounds), never on new
    values (a different seed's schedule reuses the compiled program).
    """

    kind: str  # "dense" | "sparse" | "sparse_pallas" | "sparse_sharded"
    n: int
    num_periods: int
    cadence: str  # "always" | "never" | "mask"
    period_idx: jax.Array  # (rounds,) int32: round -> stacked period slot
    gossip_mask: jax.Array  # (rounds,) bool
    p_chunk: int | None = None  # sparse gather feature-axis chunk (see sparse.mix_sparse)
    w: jax.Array | None = None  # (T, N, N) f32, kind == "dense"
    rows: jax.Array | None = None  # (T, E) int32, kind == "sparse"
    cols: jax.Array | None = None  # (T, E) int32
    values: jax.Array | None = None  # (T, E) f32
    pad_ratio: float = 1.0  # stacked operator slots per real W entry
    bell_idx: jax.Array | None = None  # (T, NB, KB) int32, kind == "sparse_pallas"
    bell_val: jax.Array | None = None  # (T, NB*8, KB*8) f32
    sh_halo: jax.Array | None = None  # (T, S, H) int32, kind == "sparse_sharded"
    sh_rows: jax.Array | None = None  # (T, S, E) int32
    sh_cols: jax.Array | None = None  # (T, S, E) int32
    sh_values: jax.Array | None = None  # (T, S, E) f32
    sh_local_src: jax.Array | None = None  # (T, S, L) int32
    sh_local_dst: jax.Array | None = None  # (T, S, L) int32
    sh_ring_send: tuple[jax.Array, ...] = ()  # per ring step: (T, S, K_d) int32
    sh_ring_recv: tuple[jax.Array, ...] = ()
    interpret: bool | None = None  # kind == "sparse_pallas" (resolved at staging)
    mesh: jax.sharding.Mesh | None = None  # kind == "sparse_sharded"
    node_axis: str | None = None
    shards: int | None = None
    halo_schedule: str | None = None
    # Fault-injection axis (core/faults.py), staged by round rather than by
    # period — masks are drawn per round even within one schedule period.
    faulted: bool = False
    delay_max: int = 0  # straggler ring-buffer depth is delay_max + 1
    f_alive: jax.Array | None = None  # (rounds, N) bool
    f_keep: jax.Array | None = None  # (rounds,N,N) | (rounds,E) | (rounds,S,E)
    f_delay: jax.Array | None = None  # (N,) int32 per-node staleness

    @property
    def rounds(self) -> int:
        return int(self.period_idx.shape[0])

    def _shcsr_at(self, t: jax.Array):
        """Reconstruct round slot ``t``'s ShardedCSR view (traced slices of
        the stacked metadata; static shapes are period-independent)."""
        from repro.core import sparse

        return sparse.ShardedCSR(
            halo=self.sh_halo[t],
            rows=self.sh_rows[t],
            cols=self.sh_cols[t],
            values=self.sh_values[t],
            local_src=self.sh_local_src[t],
            local_dst=self.sh_local_dst[t],
            ring_send=tuple(a[t] for a in self.sh_ring_send),
            ring_recv=tuple(a[t] for a in self.sh_ring_recv),
            shape=(self.n, self.n),
            shards=self.shards,
            rows_per_shard=self.n // self.shards,
        )

    def apply(self, params: PyTree, r: jax.Array, pub: PyTree | None = None) -> PyTree:
        """One unconditional mixing round with round ``r``'s operator
        (``r`` may be a tracer inside a scan body).

        When the program is ``faulted``, round ``r``'s alive / entry-keep
        masks renormalize the operator on the fly and ``pub`` supplies the
        published snapshots stragglers gossip (defaults to ``params``)."""
        t = self.period_idx[r]
        if self.faulted:
            from repro.core import faults as _faults

            keep, alive = self.f_keep[r], self.f_alive[r]
            if pub is None:
                pub = params
            if self.kind == "dense":
                return _faults.mix_faulted_dense(
                    self.w[t], keep, alive, params, pub
                )
            if self.kind == "sparse":
                return _faults.mix_faulted_csr(
                    self.rows[t], self.cols[t], self.values[t],
                    keep, alive, self.n, params, pub,
                )
            if self.kind == "sparse_sharded":
                return mix_sharded_sparse_faulted(
                    self._shcsr_at(t), params, pub, keep, alive,
                    mesh=self.mesh, node_axis=self.node_axis,
                    halo_schedule=self.halo_schedule,
                )
            raise ValueError(f"kind {self.kind!r} does not support faults")
        if self.kind == "dense":
            return mix_dense(self.w[t], params)
        if self.kind == "sparse_pallas":
            from repro.kernels import ops

            idx, val = self.bell_idx[t], self.bell_val[t]

            def bleaf(l: jax.Array) -> jax.Array:
                flat = l.reshape(self.n, -1)
                out = ops.gossip_mix_sparse_blocked(
                    idx, val, flat, interpret=self.interpret
                )
                return out.reshape(l.shape).astype(l.dtype)

            return jax.tree.map(bleaf, params)
        if self.kind == "sparse_sharded":
            return mix_sharded_sparse(
                self._shcsr_at(t), params,
                mesh=self.mesh, node_axis=self.node_axis,
                p_chunk=self.p_chunk, halo_schedule=self.halo_schedule,
            )
        rows, cols, values = self.rows[t], self.cols[t], self.values[t]

        def seg(flat: jax.Array) -> jax.Array:
            gathered = flat[cols] * values[:, None]  # (E, pc)
            return jax.ops.segment_sum(
                gathered, rows, num_segments=self.n, indices_are_sorted=True
            )

        def leaf(l: jax.Array) -> jax.Array:
            flat = l.reshape(self.n, -1).astype(jnp.float32)
            p = flat.shape[1]
            if self.p_chunk is not None and self.p_chunk < p:
                # Same transient bound as sparse.mix_sparse(p_chunk=...):
                # serialized feature-axis chunks keep the gather buffer at
                # O(E * p_chunk) inside the scan body too.
                pad = (-p) % self.p_chunk
                if pad:
                    flat = jnp.pad(flat, ((0, 0), (0, pad)))
                chunks = flat.reshape(self.n, -1, self.p_chunk).transpose(1, 0, 2)
                out = jax.lax.map(seg, chunks)
                out = out.transpose(1, 0, 2).reshape(self.n, -1)[:, :p]
            else:
                out = seg(flat)
            return out.reshape(l.shape).astype(l.dtype)

        return jax.tree.map(leaf, params)

    def mix_at(self, params: PyTree, r: jax.Array, pub: PyTree | None = None) -> PyTree:
        """``apply`` gated by the gossip cadence (identity on skip rounds)."""
        if self.cadence == "never":
            return params
        with jax.named_scope("decavg.mix"):
            if self.cadence == "always":
                return self.apply(params, r, pub)
            if pub is None:
                return jax.lax.cond(
                    self.gossip_mask[r], lambda p: self.apply(p, r), lambda p: p, params
                )
            return jax.lax.cond(
                self.gossip_mask[r],
                lambda a: self.apply(a[0], r, a[1]), lambda a: a[0], (params, pub),
            )

    def _sharded_static(self) -> tuple[tuple[str, ...], bool, int]:
        """(axes, ring?, blk) for the stacked sharded layout. The ring/
        allgather decision uses the same rule as ``mix_sharded_sparse`` but
        resolves ONCE from the stacked widths, which ``stack_shard_csr``
        keeps common to every period."""
        axes = (
            (self.node_axis,) if isinstance(self.node_axis, str)
            else tuple(self.node_axis)
        )
        blk = self.n // self.shards
        sched = self.halo_schedule
        if sched == "auto":
            ring_width = sum(int(a.shape[2]) for a in self.sh_ring_send)
            sched = "ring" if ring_width < self.n - blk else "allgather"
        return axes, sched == "ring", blk

    def apply_local(self, params: PyTree, r: jax.Array, pub: PyTree | None = None) -> PyTree:
        """Kind "sparse_sharded" only: round ``r``'s mix on this device's
        LOCAL (N/S, ...) slab — must be called inside a ``shard_map`` over
        ``node_axis``. Under ``faulted`` programs, ``pub`` is the local slab
        of published snapshots (defaults to ``params``).

        This is what lets the fused trainer keep the ENTIRE round scan under
        one shard_map (train step genuinely node-sharded, carry never
        resharded between rounds): the ring ppermutes / allgather execute
        directly in the caller's SPMD context. Calling ``apply`` instead —
        a shard_map per mix inside the scan — makes everything *outside* the
        mix replicated on every device and reshards the carry each iteration.
        """
        if self.kind != "sparse_sharded":
            raise ValueError(
                f"apply_local needs kind 'sparse_sharded', got {self.kind!r}"
            )
        t = self.period_idx[r]
        axes, ring, blk = self._sharded_static()
        if self.faulted:
            mix = functools.partial(
                _sharded_mix_leaf_faulted,
                self.sh_halo[t], self.sh_rows[t], self.sh_cols[t],
                self.sh_values[t], self.f_keep[r], self.f_alive[r],
                self.sh_local_src[t], self.sh_local_dst[t],
                tuple(a[t] for a in self.sh_ring_send),
                tuple(a[t] for a in self.sh_ring_recv),
                axes=axes, shards=self.shards, blk=blk,
                h=int(self.sh_halo.shape[2]), ring=ring,
            )
            return _mix_leaves_concatenated2(
                params, params if pub is None else pub, blk, mix
            )
        mix = functools.partial(
            _sharded_mix_leaf,
            self.sh_halo[t], self.sh_rows[t], self.sh_cols[t],
            self.sh_values[t], self.sh_local_src[t], self.sh_local_dst[t],
            tuple(a[t] for a in self.sh_ring_send),
            tuple(a[t] for a in self.sh_ring_recv),
            axes=axes, shards=self.shards, blk=blk,
            h=int(self.sh_halo.shape[2]), ring=ring, p_chunk=self.p_chunk,
        )
        return _mix_leaves_concatenated(params, blk, mix)

    def mix_at_local(self, params: PyTree, r: jax.Array, pub: PyTree | None = None) -> PyTree:
        """``apply_local`` gated by the gossip cadence (cf. ``mix_at``)."""
        if self.cadence == "never":
            return params
        with jax.named_scope("decavg.mix"):
            if self.cadence == "always":
                return self.apply_local(params, r, pub)
            if pub is None:
                return jax.lax.cond(
                    self.gossip_mask[r],
                    lambda p: self.apply_local(p, r), lambda p: p, params,
                )
            return jax.lax.cond(
                self.gossip_mask[r],
                lambda a: self.apply_local(a[0], r, a[1]), lambda a: a[0],
                (params, pub),
            )


# ---------------------------------------------------------------------------
# GossipEngine: one capability-checked front door over every mixing path
# ---------------------------------------------------------------------------

_MATRIX_KINDS = ("decavg", "uniform", "mh")

# Backend -> {requires, cost, wire, fused, faults, notes}.
# Source of truth for GossipEngine.capabilities() and the README matrix —
# the matrix is generated from this table (`python -m repro.lint
# --write-capmatrix`) and lint rule C001 fails CI when they drift.
# ``fused`` means program() can stage every schedule period for this backend,
# so DecentralizedTrainer.run_fused covers it (its _FUSED_BACKENDS mirrors
# this flag, pinned by test and by C001). ``faults`` means the backend
# supports the core/faults.py renormalized-mixing semantics (per-round alive
# / edge-drop masks + straggler snapshots): the Pallas kernels bake W values
# into tiles and the dense-sharded / permute paths precompute their
# collective coefficients, so per-round renormalization is
# dense/sparse/sparse_sharded territory.
_BACKEND_INFO = {
    "dense": {
        "requires": "any backend; W materialized (N,N)",
        "cost": "O(N^2 * P)",
        "wire": "—",
        "fused": True,
        "faults": True,
        "notes": "XLA einsum per leaf; reference path",
    },
    "pallas": {
        "requires": "TPU (interpret elsewhere); W materialized (N,N)",
        "cost": "O(N^2 * P), zero W tiles skipped",
        "wire": "—",
        "fused": False,
        "faults": False,
        "notes": "MXU-tiled blocked matmul",
    },
    "sparse": {
        "requires": "any backend; W stored CSR, O(E) memory",
        "cost": "O(E * P)",
        "wire": "—",
        "fused": True,
        "faults": True,
        "notes": "CSR gather + segment-sum; default at N >= 512",
    },
    "sparse_pallas": {
        "requires": "TPU (interpret elsewhere); W stored blocked ELL",
        "cost": "O(E * P)",
        "wire": "—",
        "fused": True,
        "faults": False,
        "notes": "8-row-blocked ELL kernel (sublane-packed block DMAs); "
                 "scalar row-gather fallback under interpret",
    },
    "sharded": {
        "requires": "mesh with node axis; N divisible by shards",
        "cost": "O(N^2 * P / S) per device",
        "wire": "always O(N * P) allgather",
        "fused": False,
        "faults": False,
        "notes": "shard_map allgather / reduce-scatter",
    },
    "sparse_sharded": {
        "requires": "mesh with node axis (default: all local devices); N "
                    "divisible by shards; W stored per-shard CSR with halo "
                    "columns; halo_schedule allgather|ring|auto",
        "cost": "O(E * P / S) work per device",
        "wire": "allgather O(N * P) / ring O(H * P); auto picks ring when "
                "it undercuts",
        "fused": True,
        "faults": True,
        "notes": "per-shard CSR row ranges + halo buffers; default at "
                 "N >= 512 with a mesh",
    },
    "permute": {
        "requires": "mesh with node axis; N == |axis|; recolors per "
                    "schedule period",
        "cost": "O(degree * P) compute per device",
        "wire": "O(degree * P) per device",
        "fused": False,
        "faults": False,
        "notes": "edge-colored ppermute schedule; recolors per period for "
                 "time-varying schedules",
    },
}


class GossipEngine:
    """Owns topology, mixing matrix, backend dispatch and gossip cadence.

    One engine replaces the per-call-site wiring of graph construction,
    ``decavg_matrix``, backend choice and the ``gossip_every`` loop logic::

        engine = GossipEngine("ba:n=4096,m=2", backend="auto", gossip_every=2)
        params = engine.mix(params, round=i)   # identity rounds are free

    Args:
      topology: a registry spec string (``"ba:n=100,m=2"``, may carry an
        ``@regen=``/``@rewire=`` schedule suffix), a built ``Graph``, or a
        ``TopologySchedule``.
      data_sizes: per-node |D_j| for the Eq. 1 weights (default: uniform).
      matrix: "decavg" (paper Eq. 1), "uniform" (closed-neighborhood mean)
        or "mh" (Metropolis–Hastings, doubly stochastic).
      backend: one of ``GossipEngine.BACKENDS`` or "auto" (sparse at
        N >= sparse_threshold, else dense; with a mesh, sparse_sharded at
        N >= sparse_threshold, else sharded). "sparse_sharded" without a
        mesh builds a 1-D mesh over all local devices.
      gossip_every: mix on rounds with ``round % gossip_every == 0``; other
        rounds are identity and skip all work.
      mesh/node_axis/sharded_schedule: for the shard_map backends.
      halo_schedule: sparse_sharded halo assembly — "allgather" (one
        collective, O(N*P) wire), "ring" (S-1 ppermute steps, O(H*P) wire)
        or "auto" (ring whenever its modeled wire undercuts the allgather's).
      interpret: forwarded to the Pallas backends (default: auto-detect).
      sparse_p_chunk: feature-axis chunk for the sparse gather — an int,
        "auto" (sized from nnz to a ~16 MiB transient), or None (off).
        Bounds the O(nnz * P) gather buffer for very large per-leaf P.
      faults: a fault spec string or ``FaultSchedule`` (core/faults.py) —
        per-round churn / straggler / edge-drop injection, expanded
        deterministically from ``seed``. Only the fault-capable backends
        (``capabilities()[b]["faults"]``) accept it, and it does not
        compose with ``sparse_p_chunk``.
      **topology_defaults: fallback spec params (e.g. ``n=...``) when
        ``topology`` is a spec string.
    """

    BACKENDS = (
        "dense", "pallas", "sparse", "sparse_pallas", "sharded",
        "sparse_sharded", "permute",
    )

    def __init__(
        self,
        topology,
        *,
        data_sizes: np.ndarray | None = None,
        matrix: str = "decavg",
        backend: str = "auto",
        gossip_every: int = 1,
        mesh: jax.sharding.Mesh | None = None,
        node_axis: str = "data",
        sharded_schedule: Literal["allgather", "reduce_scatter"] = "reduce_scatter",
        halo_schedule: Literal["allgather", "ring", "auto"] = "auto",
        interpret: bool | None = None,
        sparse_threshold: int = 512,
        sparse_p_chunk: int | Literal["auto"] | None = None,
        faults: Any = None,
        validate: bool = True,
        seed: int = 0,
        **topology_defaults,
    ):
        from repro.core import topology as topo

        if isinstance(topology, str):
            topology = topo.make_schedule(topology, seed=seed, **topology_defaults)
        elif isinstance(topology, topo.Graph):
            topology = topo.TopologySchedule.static(topology)
        elif not isinstance(topology, topo.TopologySchedule):
            raise TypeError(f"topology must be spec/Graph/TopologySchedule, got {type(topology)}")
        self.schedule = topology
        self.num_nodes = topology.num_nodes
        if matrix not in _MATRIX_KINDS:
            raise ValueError(f"matrix must be one of {_MATRIX_KINDS}, got {matrix!r}")
        self.matrix = matrix
        self.data_sizes = (
            np.ones(self.num_nodes) if data_sizes is None
            else np.asarray(data_sizes, dtype=np.float64)
        )
        self.gossip_every = int(gossip_every)
        self.mesh = mesh
        self.node_axis = node_axis
        self.sharded_schedule = sharded_schedule
        if halo_schedule not in ("allgather", "ring", "auto"):
            raise ValueError(
                f"halo_schedule must be 'allgather', 'ring' or 'auto', "
                f"got {halo_schedule!r}"
            )
        self.halo_schedule = halo_schedule
        self.interpret = interpret
        self.sparse_threshold = int(sparse_threshold)
        # Feature-axis chunking for the sparse gather (None = off; "auto"
        # sizes the chunk from nnz so the transient buffer stays ~16 MiB).
        self.sparse_p_chunk = sparse_p_chunk
        self.validate = validate
        self.seed = int(seed)
        if faults is not None:
            from repro.core import faults as faults_mod

            self.faults = faults_mod.FaultSchedule.parse(faults)
            if sparse_p_chunk is not None:
                raise ValueError(
                    "faults do not compose with sparse_p_chunk: the faulted "
                    "mix renormalizes per entry, so chunked gathers would "
                    "redo it per chunk for no transient win"
                )
        else:
            self.faults = None
        self._fault_trace = None
        self._fault_hist = None  # loop-path straggler ring buffer (mix())
        self.backend = self._resolve_backend(backend)
        if self.backend == "sparse_sharded" and self.mesh is None:
            self.mesh = self._default_node_mesh()
        self.check(self.backend)
        self._period: int | None = None
        self._graph = None
        self._w = None
        self._csr = None
        self._ell = None
        self._bell = None
        self._shcsr = None
        self._colors = None
        # Edge colorings are deterministic per schedule period; cache them so
        # revisiting a period (or mixing repeatedly within one) never recolors.
        self._colors_cache: dict[int, list] = {}
        self.refresh(0)

    # -- capability checking -------------------------------------------------

    @classmethod
    def capabilities(cls) -> dict[str, dict[str, str | bool]]:
        """Backend -> {requires, cost, wire, fused, faults, notes} — the
        README matrix rows (repro.lint C001 keeps the two in lockstep)."""
        return {b: dict(info) for b, info in _BACKEND_INFO.items()}

    def _resolve_backend(self, backend: str) -> str:
        if backend != "auto":
            if backend not in self.BACKENDS:
                raise ValueError(
                    f"unknown backend {backend!r}; one of {self.BACKENDS} or 'auto'"
                )
            return backend
        if self.mesh is not None:
            return (
                "sparse_sharded"
                if self.faults is not None  # dense-sharded can't renormalize
                or self.num_nodes >= self.sparse_threshold
                else "sharded"
            )
        return "sparse" if self.num_nodes >= self.sparse_threshold else "dense"

    def _default_node_mesh(self) -> jax.sharding.Mesh:
        """1-D mesh over every local device — the sparse_sharded default, so
        large-N sparse cohorts run node-sharded without call-site mesh wiring."""
        return jax.sharding.Mesh(np.asarray(jax.devices()), (self.node_axis,))

    def check(self, backend: str, mesh: jax.sharding.Mesh | None = None) -> None:
        """Raise with an actionable message if ``backend`` can't run here.
        ``mesh`` overrides ``self.mesh`` for the check (per-call overrides)."""
        mesh = self.mesh if mesh is None else mesh
        if backend in ("sharded", "sparse_sharded", "permute") and mesh is None:
            raise ValueError(f"backend {backend!r} needs a mesh (mesh=...)")
        if backend == "permute":
            k = mesh.shape[self.node_axis]
            if self.num_nodes != k:
                raise ValueError(
                    f"backend 'permute' needs num_nodes == |{self.node_axis}| "
                    f"({k}), got {self.num_nodes}"
                )
        if backend in ("sharded", "sparse_sharded"):
            shards = mesh.shape[self.node_axis]
            if self.num_nodes % shards:
                raise ValueError(
                    f"backend {backend!r}: num_nodes {self.num_nodes} not divisible "
                    f"by node shards {shards}"
                )
        if self.faults is not None and not _BACKEND_INFO[backend]["faults"]:
            capable = tuple(
                b for b, info in _BACKEND_INFO.items() if info["faults"]
            )
            raise ValueError(
                f"backend {backend!r} does not support faults; "
                f"fault-capable backends: {capable}"
            )

    # -- per-period state ----------------------------------------------------

    def refresh(self, round: int) -> bool:
        """Rebuild graph/W/CSR if ``round`` enters a new schedule period.
        Returns True when the mixing state changed."""
        period = self.schedule.period_of(round)
        if period == self._period:
            return False
        from repro.core import mixing, sparse

        g = self.schedule.graph_at(round)
        if self.matrix == "decavg":
            w = mixing.decavg_matrix(g, self.data_sizes)
        elif self.matrix == "uniform":
            w = mixing.uniform_neighbor_matrix(g)
        else:
            w = mixing.metropolis_hastings_matrix(g)
        if self.validate:
            mixing.validate_mixing(w, g)
        self._period = period
        self._graph = g
        self._w = jnp.asarray(w, jnp.float32)
        # Built from the edge list, not the dense W: the exact same
        # construction GossipEngine.program uses for its stacked periods, so
        # the loop and fused paths mix with bit-identical CSR values.
        self._csr = (
            sparse.csr_from_graph(g, self.data_sizes, matrix=self.matrix)
            if self.backend in ("sparse", "sparse_pallas", "sparse_sharded")
            else None
        )
        # Period-constant derived layouts, built lazily on first use.
        self._ell = None  # scalar ELL view of _csr
        self._bell = None  # blocked ELL view of _csr
        # The sharded-CSR view is staged here, outside any trace: the loop
        # path's jitted round reads it through sharded_csr(), and one built
        # lazily inside that trace would cache tracers past its end.
        self._shcsr = (
            sparse.shard_csr(self._csr, self.mesh.shape[self.node_axis])
            if self.backend == "sparse_sharded"
            else None
        )
        self._colors = (
            self._coloring_for(period, g) if self.backend == "permute" else None
        )
        return True

    def _coloring_for(self, period: int, graph) -> list:
        """Edge coloring for ``period``, cached — recoloring per schedule
        period is what lets ``permute`` track time-varying topologies."""
        colors = self._colors_cache.get(period)
        if colors is None:
            from repro.core import mixing

            colors = mixing.edge_coloring(graph)
            if len(self._colors_cache) >= 64:  # bound memory on long regen runs
                self._colors_cache.pop(next(iter(self._colors_cache)))
            self._colors_cache[period] = colors
        return colors

    @property
    def graph(self):
        return self._graph

    @property
    def w(self) -> jax.Array:
        """Dense (N, N) f32 mixing matrix for the current period."""
        return self._w

    @property
    def csr(self):
        from repro.core import sparse

        if self._csr is None:
            self._csr = sparse.csr_from_dense(np.asarray(self._w))
        return self._csr

    def w_at(self, round: int) -> jax.Array:
        self.refresh(round)
        return self._w

    def graph_at(self, round: int):
        self.refresh(round)
        return self._graph

    def is_gossip_round(self, round: int) -> bool:
        # gossip_every == 0 disables gossip entirely (isolated training),
        # matching the legacy launch/train.py falsy-flag semantics.
        if self.gossip_every < 1:
            return False
        return self.gossip_every == 1 or round % self.gossip_every == 0

    @property
    def fault_trace(self):
        """The engine's deterministic ``FaultTrace`` (requires ``faults=``).
        Lazy and cached: loop mixing, fused staging, and runner analytics
        all read the same per-round masks."""
        if self.faults is None:
            raise ValueError("engine has no fault schedule (faults=...)")
        if self._fault_trace is None:
            from repro.core import faults as faults_mod

            self._fault_trace = faults_mod.FaultTrace(
                self.faults, self.schedule, seed=self.seed
            )
        return self._fault_trace

    def sharded_csr(self, mesh: jax.sharding.Mesh | None = None):
        """Current period's ``ShardedCSR`` for the mesh's shard count
        (cached; rebuilt on a new period or a different shard count)."""
        from repro.core import sparse

        mesh = self.mesh if mesh is None else mesh
        shards = mesh.shape[self.node_axis]
        if self._shcsr is None or self._shcsr.shards != shards:
            self._shcsr = sparse.shard_csr(self.csr, shards)
        return self._shcsr

    def program(self, rounds: int, *, kind: str | None = None) -> MixingProgram:
        """Stage every schedule period of a ``rounds``-long run up front.

        Returns a ``MixingProgram`` — stacked per-period operators plus the
        round -> period map and the gossip cadence — for the fused
        single-``lax.scan`` training path. ``kind`` defaults to the backend's
        own kind for the sparse backends ("sparse", "sparse_pallas",
        "sparse_sharded") and "dense" otherwise.

        With ``faults=`` set, the program additionally stages the whole
        run's per-round alive and entry-keep masks (``f_alive``/``f_keep``,
        one more stacked axis) plus the static per-node staleness delays —
        a faulty multi-host run stays one compiled SPMD ``lax.scan``.

        The sparse kinds build each period's CSR straight from the
        schedule's graphs (``sparse.csr_from_graph``) — the dense (N, N)
        matrix is never materialized, so staging a T-period ``@rewire`` run
        is O(T * E) host memory, not O(T * N^2). The loop path's ``refresh``
        builds its CSR the same way, which is what keeps fused and loop runs
        bit-identical for the sparse backends. For the dense kind the
        engine's period state is walked and then restored to round 0, so an
        interleaved Python-loop run sees the same state it would have
        without this call.
        """
        prog = self._program_operators(rounds, kind=kind)
        if self.faults is None:
            return prog
        return self._attach_faults(prog, int(rounds))

    def _attach_faults(self, prog: MixingProgram, rounds: int) -> MixingProgram:
        """Stage the fault axis onto a built program: per-round alive masks
        and entry-keep masks in the program's own operator layout."""
        if prog.kind not in ("dense", "sparse", "sparse_sharded"):
            raise ValueError(
                f"program kind {prog.kind!r} does not support faults"
            )
        trace = self.fault_trace
        trace.ensure(rounds)
        f_alive = trace.alive_matrix(rounds)
        pid = np.asarray(prog.period_idx)
        if prog.kind == "dense":
            keep = np.stack([trace.dense_keep(r) for r in range(rounds)])
        elif prog.kind == "sparse":
            rows = np.asarray(prog.rows)
            cols = np.asarray(prog.cols)
            values = np.asarray(prog.values)
            keep = np.stack([
                trace.entry_keep(r, rows[pid[r]], cols[pid[r]], values[pid[r]])
                for r in range(rounds)
            ])
        else:  # sparse_sharded: per-shard layout with halo-local columns
            halo = np.asarray(prog.sh_halo)
            rows = np.asarray(prog.sh_rows)
            cols = np.asarray(prog.sh_cols)
            values = np.asarray(prog.sh_values)
            blk = prog.n // prog.shards
            offs = np.arange(prog.shards)[:, None] * blk
            keep = np.stack([
                trace.entry_keep(
                    r,
                    rows[pid[r]] + offs,  # local row -> global id
                    np.take_along_axis(halo[pid[r]], cols[pid[r]], axis=1),
                    values[pid[r]],
                )
                for r in range(rounds)
            ])
        return dataclasses.replace(
            prog,
            faulted=True,
            delay_max=trace.delay_max,
            f_alive=jnp.asarray(f_alive),
            f_keep=jnp.asarray(keep),
            f_delay=jnp.asarray(trace.delay),
        )

    def _program_operators(self, rounds: int, *, kind: str | None = None) -> MixingProgram:
        """The fault-free operator staging behind ``program`` (docs there)."""
        from repro.core import sparse

        rounds = int(rounds)
        if rounds < 1:
            raise ValueError(f"rounds must be >= 1, got {rounds}")
        sparse_kinds = ("sparse", "sparse_pallas", "sparse_sharded")
        if kind is None:
            kind = self.backend if self.backend in sparse_kinds else "dense"
        if kind not in ("dense",) + sparse_kinds:
            raise ValueError(
                f"program kind must be one of {('dense',) + sparse_kinds}, "
                f"got {kind!r}"
            )
        first_round: dict[int, int] = {}
        for r in range(rounds):
            first_round.setdefault(self.schedule.period_of(r), r)
        period_list = sorted(first_round)
        slot = {p: i for i, p in enumerate(period_list)}
        period_idx = np.array(
            [slot[self.schedule.period_of(r)] for r in range(rounds)], np.int32
        )
        gossip_mask = np.array([self.is_gossip_round(r) for r in range(rounds)], bool)
        cadence = (
            "never" if self.gossip_every < 1
            else "always" if self.gossip_every == 1
            else "mask"
        )
        common = dict(
            n=self.num_nodes,
            num_periods=len(period_list),
            cadence=cadence,
            period_idx=jnp.asarray(period_idx),
            gossip_mask=jnp.asarray(gossip_mask),
        )
        if kind == "dense":
            ws = [np.asarray(self.w_at(first_round[p])) for p in period_list]
            self.refresh(0)  # leave the engine where a fresh run expects it
            return MixingProgram(kind="dense", w=jnp.asarray(np.stack(ws)), **common)
        # Sparse kinds: per-period CSR straight from the graphs — no dense
        # (N, N) staging, no engine period churn (graph_at reads the
        # schedule's own period cache).
        csrs = [
            sparse.csr_from_graph(
                self.schedule.graph_at(first_round[p]), self.data_sizes,
                matrix=self.matrix,
            )
            for p in period_list
        ]
        if self.validate:
            for c in csrs:  # O(E) row-stochasticity check, no dense rebuild
                rs = np.bincount(
                    np.asarray(c.rows),
                    weights=np.asarray(c.values, np.float64),
                    minlength=self.num_nodes,
                )
                if not np.allclose(rs, 1.0, atol=1e-5):
                    raise ValueError("staged mixing rows must sum to 1")
        real_nnz = sum(c.nnz for c in csrs)
        e_max = max(c.nnz for c in csrs)
        p_chunk = self.sparse_p_chunk
        n = self.num_nodes
        if kind == "sparse_pallas":
            from repro.kernels import ops

            interp = (not ops.on_tpu()) if self.interpret is None else bool(self.interpret)
            bell_idx, bell_val = sparse.stack_block_ell(csrs)
            return MixingProgram(
                kind="sparse_pallas",
                bell_idx=jnp.asarray(bell_idx),
                bell_val=jnp.asarray(bell_val),
                interpret=interp,
                pad_ratio=bell_val.size / real_nnz,
                **common,
            )
        if kind == "sparse_sharded":
            mesh = self.mesh if self.mesh is not None else self._default_node_mesh()
            self.check("sparse_sharded", mesh)
            shards = mesh.shape[self.node_axis]
            st = sparse.stack_shard_csr([sparse.shard_csr(c, shards) for c in csrs])
            if p_chunk == "auto":
                # Per-device transient: size from the padded per-shard width.
                p_chunk = sparse.auto_p_chunk(int(st["values"].shape[2]))
            return MixingProgram(
                kind="sparse_sharded",
                sh_halo=jnp.asarray(st["halo"]),
                sh_rows=jnp.asarray(st["rows"]),
                sh_cols=jnp.asarray(st["cols"]),
                sh_values=jnp.asarray(st["values"]),
                sh_local_src=jnp.asarray(st["local_src"]),
                sh_local_dst=jnp.asarray(st["local_dst"]),
                sh_ring_send=tuple(jnp.asarray(a) for a in st["ring_send"]),
                sh_ring_recv=tuple(jnp.asarray(a) for a in st["ring_recv"]),
                mesh=mesh,
                node_axis=self.node_axis,
                shards=shards,
                halo_schedule=self.halo_schedule,
                p_chunk=None if p_chunk is None else int(p_chunk),
                pad_ratio=st["values"].size / real_nnz,
                **common,
            )
        if p_chunk == "auto":
            # Size from the padded entry count: the in-scan gather transient
            # is O(e_max * chunk) per leaf, same bound as the loop path's.
            p_chunk = sparse.auto_p_chunk(e_max)
        rows = np.full((len(csrs), e_max), n - 1, np.int32)
        cols = np.zeros((len(csrs), e_max), np.int32)
        values = np.zeros((len(csrs), e_max), np.float32)
        for t, c in enumerate(csrs):
            # Real entries first (rows sorted ascending), zero-weight padding
            # at row n-1 after them — segment ids stay sorted, sums are exact.
            rows[t, : c.nnz] = np.asarray(c.rows)
            cols[t, : c.nnz] = np.asarray(c.indices)
            values[t, : c.nnz] = np.asarray(c.values)
        return MixingProgram(
            kind="sparse",
            rows=jnp.asarray(rows),
            cols=jnp.asarray(cols),
            values=jnp.asarray(values),
            p_chunk=None if p_chunk is None else int(p_chunk),
            pad_ratio=(len(csrs) * e_max) / real_nnz,
            **common,
        )

    # -- mixing --------------------------------------------------------------

    def mix(
        self,
        params: PyTree,
        *,
        round: int | None = None,
        backend: str | None = None,
        spec: str | None = None,
    ) -> PyTree:
        """One communication round.

        With ``round`` given, the engine applies the cadence (identity
        rounds return ``params`` untouched — no identity matmul) and
        refreshes schedule state for that round. Without ``round``, the
        current-period matrix is applied unconditionally (callers that
        manage ``refresh`` themselves, e.g. the trainer's jitted closure,
        must not have their period reset here). ``backend`` (alias
        ``spec``) overrides the engine's backend for this call.

        With ``faults=`` set the engine runs the faulted round instead:
        renormalized mixing over surviving neighbors, straggler snapshots
        from an internal ring buffer (which assumes one ``mix`` call per
        round, in round order — the lm loop's contract), dead/empty rows
        passing through bit-unchanged. Freezing dead nodes' *training* is
        the trainer's job; the engine only governs gossip."""
        if self.faults is not None:
            if round is None:
                raise ValueError("faulted mixing needs round= (per-round masks)")
            return self._mix_faulted(params, round, backend or spec or self.backend)
        if round is not None:
            if not self.is_gossip_round(round):
                return params
            self.refresh(round)
        backend = backend or spec or self.backend
        mesh = self.mesh
        if backend != self.backend:
            if backend == "sparse_sharded" and mesh is None:
                # Local to this call: an override must not mutate the engine's
                # capability surface for later calls with other backends.
                mesh = self._default_node_mesh()
            self.check(backend, mesh)
        if backend == "dense":
            return mix_dense(self._w, params)
        if backend == "pallas":
            return mix_pallas(self._w, params, interpret=self.interpret)
        if backend == "sparse":
            from repro.core import sparse

            p_chunk = self.sparse_p_chunk
            if p_chunk == "auto":
                p_chunk = sparse.auto_p_chunk(self.csr.nnz)
            return sparse.mix_sparse(self.csr, params, p_chunk=p_chunk)
        if backend == "sparse_pallas":
            from repro.core import sparse
            from repro.kernels import ops

            interp = (not ops.on_tpu()) if self.interpret is None else self.interpret
            if interp:  # scalar row-gather fallback kernel under interpret
                if self._ell is None:  # period-constant; avoids per-call rebuild
                    self._ell = sparse.ell_from_csr(self.csr)
                return sparse.mix_sparse_pallas(
                    self.csr, params, ell=self._ell, interpret=True, blocked=False
                )
            if self._bell is None:
                self._bell = sparse.block_ell_from_csr(self.csr)
            return sparse.mix_sparse_pallas(
                self.csr, params, bell=self._bell, interpret=False, blocked=True
            )
        if backend == "sharded":
            return mix_sharded(
                self._w, params, mesh=mesh, node_axis=self.node_axis,
                schedule=self.sharded_schedule,
            )
        if backend == "sparse_sharded":
            from repro.core import sparse

            self.sharded_csr(mesh)
            p_chunk = self.sparse_p_chunk
            if p_chunk == "auto":
                # Size from the per-shard entry count: the gather transient
                # is O(nnz_s * chunk) per device, not O(nnz * chunk).
                p_chunk = sparse.auto_p_chunk(int(self._shcsr.values.shape[1]))
            return mix_sharded_sparse(
                self._shcsr, params, mesh=mesh, node_axis=self.node_axis,
                p_chunk=p_chunk, halo_schedule=self.halo_schedule,
            )
        if backend == "permute":
            if self._colors is None:
                self._colors = self._coloring_for(self._period, self._graph)
            return mix_permute(
                self._w, params, self._colors, mesh=mesh,
                node_axis=self.node_axis,
            )
        raise ValueError(f"unknown backend {backend!r}")

    def _mix_faulted(self, params: PyTree, round: int, backend: str) -> PyTree:
        """One faulted loop-path round (see ``mix``)."""
        from repro.core import faults as faults_mod

        self.check(backend, self.mesh)
        self.refresh(round)
        trace = self.fault_trace
        # Push into the straggler ring buffer BEFORE the cadence gate: a
        # straggler's history advances whether or not this round gossips.
        pub = None
        if trace.delay_max > 0:
            if self._fault_hist is None:
                self._fault_hist = faults_mod.init_history(
                    params, trace.delay_max + 1
                )
            pub, self._fault_hist = faults_mod.push_and_publish(
                params, self._fault_hist, jnp.int32(round),
                jnp.asarray(trace.delay),
            )
        if not self.is_gossip_round(round):
            return params
        alive = jnp.asarray(trace.alive(round))
        if backend == "dense":
            keep = jnp.asarray(trace.dense_keep(round))
            return faults_mod.mix_faulted_dense(
                self._w, keep, alive, params, pub
            )
        if backend == "sparse":
            csr = self.csr
            keep = jnp.asarray(trace.entry_keep(
                round, np.asarray(csr.rows), np.asarray(csr.indices),
                np.asarray(csr.values),
            ))
            return faults_mod.mix_faulted_csr(
                csr.rows, csr.indices, csr.values, keep, alive,
                self.num_nodes, params, pub,
            )
        if backend == "sparse_sharded":
            shcsr = self.sharded_csr()
            blk = shcsr.rows_per_shard
            rows_g = np.asarray(shcsr.rows) + np.arange(shcsr.shards)[:, None] * blk
            cols_g = np.take_along_axis(
                np.asarray(shcsr.halo), np.asarray(shcsr.cols), axis=1
            )
            keep = jnp.asarray(trace.entry_keep(
                round, rows_g, cols_g, np.asarray(shcsr.values)
            ))
            return mix_sharded_sparse_faulted(
                shcsr, params, params if pub is None else pub, keep, alive,
                mesh=self.mesh, node_axis=self.node_axis,
                halo_schedule=self.halo_schedule,
            )
        raise ValueError(f"backend {backend!r} does not support faults")

    def __repr__(self) -> str:
        return (
            f"GossipEngine(n={self.num_nodes}, backend={self.backend}, "
            f"matrix={self.matrix}, gossip_every={self.gossip_every}, "
            f"topology={self.schedule!r})"
        )


def gossip_error(params: PyTree) -> jax.Array:
    """Consensus distance: mean over leaves of ||w_i - mean_i w_i||^2 / ||mean||^2.

    The quantity the spectral gap contracts per round; benchmarks report it to
    connect topology properties to knowledge-spread speed.
    """
    def leaf_err(leaf: jax.Array) -> jax.Array:
        f = leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)
        mean = f.mean(axis=0, keepdims=True)
        num = jnp.sum((f - mean) ** 2)
        den = jnp.sum(mean**2) * f.shape[0] + 1e-12
        return num / den

    errs = [leaf_err(l) for l in jax.tree.leaves(params)]
    return jnp.mean(jnp.stack(errs))
