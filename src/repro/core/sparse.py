"""Sparse (CSR) gossip mixing — the large-N DecAvg path.

A gossip matrix W over a sparse collaboration graph has nnz = 2E + N entries
(neighbors + self loops), while the dense representation is N^2 floats: at
N=4096 on BA(m=2) that is 64 MB of dense W vs ~230 KB of CSR, and a per-round
cost of O(E*P) instead of O(N^2*P). This module stores W as (indptr, indices,
values) plus the precomputed COO row ids, and applies one DecAvg round as a
row-gather + segment-sum:

    out[i] = sum_{e : rows[e] == i} values[e] * P[indices[e]]

Two execution paths, numerically allclose to ``decavg.mix_dense``:

1. ``mix_sparse``         — XLA gather + ``jax.ops.segment_sum`` (sorted
                            segments), f32 accumulation. Default everywhere.
2. ``mix_sparse_pallas``  — ELL-padded Pallas row-gather kernel
                            (kernels/sparse_gossip.py) driven by scalar
                            prefetch; validated in interpret mode on CPU.

The transient gather buffer is O(nnz * P_leaf); for sparse graphs nnz ~ c*N,
so memory stays linear in N (dense mixing materializes the same O(N * P_leaf)
output anyway).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "CSR",
    "ShardedCSR",
    "BlockELL",
    "csr_from_dense",
    "csr_from_graph",
    "csr_to_dense",
    "ell_from_csr",
    "block_ell_from_csr",
    "stack_block_ell",
    "shard_csr",
    "stack_shard_csr",
    "halo_wire_bytes",
    "mix_sparse",
    "mix_sparse_pallas",
    "auto_p_chunk",
]

PyTree = Any


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("indptr", "indices", "rows", "values"),
    meta_fields=("shape",),
)
@dataclasses.dataclass(frozen=True)
class CSR:
    """Row-compressed sparse matrix with precomputed COO row ids.

    Attributes:
      indptr:  (N+1,) int32 — row e spans entries indptr[i]:indptr[i+1].
      indices: (nnz,) int32 — column (source node) of each entry.
      rows:    (nnz,) int32 — row (destination node) of each entry, sorted
               ascending (derivable from indptr; kept so segment_sum needs no
               host round-trip inside jit).
      values:  (nnz,) float32 — W entries.
      shape:   (N, N) static.
    """

    indptr: jax.Array
    indices: jax.Array
    rows: jax.Array
    values: jax.Array
    shape: tuple[int, int]

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    @property
    def nbytes(self) -> int:
        """Bytes of the W representation (the O(E) vs O(N^2) claim)."""
        return sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for a in (self.indptr, self.indices, self.rows, self.values)
        )

    @property
    def max_row_nnz(self) -> int:
        ptr = np.asarray(self.indptr)
        return int((ptr[1:] - ptr[:-1]).max()) if self.shape[0] else 0


def csr_from_dense(w: np.ndarray | jax.Array, *, tol: float = 0.0) -> CSR:
    """Compress a dense (N, N) mixing matrix; entries with |w| <= tol drop."""
    wd = np.asarray(w, dtype=np.float32)
    if wd.ndim != 2 or wd.shape[0] != wd.shape[1]:
        raise ValueError(f"mixing matrix must be square, got {wd.shape}")
    mask = np.abs(wd) > tol
    rows, cols = np.nonzero(mask)  # row-major order -> rows sorted ascending
    counts = mask.sum(axis=1)
    indptr = np.zeros(wd.shape[0] + 1, dtype=np.int32)
    np.cumsum(counts, out=indptr[1:])
    return CSR(
        indptr=jnp.asarray(indptr),
        indices=jnp.asarray(cols.astype(np.int32)),
        rows=jnp.asarray(rows.astype(np.int32)),
        values=jnp.asarray(wd[rows, cols]),
        shape=wd.shape,
    )


def csr_from_graph(
    g,
    data_sizes: np.ndarray | None = None,
    *,
    matrix: str = "decavg",
    self_trust: float = 1.0,
) -> CSR:
    """Build the mixing-matrix CSR straight from a graph's edge list.

    Equivalent (same support, values allclose at f32) to
    ``csr_from_dense(mixing.decavg_matrix(g, sizes))`` et al., but never
    materializes the dense (N, N) float matrix: the only transient is the
    O(E) entry list plus a boolean adjacency view. This is what lets
    ``GossipEngine.program`` stage every ``@rewire`` period of an N=4096 run
    without O(T * N^2) host memory.

    ``matrix``: "decavg" (paper Eq. 1 — weights omega * |D_j|, row-
    normalized; isolated zero-data rows keep their own model), "uniform"
    (closed-neighborhood mean) or "mh" (Metropolis-Hastings). Exact zeros
    (zero-size sources, zero MH diagonals) are dropped, matching
    ``csr_from_dense``'s support. Entries come out row-major sorted.
    """
    n = g.num_nodes
    if matrix == "mh":
        deg = g.adj.sum(axis=1).astype(np.float64)
        rr, cc = np.nonzero(g.adj)  # off-diagonal edges, no self loops
        off = 1.0 / (1.0 + np.maximum(deg[rr], deg[cc]))
        diag = 1.0 - np.bincount(rr, weights=off, minlength=n)
        rows = np.concatenate([rr, np.arange(n)])
        cols = np.concatenate([cc, np.arange(n)])
        vals = np.concatenate([off, diag])
    else:
        closed = g.adj.copy()
        np.fill_diagonal(closed, True)
        rows, cols = np.nonzero(closed)  # row-major: rows sorted ascending
        if matrix == "uniform":
            inv = 1.0 / np.bincount(rows, minlength=n).astype(np.float64)
            vals = inv[rows]
        elif matrix == "decavg":
            sizes = (
                np.ones(n) if data_sizes is None
                else np.asarray(data_sizes, dtype=np.float64)
            )
            if sizes.shape != (n,):
                raise ValueError(f"data_sizes must be ({n},), got {sizes.shape}")
            omega = np.where(rows == cols, float(self_trust), 1.0)
            vals = omega * sizes[cols]
            rowsum = np.bincount(rows, weights=vals, minlength=n)
            bad = rowsum == 0
            if bad.any():
                # Isolated node with zero data: keep its own model unchanged.
                vals = np.where(
                    bad[rows], np.where(rows == cols, 1.0, 0.0), vals
                )
                rowsum = np.where(bad, 1.0, rowsum)
            vals = vals / rowsum[rows]
        else:
            raise ValueError(
                f"matrix must be 'decavg', 'uniform' or 'mh', got {matrix!r}"
            )
    keep = vals != 0.0  # match csr_from_dense's |w| > 0 support
    rows, cols, vals = rows[keep], cols[keep], vals[keep]
    order = np.lexsort((cols, rows))  # mh appends the diagonal out of order
    rows = rows[order].astype(np.int32)
    cols = cols[order].astype(np.int32)
    vals = vals[order].astype(np.float32)
    indptr = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return CSR(
        indptr=jnp.asarray(indptr),
        indices=jnp.asarray(cols),
        rows=jnp.asarray(rows),
        values=jnp.asarray(vals),
        shape=(n, n),
    )


def csr_to_dense(csr: CSR) -> np.ndarray:
    out = np.zeros(csr.shape, dtype=np.float32)
    out[np.asarray(csr.rows), np.asarray(csr.indices)] = np.asarray(csr.values)
    return out


def ell_from_csr(csr: CSR) -> tuple[np.ndarray, np.ndarray]:
    """ELL padding for the Pallas kernel: (N, K) column indices + values,
    K = max row nnz. Padding entries point at column 0 with weight 0."""
    n = csr.shape[0]
    k = max(csr.max_row_nnz, 1)
    idx = np.zeros((n, k), dtype=np.int32)
    val = np.zeros((n, k), dtype=np.float32)
    ptr = np.asarray(csr.indptr)
    cols = np.asarray(csr.indices)
    vals = np.asarray(csr.values)
    for i in range(n):
        lo, hi = int(ptr[i]), int(ptr[i + 1])
        idx[i, : hi - lo] = cols[lo:hi]
        val[i, : hi - lo] = vals[lo:hi]
    return idx, val


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=(
        "halo", "rows", "cols", "values",
        "local_src", "local_dst", "ring_send", "ring_recv",
    ),
    meta_fields=("shape", "shards", "rows_per_shard"),
)
@dataclasses.dataclass(frozen=True)
class ShardedCSR:
    """CSR with the node (row) axis split into ``shards`` contiguous ranges.

    Shard ``s`` owns destination rows ``[s*rows_per_shard, (s+1)*rows_per_shard)``
    and stores its W entries with *halo-local* column ids: ``halo[s]`` lists
    the global source nodes shard ``s`` needs (its own rows plus cross-shard
    neighbors), and ``cols`` indexes into that halo list. One sharded DecAvg
    round (decavg.mix_sharded_sparse) assembles the shard's halo rows of P
    into an (H, p) buffer and runs an O(nnz_s * P) segment-sum per shard.

    Two halo assembly schedules are supported by the same layout:

    - allgather: gather the full node axis once, slice ``halo[s]`` rows.
    - ring: S-1 ``ppermute`` steps; at step d every shard sends exactly the
      rows shard ``(s+d) % S`` needs from it (``ring_send[d-1]``) and places
      what it receives from shard ``(s-d) % S`` at the matching halo slots
      (``ring_recv[d-1]``); its own rows are copied locally via
      ``local_src``/``local_dst``. Per-device wire drops from O(N*P) to
      O(H*P). Steps in which no shard pair exchanges anything have zero-width
      index arrays and are skipped entirely at trace time.

    All per-shard arrays are stacked on a leading shard axis and zero-padded
    to the max shard size so the same SPMD program runs on every device:
    padded entries carry weight 0 and point at halo slot 0 / the shard's last
    local row, so they contribute nothing while keeping segment ids sorted.
    Padded ring/local *destination* slots point at the scratch slot H (one
    past the halo), which the mixing kernel discards.

    Attributes:
      halo:   (S, H) int32 — global source node ids needed by shard s
              (sorted ascending per shard; padded by repeating id 0).
      rows:   (S, E) int32 — destination row LOCAL to the shard, sorted
              ascending (padded with rows_per_shard - 1).
      cols:   (S, E) int32 — index into ``halo[s]`` (padded with 0).
      values: (S, E) float32 — W entries (padded with 0).
      local_src: (S, L) int32 — shard-local rows copied into the halo buffer
              without communication (padded with 0).
      local_dst: (S, L) int32 — halo slots for ``local_src`` (padded with H).
      ring_send: tuple of (S, K_d) int32, one per ring step d=1..S-1 — rows
              LOCAL to the sending shard, packed in the receiver's halo
              order (padded with 0; sent but discarded by the receiver).
      ring_recv: tuple of (S, K_d) int32 — halo slots where the rows received
              at step d land (padded with the scratch slot H).
      shape:  (N, N) static; shards, rows_per_shard: static ints.
    """

    halo: jax.Array
    rows: jax.Array
    cols: jax.Array
    values: jax.Array
    local_src: jax.Array
    local_dst: jax.Array
    ring_send: tuple[jax.Array, ...]
    ring_recv: tuple[jax.Array, ...]
    shape: tuple[int, int]
    shards: int
    rows_per_shard: int

    @property
    def halo_width(self) -> int:
        """Max rows of P any shard gathers (the halo buffer height)."""
        return int(self.halo.shape[1])

    @property
    def ring_width(self) -> int:
        """Rows of P one device receives per round under the ring schedule
        (sum of padded per-step widths — the O(H) wire bound)."""
        return sum(int(a.shape[1]) for a in self.ring_send)

    @property
    def nbytes(self) -> int:
        return sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for a in (
                self.halo, self.rows, self.cols, self.values,
                self.local_src, self.local_dst, *self.ring_send, *self.ring_recv,
            )
        )


def shard_csr(csr: CSR, shards: int) -> ShardedCSR:
    """Split a CSR mixing matrix into per-shard row ranges with halo columns.

    Requires N divisible by ``shards`` (same contract as the dense sharded
    backend). Pure host-side preprocessing, done once per schedule period.
    Besides the per-shard CSR entries, this derives the peer metadata for the
    ring halo exchange: which shard owns each halo row, which local rows each
    shard must send at every ring step, and the halo slot each received row
    lands in (see ShardedCSR).
    """
    n = csr.shape[0]
    if shards < 1 or n % shards:
        raise ValueError(f"num_nodes {n} not divisible by shards {shards}")
    blk = n // shards
    ptr = np.asarray(csr.indptr)
    cols = np.asarray(csr.indices)
    vals = np.asarray(csr.values)
    coo_rows = np.asarray(csr.rows)

    halos: list[np.ndarray] = []
    loc_rows: list[np.ndarray] = []
    loc_cols: list[np.ndarray] = []
    loc_vals: list[np.ndarray] = []
    for s in range(shards):
        lo, hi = int(ptr[s * blk]), int(ptr[(s + 1) * blk])
        c = cols[lo:hi]
        need = np.unique(c)  # sorted global sources for this shard (the halo)
        if need.size == 0:
            need = np.zeros(1, dtype=np.int32)
        halos.append(need.astype(np.int32))
        loc_rows.append((coo_rows[lo:hi] - s * blk).astype(np.int32))
        loc_cols.append(np.searchsorted(need, c).astype(np.int32))
        loc_vals.append(vals[lo:hi].astype(np.float32))

    h_max = max(h.size for h in halos)
    e_max = max(max(r.size for r in loc_rows), 1)
    halo = np.zeros((shards, h_max), dtype=np.int32)
    rows = np.full((shards, e_max), blk - 1, dtype=np.int32)
    lcols = np.zeros((shards, e_max), dtype=np.int32)
    lvals = np.zeros((shards, e_max), dtype=np.float32)
    for s in range(shards):
        halo[s, : halos[s].size] = halos[s]
        k = loc_rows[s].size
        rows[s, :k] = loc_rows[s]
        lcols[s, :k] = loc_cols[s]
        lvals[s, :k] = loc_vals[s]

    # Ring peer metadata. Each halo row of shard s is owned by shard
    # owner = id // blk; at ring step d shard s receives exactly its halo
    # rows owned by (s - d) % shards, packed in halo order, while sending the
    # rows (s + d) % shards needs from it in *that* receiver's halo order —
    # sender packing and receiver slots line up by construction.
    scratch = h_max  # one-past-the-halo slot; padded writes land here
    loc_src = [np.flatnonzero(halos[s] // blk == s) for s in range(shards)]
    l_max = max(max((p.size for p in loc_src), default=0), 1)
    local_src = np.zeros((shards, l_max), dtype=np.int32)
    local_dst = np.full((shards, l_max), scratch, dtype=np.int32)
    for s in range(shards):
        p = loc_src[s]
        local_src[s, : p.size] = halos[s][p] - s * blk
        local_dst[s, : p.size] = p

    ring_send: list[jax.Array] = []
    ring_recv: list[jax.Array] = []
    for d in range(1, shards):
        # recv_pos[r]: positions in halos[r] owned by o = (r - d) % shards.
        recv_pos = [
            np.flatnonzero(halos[r] // blk == (r - d) % shards)
            for r in range(shards)
        ]
        k_d = max(p.size for p in recv_pos)
        send = np.zeros((shards, k_d), dtype=np.int32)
        recv = np.full((shards, k_d), scratch, dtype=np.int32)
        for r in range(shards):
            o = (r - d) % shards
            p = recv_pos[r]
            send[o, : p.size] = halos[r][p] - o * blk
            recv[r, : p.size] = p
        ring_send.append(jnp.asarray(send))
        ring_recv.append(jnp.asarray(recv))

    return ShardedCSR(
        halo=jnp.asarray(halo),
        rows=jnp.asarray(rows),
        cols=jnp.asarray(lcols),
        values=jnp.asarray(lvals),
        local_src=jnp.asarray(local_src),
        local_dst=jnp.asarray(local_dst),
        ring_send=tuple(ring_send),
        ring_recv=tuple(ring_recv),
        shape=csr.shape,
        shards=shards,
        rows_per_shard=blk,
    )


def stack_shard_csr(shcsrs: list[ShardedCSR]) -> dict[str, Any]:
    """Pad per-period ShardedCSRs to common widths and stack on a period axis.

    The fused sharded scan body selects the current period by index, so every
    period's layout must share one shape: halo padded to the max halo width
    (repeating id 0 — extra gathered rows are simply never referenced), CSR
    entries padded with zero-weight rows at the shard's last local row (after
    the sorted real entries, so segment ids stay sorted), and ring/local
    tables padded per step to the max step width. Ring steps keep their
    per-period zero-width collapse only when the width is zero across *all*
    periods (shapes are shared), which also keeps ``ring_width`` — and hence
    the ``halo_schedule="auto"`` decision — common to the whole program.

    Padded local/ring *destination* slots point at the scratch slot; because
    the halo widens to ``h_max``, each period's own scratch slot
    (``halo_width_t``) is remapped to the stacked scratch ``h_max`` so padded
    writes keep landing one past the halo.

    Returns a dict of stacked arrays: halo/rows/cols/values/local_src/
    local_dst with leading (T, S, ...) axes and ring_send/ring_recv as tuples
    of (T, S, K_d) arrays, mirroring the ShardedCSR fields.
    """
    s0 = shcsrs[0]
    if any(s.shards != s0.shards or s.shape != s0.shape for s in shcsrs):
        raise ValueError("all periods must share shape and shard count")
    h_max = max(s.halo_width for s in shcsrs)
    e_max = max(int(s.rows.shape[1]) for s in shcsrs)
    l_max = max(int(s.local_src.shape[1]) for s in shcsrs)
    steps = s0.shards - 1
    k_max = [max(int(s.ring_send[d].shape[1]) for s in shcsrs) for d in range(steps)]

    def pad(a: jax.Array, width: int, fill) -> np.ndarray:
        a = np.asarray(a)
        return np.pad(a, ((0, 0), (0, width - a.shape[1])), constant_values=fill)

    def remap_scratch(a: jax.Array, s: ShardedCSR) -> np.ndarray:
        # Destination slots: the period's own scratch (== halo_width_t) must
        # follow the halo as it widens to h_max; real slots are < halo_width_t
        # and stay put.
        a = np.asarray(a)
        return np.where(a == s.halo_width, h_max, a).astype(a.dtype)

    return {
        "halo": np.stack([pad(s.halo, h_max, 0) for s in shcsrs]),
        "rows": np.stack(
            [pad(s.rows, e_max, s0.rows_per_shard - 1) for s in shcsrs]
        ),
        "cols": np.stack([pad(s.cols, e_max, 0) for s in shcsrs]),
        "values": np.stack([pad(s.values, e_max, 0.0) for s in shcsrs]),
        "local_src": np.stack([pad(s.local_src, l_max, 0) for s in shcsrs]),
        "local_dst": np.stack(
            [pad(remap_scratch(s.local_dst, s), l_max, h_max) for s in shcsrs]
        ),
        "ring_send": tuple(
            np.stack([pad(s.ring_send[d], k_max[d], 0) for s in shcsrs])
            for d in range(steps)
        ),
        "ring_recv": tuple(
            np.stack(
                [pad(remap_scratch(s.ring_recv[d], s), k_max[d], h_max)
                 for s in shcsrs]
            )
            for d in range(steps)
        ),
    }


def halo_wire_bytes(shcsr: ShardedCSR, p: int, *, itemsize: int = 4) -> dict[str, int]:
    """Modeled per-device *receive* volume of one mixing round, per schedule.

    allgather moves the (S-1)/S complement of the full node axis onto every
    device; the ring moves only the padded per-step halo rows (``ring_width``,
    O(H)). Both count payload bytes of P rows at ``p`` features — layout
    metadata (a few KB of int32, round-constant) is excluded.
    """
    n = shcsr.shape[0]
    return {
        "allgather": (n - shcsr.rows_per_shard) * p * itemsize,
        "ring": shcsr.ring_width * p * itemsize,
    }


@dataclasses.dataclass(frozen=True)
class BlockELL:
    """8-row-blocked ELL layout for the TPU sparse gossip kernel.

    Rows are grouped into blocks of ``block`` (the f32 sublane count); for
    each destination block the distinct *source blocks* touched by any of its
    rows are enumerated, and the weights coupling the two blocks are stored
    as a dense (block, block) tile. Each tile is applied as one aligned DMA
    of the source block's P rows plus a (block, block) @ (block, bd)
    mini-matmul — real sublane packing instead of the scalar kernel's
    (1, bd) row-at-a-time gathers.

    Attributes:
      idx: (NB, KB) int32 — source block ids per destination block, padded
           with 0 (their weight tiles are all-zero).
      val: (NB*block, KB*block) f32 — ``val[r, t*block + o]`` is the weight
           of global row r against row ``idx[r//block, t]*block + o``. KB is
           padded to a multiple of the kernel's tiles per grid step (16), so
           each step reads one full (8, 128) weight block.
      n:   unpadded row count; block: rows per block.
    """

    idx: np.ndarray
    val: np.ndarray
    n: int
    block: int = 8

    @property
    def num_blocks(self) -> int:
        return int(self.idx.shape[0])

    @property
    def max_blocks_per_row(self) -> int:
        return int(self.idx.shape[1])


def block_ell_from_csr(csr: CSR, *, block: int = 8) -> BlockELL:
    """Build the 8-row-blocked ELL layout (see BlockELL) from a CSR matrix.

    The per-block source count is rounded up to a multiple of the kernel's
    ``TILES_PER_STEP``, so the stacked weight tiles' trailing dim is a
    multiple of its (8, 128) weight block.
    """
    from repro.kernels.sparse_gossip import TILES_PER_STEP

    n = csr.shape[0]
    nb = -(-n // block)
    ptr = np.asarray(csr.indptr)
    cols = np.asarray(csr.indices)
    vals = np.asarray(csr.values)

    slots: list[dict[int, int]] = []
    entries: list[list[tuple[int, int, float]]] = []  # (row, val-col, value)
    for b in range(nb):
        slot: dict[int, int] = {}
        ent: list[tuple[int, int, float]] = []
        for r in range(b * block, min((b + 1) * block, n)):
            for e in range(int(ptr[r]), int(ptr[r + 1])):
                sb, off = divmod(int(cols[e]), block)
                t = slot.setdefault(sb, len(slot))
                ent.append((r, t * block + off, float(vals[e])))
        slots.append(slot)
        entries.append(ent)

    kb = max(max((len(s) for s in slots), default=0), 1)
    kb = -(-kb // TILES_PER_STEP) * TILES_PER_STEP
    idx = np.zeros((nb, kb), dtype=np.int32)
    val = np.zeros((nb * block, kb * block), dtype=np.float32)
    for b, (slot, ent) in enumerate(zip(slots, entries)):
        for sb, t in slot.items():
            idx[b, t] = sb
        for r, c, v in ent:
            val[r, c] = v
    return BlockELL(idx=idx, val=val, n=n, block=block)


def stack_block_ell(
    csrs: list[CSR], *, block: int = 8
) -> tuple[np.ndarray, np.ndarray]:
    """Blocked-ELL layouts for every schedule period, padded to a common
    block count and stacked on a leading period axis.

    Periods with fewer source blocks per destination block are padded with
    index-0 tiles whose weights are all zero (the kernel multiplies them in
    as exact zeros, same convention as ``block_ell_from_csr``'s own lane
    padding). Returns ``idx`` (T, NB, KB) int32 and ``val``
    (T, NB*block, KB*block) f32 for the fused scan body to index by period.
    """
    if not csrs:
        raise ValueError("need at least one period")
    if any(c.shape != csrs[0].shape for c in csrs):
        raise ValueError("all periods must share the matrix shape")
    bells = [block_ell_from_csr(c, block=block) for c in csrs]
    kb = max(b.max_blocks_per_row for b in bells)  # lane-aligned per period
    idx = np.stack(
        [np.pad(b.idx, ((0, 0), (0, kb - b.idx.shape[1]))) for b in bells]
    )
    val = np.stack(
        [np.pad(b.val, ((0, 0), (0, (kb - b.idx.shape[1]) * block))) for b in bells]
    )
    return idx, val


def _gather_segment_sum(csr: CSR, flat: jax.Array) -> jax.Array:
    gathered = flat[csr.indices] * csr.values[:, None]  # (nnz, p)
    return jax.ops.segment_sum(
        gathered, csr.rows, num_segments=csr.shape[0], indices_are_sorted=True
    )


def _mix_sparse_leaf(csr: CSR, leaf: jax.Array, p_chunk: int | None = None) -> jax.Array:
    n = csr.shape[0]
    if leaf.shape[0] != n:
        raise ValueError(f"leaf leading axis {leaf.shape[0]} != num_nodes {n}")
    flat = leaf.reshape(n, -1).astype(jnp.float32)
    p = flat.shape[1]
    if p_chunk is not None and p_chunk < p:
        # Chunk the feature axis so the transient gather buffer is
        # O(nnz * p_chunk) instead of O(nnz * P) — at N=4096 / BA(m=2) a
        # P=2^20 leaf would otherwise materialize a ~65 GB intermediate.
        # lax.map serializes the chunks, bounding peak memory.
        pad = (-p) % p_chunk
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        chunks = flat.reshape(n, -1, p_chunk).transpose(1, 0, 2)  # (k, n, pc)
        out = jax.lax.map(functools.partial(_gather_segment_sum, csr), chunks)
        out = out.transpose(1, 0, 2).reshape(n, -1)[:, :p]
    else:
        out = _gather_segment_sum(csr, flat)
    return out.reshape(leaf.shape).astype(leaf.dtype)


@functools.partial(jax.jit, static_argnames=("p_chunk",))
def mix_sparse(csr: CSR, params: PyTree, *, p_chunk: int | None = None) -> PyTree:
    """One DecAvg round ``P <- W @ P`` with W in CSR, O(E*P) work.

    ``p_chunk`` bounds the transient gather buffer to O(nnz * p_chunk) per
    leaf (serialized chunks over the feature axis) — use for very large
    per-leaf P at large N. Default None preserves the single-gather path.
    """
    return jax.tree.map(functools.partial(_mix_sparse_leaf, csr, p_chunk=p_chunk), params)


def auto_p_chunk(nnz: int, budget_elems: int = 1 << 22) -> int:
    """Feature-axis chunk size keeping the gather buffer under ``budget_elems``
    f32 elements (default 4M ~= 16 MiB)."""
    return max(64, budget_elems // max(nnz, 1))


def mix_sparse_pallas(
    csr: CSR,
    params: PyTree,
    *,
    ell: tuple[np.ndarray, np.ndarray] | None = None,
    bell: BlockELL | None = None,
    interpret: bool | None = None,
    blocked: bool | None = None,
) -> PyTree:
    """Sparse DecAvg round via the Pallas ELL kernels.

    Two kernels (kernels/sparse_gossip.py), selected by ``blocked``:

    - blocked (default on real TPU): 8-row-blocked ELL — sublane-packed
      (8, bd) source-block DMAs + (8, 8) weight-tile mini-matmuls, 16 tiles
      per grid step.
    - scalar (default under interpret, i.e. off-TPU): the per-row (1, bd)
      gather kernel; far fewer grid steps through the slow interpreter.

    ``ell`` / ``bell`` let callers that mix repeatedly with the same W
    (GossipEngine) pass a precomputed layout instead of paying the host-side
    padding loop per call.
    """
    from repro.kernels import ops  # local import: kernels are optional at import time

    if interpret is None:
        interpret = not ops.on_tpu()
    if blocked is None:
        blocked = not interpret  # scalar fallback kernel under interpret

    n = csr.shape[0]
    if blocked:
        b = block_ell_from_csr(csr) if bell is None else bell
        idx_j, val_j = jnp.asarray(b.idx), jnp.asarray(b.val)

        def mix(leaf: jax.Array) -> jax.Array:
            flat = leaf.reshape(n, -1)
            out = ops.gossip_mix_sparse_blocked(idx_j, val_j, flat, interpret=interpret)
            return out.reshape(leaf.shape).astype(leaf.dtype)

    else:
        idx, val = ell_from_csr(csr) if ell is None else ell
        idx_j, val_j = jnp.asarray(idx), jnp.asarray(val)

        def mix(leaf: jax.Array) -> jax.Array:
            flat = leaf.reshape(n, -1)
            out = ops.gossip_mix_sparse(idx_j, val_j, flat, interpret=interpret)
            return out.reshape(leaf.shape).astype(leaf.dtype)

    return jax.tree.map(mix, params)
