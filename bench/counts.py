"""Operations and bytes a round requires, computed from shapes.

Lower bounds: no honest change to the program can do a round's work with
fewer, so a share of the chip's peak built on them cannot pass 100%.
"""

from __future__ import annotations

import numpy as np


def mlp_params(dims) -> int:
    """Weights and biases of an MLP with layer widths ``dims``."""
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))


def nnz(adj: np.ndarray) -> int:
    """Entries of the Eq. 1 mixing matrix: both directions of every edge,
    plus one self-weight per node."""
    return int(np.asarray(adj, bool).sum()) + adj.shape[0]


def halo_rows(adj: np.ndarray, shards: int) -> tuple[int, int]:
    """(halo, remote) rows of the shard with the widest halo.

    Nodes are split over ``shards`` in contiguous blocks. A shard's halo is
    every node in the closed neighbourhood of its own nodes; the remote
    rows are those owned by another shard, which the exchange brings in.
    """
    adj = np.asarray(adj, bool)
    n = adj.shape[0]
    if n % shards:
        raise ValueError(f"{n} nodes do not split over {shards} shards")
    blk = n // shards
    best = (-1, 0)
    for s in range(shards):
        lo, hi = s * blk, (s + 1) * blk
        need = adj[lo:hi].any(axis=0)
        need[lo:hi] = True  # self-weights
        halo = int(need.sum())
        remote = halo - blk
        if halo > best[0]:
            best = (halo, remote)
    return best
