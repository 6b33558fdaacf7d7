"""The operation and byte counters give the hand-worked numbers."""

import numpy as np

import inputs
from counts import halo_rows, mlp_params, nnz
from repro.core import sparse, topology


def test_paper_mlp_params():
    # 784*512+512 + 512*256+256 + 256*128+128 + 128*10+10
    assert mlp_params([784, 512, 256, 128, 10]) == 567_434


def test_nnz_ba100():
    # BA(m=2) has 2 + 2*(n-3) edges; both directions plus n self-weights.
    adj = inputs.barabasi_albert(100, 2, seed=0)
    assert nnz(adj) == 2 * (2 + 2 * 97) + 100 == 492


def test_widest_halo_ba2048_over_4():
    adj = inputs.barabasi_albert(2048, 2, seed=0)
    assert halo_rows(adj, 4) == (1867, 1355)
    # The program's own sharding of the Eq. 1 CSR agrees on the halo width.
    csr = sparse.csr_from_graph(topology.Graph(adj=adj), np.ones(2048))
    assert sparse.shard_csr(csr, 4).halo.shape[1] == 1867
