"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

Each test lowers one kernel with ``interpret=False`` for a described (not
attached) ``v5e:2x2`` chip and compiles it with the TPU compiler installed
alongside JAX, then checks that the compiled program holds the kernel
(``tpu_custom_call``) rather than a fallback. Interpret-mode tests cannot
see what only the chip's compiler refuses: block shapes off the (8, 128)
tiling, VMEM overuse, unsupported ops inside a kernel.

Widths are those ``chip_smoke.py`` runs on the chip: llama3.2-1b attention
heads at S = T = 1024, the paper MLP gossiped over N = 100 nodes, and the
``large_n`` preset's BA n=4096 graph with its hidden=[64] member; and the
benchmark's paper cell for the local-round kernel: N = 100 nodes of the
paper MLP, 9 local steps of batch 32 a round, from a 60,000-image bank.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cfgbase
from repro.configs.paper_mlp import CONFIG as PAPER_MLP
from repro.core import sparse
from repro.core import topology as T
from repro.experiments import presets
from repro.kernels import local_round, ops
from repro.models.mlp import init_mlp


def _param_count(init) -> int:
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles_at_llama32_1b_heads(one_chip):
    cfg = cfgbase.get("llama3.2-1b")
    s = 1024
    q = ((1, s, cfg.num_heads, cfg.head_dim), jnp.bfloat16)
    kv = ((1, s, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16)
    text = _compile(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True, interpret=False),
        q, kv, kv, sharding=one_chip,
    )
    assert "tpu_custom_call" in text


def test_gossip_mix_compiles_at_paper_mlp_width(one_chip):
    p = _param_count(lambda k: init_mlp(
        k, in_dim=PAPER_MLP.in_dim, hidden=PAPER_MLP.hidden,
        num_classes=PAPER_MLP.num_classes,
    ))
    n = PAPER_MLP.num_nodes
    text = _compile(
        lambda w, x: ops.gossip_mix(w, x, interpret=False),
        ((n, n), jnp.float32), ((n, p), jnp.float32), sharding=one_chip,
    )
    assert "tpu_custom_call" in text


def test_blocked_ell_compiles_at_large_n_width(one_chip):
    spec = next(
        s for s in presets.get_preset("large_n") if s.backend == "sparse_sharded"
    )
    g = T.make(spec.topology, seed=spec.seed)
    bell = sparse.block_ell_from_csr(sparse.csr_from_graph(g))
    p = _param_count(lambda k: init_mlp(k, hidden=tuple(spec.model["hidden"])))
    text = _compile(
        lambda i, v, x: ops.gossip_mix_sparse_blocked(i, v, x, interpret=False),
        (bell.idx.shape, jnp.int32), (bell.val.shape, jnp.float32),
        ((g.num_nodes, p), jnp.float32), sharding=one_chip,
    )
    assert "tpu_custom_call" in text


def test_local_round_compiles_at_paper_mlp_width(one_chip):
    n, steps, batch = PAPER_MLP.num_nodes, 9, 32

    def stacked(k):
        return jax.vmap(lambda k: init_mlp(
            k, in_dim=PAPER_MLP.in_dim, hidden=PAPER_MLP.hidden,
            num_classes=PAPER_MLP.num_classes,
        ))(jax.random.split(k, n))

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    state = jax.tree.map(sds, jax.eval_shape(stacked, jax.random.PRNGKey(0)))
    x = sds(jax.ShapeDtypeStruct((60_000, PAPER_MLP.in_dim), jnp.float32))
    rows = sds(jax.ShapeDtypeStruct((n, steps, batch), jnp.int32))
    assert local_round.fits(state, state, x, steps=steps, batch=batch) is False  # not at highest
    with jax.default_matmul_precision("highest"):
        assert local_round.fits(state, state, x, steps=steps, batch=batch)
        text = jax.jit(
            lambda x, r, l, p, m: local_round.local_round(
                x, r, l, p, m, lr=0.05, mu=0.9, interpret=False),
            donate_argnums=(3, 4),
        ).lower(x, rows, rows, state, state).compile().as_text()
    assert "tpu_custom_call" in text
