"""Pallas TPU kernels for sparse (ELL) DecAvg gossip ``C = W @ P``.

Unlike the dense kernel (gossip_mix.py) — which streams (bm, bk) W tiles
through the MXU and merely *skips* zero blocks — these kernels never
materialize W at all. Per-round work and wire volume are O(E * D), the
row-gather analogue of the segment-sum path in core/sparse.py, which both
kernels match allclose (tests/test_sparse.py, tests/test_backend_equivalence.py).

Two layouts, two kernels:

1. **8-row-blocked ELL** (``sparse_gossip_blocked_pallas``) — the real TPU
   path. Rows are grouped into blocks of 8 (the f32 sublane count); the
   layout (core/sparse.block_ell_from_csr) enumerates, per destination
   block, the distinct *source blocks* its rows touch and stores the
   coupling weights as dense (8, 8) tiles stacked to a lane-aligned
   (N, 8*KB) array, KB a multiple of 16. The grid is (NB, D/bd, KB/16):
   step (b, j, k) takes one (8, 128) weight block — the 16 tiles of slots
   16k..16k+15, a block the TPU compiler accepts as it stands — and, through
   16 scalar-prefetched index maps over the same P, the 16 (8, bd) slabs of
   source blocks ``blk_idx[b, 16k + i]``: aligned 8-row transfers instead of
   eight (1, bd) row gathers each. The MXU accumulates the 16 (8, 8) @
   (8, bd) mini-matmuls, in slot order, into an f32 scratch block, flushed
   at the last k.

2. **Scalar ELL row-gather** (``sparse_gossip_pallas``) — the original
   per-row kernel, kept as the *interpret-mode fallback*: its grid is
   O(N * K) single-row steps, which on TPU underutilizes the sublanes but
   through the Pallas interpreter (CPU CI) is far cheaper than the blocked
   kernel's denser tile stream. ``kernels/ops.py`` selects the kernel:
   blocked on real TPU, scalar under interpret, override via ``blocked=``.

Scalar prefetch (pltpu.PrefetchScalarGridSpec) is the canonical Pallas
pattern for data-dependent tile addressing: the index array lands in SMEM
before the body runs, so each P block fetch is a regular pipelined DMA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "sparse_gossip_kernel",
    "sparse_gossip_pallas",
    "sparse_gossip_blocked_kernel",
    "sparse_gossip_blocked_pallas",
    "DEFAULT_BD",
    "BLOCK_ROWS",
    "TILES_PER_STEP",
]

DEFAULT_BD = 512
BLOCK_ROWS = 8  # f32 sublane count: the row granularity of the blocked kernel
TILES_PER_STEP = 16  # (8, 8) weight tiles in one 128-lane weight block


# ---------------------------------------------------------------------------
# 8-row-blocked ELL kernel (TPU sublane packing)
# ---------------------------------------------------------------------------


def sparse_gossip_blocked_kernel(idx_ref, val_ref, *refs, nsteps: int):
    """One (b, j, k) grid step: acc += sum_i W_tile_i(8, 8) @ P_block_i(8, bd).

    Refs:
      idx_ref: (NB, KB) int32 scalar-prefetch (SMEM) — consumed by the index
               maps; unused in the body but part of the kernel signature.
      val_ref: (8, 128) f32 VMEM — the 16 weight tiles coupling destination
               block b to source blocks idx_ref[b, 16k + i], tile i in lanes
               8i..8i+7.
      refs:    16 (8, bd) P slabs (source block idx_ref[b, 16k + i]), then
               the (8, bd) output block, written once per (b, j), and the
               (8, bd) f32 VMEM scratch accumulator.
    """
    p_refs, out_ref, acc_ref = refs[:TILES_PER_STEP], refs[-2], refs[-1]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for i, p_ref in enumerate(p_refs):
        acc_ref[...] += jnp.dot(
            val_ref[:, i * BLOCK_ROWS:(i + 1) * BLOCK_ROWS],
            p_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    @pl.when(k == nsteps - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def sparse_gossip_blocked_pallas(
    blk_idx: jax.Array,
    blk_val: jax.Array,
    p: jax.Array,
    *,
    bd: int = DEFAULT_BD,
    interpret: bool = False,
) -> jax.Array:
    """Blocked-ELL ``W @ P`` with f32 accumulation.

    blk_idx: (NB, KB) int32 source-block ids, KB a multiple of 16; blk_val:
    (NB*8, KB*8) f32 stacked weight tiles (core/sparse.block_ell_from_csr).
    P must be pre-padded to NB*8 rows and a D multiple of ``bd`` (the ops.py
    wrapper handles padding/unpadding); padded rows/tiles carry weight 0.
    """
    nb, kb = blk_idx.shape
    n, d = p.shape
    if n != nb * BLOCK_ROWS:
        raise ValueError(f"P rows {n} != {nb} blocks x {BLOCK_ROWS}")
    if kb % TILES_PER_STEP:
        raise ValueError(f"KB={kb} must be a multiple of {TILES_PER_STEP}")
    if blk_val.shape != (nb * BLOCK_ROWS, kb * BLOCK_ROWS):
        raise ValueError(
            f"blk_val {blk_val.shape} != ({nb * BLOCK_ROWS}, {kb * BLOCK_ROWS})"
        )
    if d % bd:
        raise ValueError(f"D={d} must be padded to a multiple of bd={bd}")
    nsteps = kb // TILES_PER_STEP
    p_specs = [
        pl.BlockSpec(
            (BLOCK_ROWS, bd),
            lambda b, j, k, idx_ref, i=i: (idx_ref[b, k * TILES_PER_STEP + i], j),
        )
        for i in range(TILES_PER_STEP)
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, d // bd, nsteps),
        in_specs=[
            pl.BlockSpec(
                (BLOCK_ROWS, BLOCK_ROWS * TILES_PER_STEP),
                lambda b, j, k, idx_ref: (b, k),
            ),
            *p_specs,
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, bd), lambda b, j, k, idx_ref: (b, j)),
        scratch_shapes=[pltpu.VMEM((BLOCK_ROWS, bd), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(sparse_gossip_blocked_kernel, nsteps=nsteps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), p.dtype),
        interpret=interpret,
    )(blk_idx, blk_val.astype(jnp.float32), *([p] * TILES_PER_STEP))


# ---------------------------------------------------------------------------
# Scalar ELL row-gather kernel (interpret-mode fallback)
# ---------------------------------------------------------------------------


def sparse_gossip_kernel(idx_ref, val_ref, p_ref, out_ref, acc_ref, *, nk: int):
    """One (i, j, k) grid step: acc += val[i, k] * P[idx[i, k], j-block].

    Refs:
      idx_ref: (N, K) int32 scalar-prefetch (SMEM) — consumed by index maps;
               unused in the body but part of the kernel signature.
      val_ref: (1, K) f32 VMEM — row i's ELL weights.
      p_ref:   (1, bd) VMEM — the gathered neighbor row's D-block.
      out_ref: (1, bd) output block, written once per (i, j).
      acc_ref: (1, bd) f32 VMEM scratch accumulator.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += val_ref[0, k] * p_ref[...].astype(jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def sparse_gossip_pallas(
    idx: jax.Array,
    val: jax.Array,
    p: jax.Array,
    *,
    bd: int = DEFAULT_BD,
    interpret: bool = False,
) -> jax.Array:
    """ELL ``W @ P`` with f32 accumulation. D must be pre-padded to a
    multiple of ``bd`` (the ops.py wrapper handles padding/unpadding)."""
    n, kmax = idx.shape
    if val.shape != (n, kmax):
        raise ValueError(f"idx {idx.shape} vs val {val.shape} mismatch")
    n2, d = p.shape
    if n2 != n:
        raise ValueError(f"ELL rows {n} != params rows {n2}")
    if d % bd:
        raise ValueError(f"D={d} must be padded to a multiple of bd={bd}")
    grid = (n, d // bd, kmax)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, kmax), lambda i, j, k, idx_ref: (i, 0)),  # lint: allow[P001] — scalar row-gather fallback: interpret-only, no TPU tiling
            pl.BlockSpec((1, bd), lambda i, j, k, idx_ref: (idx_ref[i, k], j)),  # lint: allow[P001] — scalar row-gather fallback: interpret-only, no TPU tiling
        ],
        out_specs=pl.BlockSpec((1, bd), lambda i, j, k, idx_ref: (i, j)),  # lint: allow[P001] — scalar row-gather fallback: interpret-only, no TPU tiling
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(sparse_gossip_kernel, nk=kmax),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), p.dtype),
        interpret=interpret,
    )(idx, val.astype(jnp.float32), p)
