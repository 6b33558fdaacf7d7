"""Paper-faithful decentralized trainer (DecAvg over a graph of nodes).

One *communication round* (paper §3):
  1. every node runs local SGD-with-momentum epochs on its own data,
  2. every node replaces its weights by the Eq. 1 neighborhood average.

All nodes advance in lockstep as node-stacked pytrees — local training is a
``vmap`` over the node axis (on a TPU, ``run_fused`` runs the paper MLP's
local steps in one Pallas kernel a round, ``kernels/local_round.py``), the
gossip is a GossipEngine round
(core/decavg.py: XLA einsum, Pallas kernel, or sparse CSR). Momentum is
node-local and is *not* averaged (the paper gossips model weights only).

The topology may be a built ``Graph``, a registry spec string
(``"ba:n=100,m=2"``, with ``n`` defaulted from the loader), or a
``TopologySchedule``.

Two execution paths over the same numerics:

- ``run``: one Python iteration per round. The mixing operand (dense W or
  CSR) is a *traced argument* of the round closure, so ``@regen``/``@rewire``
  schedule periods reuse one compiled program instead of re-jitting (backends
  that mix through engine-held static state fall back to a per-period cache
  of jitted closures). Batches come from the loader's round-keyed sampler.
- ``run_fused``: the whole run is ``lax.scan`` chunks of ``eval_every``
  rounds inside one jit — the engine's ``MixingProgram`` stages every
  schedule period up front, the loader's dataset is staged on device and
  batch indices are generated *inside* the scan, and stacked round metrics
  stream to ``on_round`` between chunks. Same seed => same params/metrics as
  ``run`` (tests pin allclose at 1e-6; sparse and sparse_sharded are
  bit-identical); dense, sparse, sparse_pallas and sparse_sharded backends —
  sharded runs put the whole scan under one ``shard_map`` so each device
  trains its node slab and only the halo exchange crosses devices. The
  Python loop remains the fallback for verbose/debug and the other backends.

``compress=`` (top-k fraction) turns on CHOCO-style gossip compression
(core/compress.py): each gossip round every node transmits the top-k entries
of ``params - reference``, peers mix the shared *reference* models, and
``params += W @ ref - ref`` — at ``k_frac=1`` this is exactly DecAvg, at
small k it cuts wire volume to k·|params| while reference tracking keeps the
residual re-entering next round's selection.

``DecentralizedTrainer`` is the 100-node MNIST-scale reproduction engine;
``LMCohortTrainer`` (below) gives the LLM-cohort path — transformer members
on domain-skewed token streams — the same two execution paths over one
``GossipEngine``: a per-round Python loop and a fused ``MixingProgram``
``lax.scan`` with AdamW + the LR schedule inside the scan body.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import compress as compress_mod
from repro.core import decavg
from repro.core.topology import Graph, TopologySchedule
from repro.data.loader import NodeLoader, round_batch_indices
from repro.kernels import local_round, ops
from repro.models.mlp import init_mlp, mlp_forward
from repro.optim import sgd
from repro.train.losses import softmax_xent
from repro.train.metrics import (
    accuracy,
    confusion_matrix,
    consensus_distance,
    group_accuracy,
)

PyTree = Any

# Backends whose mixing operand (dense W / CSR pytree) rides through the
# round closure as a traced argument: one compiled program serves every
# schedule period. The rest (engine-held static state: ELL layouts, meshes,
# edge colorings) re-trace per period via the per-period jit cache.
_OPERAND_BACKENDS = ("dense", "pallas", "sparse")

# Backends run_fused supports: those whose per-period operators stack into a
# MixingProgram (core/decavg.py) selectable by index inside a lax.scan —
# dense W, padded CSR, blocked-ELL tiles, and per-shard ShardedCSR metadata
# (whose ring/allgather halo exchange runs inside the scan under shard_map).
# Must mirror the ``fused`` flags in decavg._BACKEND_INFO (lint rule C001).
_FUSED_BACKENDS = ("dense", "sparse", "sparse_pallas", "sparse_sharded")

# Per-round threefry dispatch inside a lax.scan costs ~0.5 ms on CPU — a
# fixed floor the fused path can hoist: one vmapped draw over the whole
# chunk's rounds yields bit-identical indices (random primitives commute
# with vmap) as scan xs. Hoisting is gated by the index-tensor element
# count so a large-N thousands-of-rounds chunk falls back to in-scan
# generation instead of staging a multi-GB (L, steps, N, B) tensor.
_IDX_HOIST_MAX_ELEMS = 1 << 24  # 64 MB of int32


@dataclasses.dataclass
class RoundMetrics:
    round: int
    per_node_acc: np.ndarray  # (N,)
    mean_acc: float
    std_acc: float
    # Knowledge-spread extras (filled when the trainer has class_groups /
    # when eval runs; None otherwise so legacy consumers are unaffected).
    group_acc: np.ndarray | None = None  # (N, G) per-node per-group accuracy
    consensus: np.ndarray | None = None  # (N,) ||theta_i - theta_bar||
    wall_s: float = 0.0  # cumulative wall-clock since run() started


class DecentralizedTrainer:
    """DecAvg over an arbitrary model family (default: the paper's MLP)."""

    def __init__(
        self,
        graph: Graph | TopologySchedule | str,
        loader: NodeLoader,
        *,
        lr: float = 1e-3,
        momentum: float = 0.5,
        local_epochs: int = 1,
        mix_impl: str = "dense",  # a GossipEngine backend ("dense"|"pallas"|...) or "auto"
        matrix: str = "decavg",  # mixing matrix kind ("decavg"|"uniform"|"mh")
        sparse_p_chunk=None,  # int | "auto": bound the sparse gather transient
        gossip_every: int = 1,  # mix on rounds r % k == 0; 0 = isolated (no gossip)
        compress: float | None = None,  # top-k fraction for gossip compression
        faults: str | None = None,  # fault spec (core/faults.py), e.g. "churn:p_leave=0.1"
        same_init: bool = True,
        seed: int = 0,
        init_fn: Callable[..., PyTree] | None = None,
        forward_fn: Callable[[PyTree, jax.Array], jax.Array] | None = None,
        in_dim: int = 784,
        num_classes: int = 10,
        class_groups: Sequence[int] | np.ndarray | None = None,
    ):
        self.loader = loader
        self.engine = decavg.GossipEngine(
            graph, data_sizes=loader.sizes.astype(np.float64), backend=mix_impl,
            matrix=matrix, sparse_p_chunk=sparse_p_chunk,
            gossip_every=gossip_every, faults=faults, seed=seed,
            n=len(loader.sizes),
        )
        if mix_impl == "auto":
            mix_impl = self.engine.backend
        self.mix_impl = mix_impl
        self.faulted = self.engine.faults is not None
        if self.faulted and compress is not None:
            raise ValueError(
                "faults do not compose with compress= gossip: the CHOCO "
                "reference update assumes every published model is current"
            )
        self.graph = self.engine.graph
        self.lr, self.mu = lr, momentum
        self.local_epochs = local_epochs
        self.num_nodes = self.engine.num_nodes
        self.num_classes = num_classes
        if compress is not None and not 0.0 < float(compress) <= 1.0:
            raise ValueError(f"compress (top-k fraction) must be in (0, 1], got {compress}")
        self.compress = None if compress is None else float(compress)
        # class_groups maps class id -> group id (e.g. G1/G2 = 0/1); when set,
        # eval rounds also report per-node per-group accuracy.
        self.class_groups = (
            None if class_groups is None else jnp.asarray(np.asarray(class_groups), jnp.int32)
        )
        self.num_groups = (
            0 if self.class_groups is None else int(np.asarray(class_groups).max()) + 1
        )
        init_fn = init_fn or (lambda k: init_mlp(k, in_dim=in_dim, num_classes=num_classes))
        self.forward = forward_fn or mlp_forward

        self.w = self.engine.w
        # _mix(op, params): op is the current-period mixing operand (dense W
        # or CSR); engine-held backends ignore it and read engine state at
        # trace time. Tests may still override self.w directly (dense path).
        if mix_impl == "dense":
            self._mix = decavg.mix_dense
        elif mix_impl == "pallas":
            self._mix = lambda w, p: decavg.mix_pallas(
                w, p, interpret=self.engine.interpret
            )
        elif mix_impl == "sparse":
            self._mix = self._mix_sparse
        else:
            self._mix = lambda op, p: self.engine.mix(p, backend=mix_impl)

        key = jax.random.PRNGKey(seed)
        if same_init:
            p0 = init_fn(key)
            self.params = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (self.num_nodes,) + x.shape).copy(), p0
            )
        else:
            keys = jax.random.split(key, self.num_nodes)
            self.params = jax.vmap(init_fn)(keys)
        self.opt_state = sgd.init(self.params)
        self.cstate = (
            None if self.compress is None else compress_mod.init(self.params)
        )
        # donate_argnums on params/opt_state (and compress reference): the
        # node-stacked pytrees are the footprint at N=4096 — without donation
        # every round double-buffers them.
        self._round_jit = jax.jit(self._round, donate_argnums=(1, 2, 3))
        self._local_jit = jax.jit(self._local_steps, donate_argnums=(0, 1))
        self._eval_jit = jax.jit(self._eval)
        self._group_eval_jit = jax.jit(self._group_eval)
        self._consensus_jit = jax.jit(consensus_distance)
        # Per-period cache for the engine-held backends (see _jit_for_period);
        # the init-time jit serves period 0 so repeat runs never recompile it.
        self._round_jit_cache: dict[int, Any] = {0: self._round_jit}
        self._fused_chunk_jit = jax.jit(
            self._fused_chunk,
            static_argnames=("length", "do_eval"),
            donate_argnums=(2, 3, 4, 5),
        )
        if self.faulted:
            trace = self.engine.fault_trace
            self._fault_delay = jnp.asarray(trace.delay)
            self._has_hist = trace.delay_max > 0
            self._round_faulted_jit = jax.jit(
                self._round_faulted, donate_argnums=(4, 5, 6)
            )
            self._local_faulted_jit = jax.jit(
                self._local_faulted, donate_argnums=(2, 3, 4)
            )

    # -- jitted bodies ------------------------------------------------------

    def _mix_sparse(self, csr, params):
        from repro.core import sparse

        p_chunk = self.engine.sparse_p_chunk
        if p_chunk == "auto":
            p_chunk = sparse.auto_p_chunk(csr.nnz)  # nnz is static under trace
        return sparse.mix_sparse(csr, params, p_chunk=p_chunk)

    def _mix_op(self):
        """The current-period mixing operand passed into the round closure."""
        return self.engine.csr if self.mix_impl == "sparse" else self.w

    def _sgd_step(self, params, opt_state, x, y):
        """One SGD-with-momentum step of every node on its own batch:
        ``x`` (N, B, D), ``y`` (N, B)."""

        def node_loss(p, xb, yb):
            return softmax_xent(self.forward(p, xb), yb)

        with jax.named_scope("decavg.local_grad"):
            grads = jax.vmap(jax.grad(node_loss))(params, x, y)
        # sgd.update broadcasts fine over the stacked node axis.
        with jax.named_scope("decavg.sgd_update"):
            return sgd.update(grads, opt_state, params, lr=self.lr, mu=self.mu)

    def _local_steps(self, params, opt_state, xs, ys):
        """xs: (steps, N, B, D); one vmapped SGD step per element of steps."""

        def one_step(carry, batch):
            return self._sgd_step(*carry, *batch), None

        (params, opt_state), _ = jax.lax.scan(one_step, (params, opt_state), (xs, ys))
        return params, opt_state

    def _local_kernel_ok(self, kind: str, params, opt_state, x) -> bool:
        """Whether a fused round's local steps run in the Pallas kernel
        (``kernels/local_round.py``) rather than XLA's scan over
        ``_sgd_step``. Decided only by what the trainer observes: a TPU, a
        single-device program without faults or compression, the paper
        MLP's forward, and state the kernel ``fits``."""
        return (
            ops.on_tpu()
            and kind != "sparse_sharded"
            and not self.faulted
            and self.compress is None
            and self.forward is mlp_forward
            and local_round.fits(
                params, opt_state.momentum, x,
                steps=self.loader.steps_per_epoch() * self.local_epochs,
                batch=self.loader.batch,
            )
        )

    def _kernel_local_steps(self, data, node, idx, params, opt_state):
        """A round's local steps (``idx`` (steps, N, B) pool positions) in
        one Pallas kernel that keeps each node's state in VMEM across them.
        Only the rows and labels are gathered here; the kernel fetches the
        images itself."""
        with jax.named_scope("decavg.batch"):
            rows = data.parts[node[:, None, None], jnp.swapaxes(idx, 0, 1)]
            labels = data.y[rows]
        with jax.named_scope("decavg.local_grad"):
            params, mom = local_round.local_round(
                data.x, rows, labels, params, opt_state.momentum,
                lr=self.lr, mu=self.mu,
            )
        return params, sgd.SGDState(mom)

    @staticmethod
    def _gather_batch(data, node, idx):
        """Each node's batch from the staged dataset: node ``node[i]`` takes
        its bank rows ``idx[i]`` (B,)."""
        with jax.named_scope("decavg.batch"):
            rows = data.parts[node[:, None], idx]  # (N, B) bank rows
            return data.x[rows], data.y[rows]

    def _fault_mask(self, alive, r, delay, params, opt_state, p_in, o_in, hist):
        """Freeze dead nodes back to their pre-round params AND momentum
        (exactly equivalent to never training them) and push the round into
        the straggler ring buffer. Returns (params, opt_state, published
        snapshots or None, hist)."""
        from repro.core import faults as faults_mod

        with jax.named_scope("decavg.fault_mask"):
            params = faults_mod.where_alive(alive, params, p_in)
            opt_state = faults_mod.where_alive(alive, opt_state, o_in)
            pub = None
            if self._has_hist:
                pub, hist = faults_mod.push_and_publish(params, hist, r, delay)
        return params, opt_state, pub, hist

    def _gossip(self, mix, params, cstate):
        """One gossip exchange via ``mix`` (a params->params mixing closure).

        Without compression this is plain DecAvg. With it, the CHOCO update:
        each node publishes the top-k of ``params - reference`` (advancing
        the shared reference), peers average *references*, and the node keeps
        its residual: ``params += W @ ref - ref``. k_frac=1 reduces exactly
        to ``params = W @ params``.
        """
        if self.compress is None:
            with jax.named_scope("decavg.mix"):
                return mix(params), cstate
        _, cstate = jax.vmap(
            functools.partial(compress_mod.compress, k_frac=self.compress)
        )(params, cstate)
        ref = cstate.reference
        with jax.named_scope("decavg.mix"):
            mixed = mix(ref)
        params = jax.tree.map(
            lambda p, m, r: (p.astype(jnp.float32) + (m - r)).astype(p.dtype),
            params, mixed, ref,
        )
        return params, cstate

    def _round(self, op, params, opt_state, cstate, xs, ys):
        params, opt_state = self._local_steps(params, opt_state, xs, ys)
        params, cstate = self._gossip(
            functools.partial(self._mix, op), params, cstate
        )
        return params, opt_state, cstate

    # -- faulted rounds (core/faults.py semantics) ---------------------------

    def _mix_op_faulted(self):
        """The traced mixing operand for the faulted round: every
        fault-capable backend is operand-style here (``ShardedCSR`` is a
        registered pytree), so one compiled round serves all periods."""
        if self.mix_impl == "dense":
            return self.w
        if self.mix_impl == "sparse":
            return self.engine.csr
        return self.engine.sharded_csr()

    def _fault_keep(self, r: int) -> np.ndarray:
        """Round ``r``'s entry-keep mask in the backend's operand layout."""
        trace = self.engine.fault_trace
        if self.mix_impl == "dense":
            return trace.dense_keep(r)
        if self.mix_impl == "sparse":
            csr = self.engine.csr
            return trace.entry_keep(
                r, np.asarray(csr.rows), np.asarray(csr.indices),
                np.asarray(csr.values),
            )
        shcsr = self.engine.sharded_csr()
        blk = shcsr.rows_per_shard
        rows_g = np.asarray(shcsr.rows) + np.arange(shcsr.shards)[:, None] * blk
        cols_g = np.take_along_axis(
            np.asarray(shcsr.halo), np.asarray(shcsr.cols), axis=1
        )
        return trace.entry_keep(r, rows_g, cols_g, np.asarray(shcsr.values))

    def _mix_faulted(self, op, keep, alive, cur, pub):
        from repro.core import faults as faults_mod

        with jax.named_scope("decavg.mix"):
            if self.mix_impl == "dense":
                return faults_mod.mix_faulted_dense(op, keep, alive, cur, pub)
            if self.mix_impl == "sparse":
                return faults_mod.mix_faulted_csr(
                    op.rows, op.indices, op.values, keep, alive,
                    self.num_nodes, cur, pub,
                )
            return decavg.mix_sharded_sparse_faulted(
                op, cur, cur if pub is None else pub, keep, alive,
                mesh=self.engine.mesh, node_axis=self.engine.node_axis,
                halo_schedule=self.engine.halo_schedule,
            )

    def _round_faulted(self, op, keep, alive, r, params, opt_state, hist, xs, ys):
        """One faulted gossip round: train, freeze dead nodes back to their
        pre-round state (params AND momentum — exactly equivalent to never
        training them), advance the straggler ring buffer, mix the published
        snapshots over the surviving renormalized W."""
        p_in, o_in = params, opt_state
        params, opt_state = self._local_steps(params, opt_state, xs, ys)
        params, opt_state, pub, hist = self._fault_mask(
            alive, r, self._fault_delay, params, opt_state, p_in, o_in, hist
        )
        params = self._mix_faulted(op, keep, alive, params, pub)
        return params, opt_state, hist

    def _local_faulted(self, r, alive, params, opt_state, hist, xs, ys):
        """A faulted non-gossip round: train + freeze + history push (a
        straggler's clock advances whether or not the round gossips)."""
        p_in, o_in = params, opt_state
        params, opt_state = self._local_steps(params, opt_state, xs, ys)
        params, opt_state, _, hist = self._fault_mask(
            alive, r, self._fault_delay, params, opt_state, p_in, o_in, hist
        )
        return params, opt_state, hist

    def _eval(self, params, x_test, y_test):
        def node_metrics(p):
            logits = self.forward(p, x_test)
            return accuracy(logits, y_test), confusion_matrix(
                logits, y_test, self.num_classes
            )

        with jax.named_scope("decavg.eval"):
            return jax.vmap(node_metrics)(params)

    def _group_eval(self, params, x_test, y_test):
        """Per-node (accuracy, per-group accuracy); used when class_groups set."""

        def node_metrics(p):
            logits = self.forward(p, x_test)
            return accuracy(logits, y_test), group_accuracy(
                logits, y_test, self.class_groups, self.num_groups
            )

        with jax.named_scope("decavg.eval"):
            return jax.vmap(node_metrics)(params)

    def _chunk_eval(self, params, x_test, y_test):
        """A fused chunk's metrics: (accs, group accs or None, consensus)."""
        if self.class_groups is not None:
            accs, gaccs = self._group_eval(params, x_test, y_test)
        else:
            accs, _ = self._eval(params, x_test, y_test)
            gaccs = None
        with jax.named_scope("decavg.eval"):
            cons = consensus_distance(params)
        return accs, gaccs, cons

    def _fused_chunk(
        self, program, data, params, opt_state, cstate, hist, start,
        x_test, y_test, *, length: int, do_eval: bool,
    ):
        """``length`` rounds as one lax.scan, plus (optionally) one eval.

        ``program`` is the engine's MixingProgram (all schedule periods
        staged), ``data`` the loader's DeviceData; batch indices are
        generated inside the scan from ``(data.key, round)`` — the same
        draws the Python loop makes on the host. ``hist`` is the straggler
        ring buffer for faulted programs (``()`` when unused) and rides the
        scan carry, so a faulty run — dead-node freezes, renormalized
        mixing, stale snapshots and all — stays one compiled program.
        """
        steps = self.loader.steps_per_epoch() * self.local_epochs
        if program.kind == "sparse_sharded":
            params, opt_state, cstate, hist = self._scan_rounds_sharded(
                program, data, params, opt_state, cstate, hist, start,
                length=length, steps=steps,
            )
            if not do_eval:
                return params, opt_state, cstate, hist, None
            metrics = self._chunk_eval(params, x_test, y_test)
            return params, opt_state, cstate, hist, metrics
        node = jnp.arange(self.num_nodes)
        hoist = (
            length * steps * self.num_nodes * self.loader.batch
            <= _IDX_HOIST_MAX_ELEMS
        )
        kernel = self._local_kernel_ok(program.kind, params, opt_state, data.x)

        def one_round(carry, x):
            params, opt, cstate, hist = carry
            if hoist:
                r, idx = x
            else:
                r = x
                with jax.named_scope("decavg.batch"):
                    idx = round_batch_indices(
                        data.key, r, steps, self.loader.batch, data.sizes
                    )

            def one_step(c, idx_s):
                x, y = self._gather_batch(data, node, idx_s)
                return self._sgd_step(*c, x, y), None

            p_in, o_in = params, opt
            if kernel:
                params, opt = self._kernel_local_steps(data, node, idx, params, opt)
            else:
                (params, opt), _ = jax.lax.scan(one_step, (params, opt), idx)
            if self.faulted:
                params, opt, pub, hist = self._fault_mask(
                    program.f_alive[r], r, program.f_delay,
                    params, opt, p_in, o_in, hist,
                )
                params = program.mix_at(params, r, pub)
            elif self.compress is None:
                params = program.mix_at(params, r)
            else:
                # Compression state must advance only on gossip rounds (the
                # loop path's non-gossip rounds never touch it).
                def do(args):
                    p, cs = args
                    return self._gossip(lambda q: program.apply(q, r), p, cs)

                if program.cadence == "always":
                    params, cstate = do((params, cstate))
                elif program.cadence == "mask":
                    params, cstate = jax.lax.cond(
                        program.gossip_mask[r], do, lambda a: a, (params, cstate)
                    )
            return (params, opt, cstate, hist), None

        rs = start + jnp.arange(length)
        if hoist:
            with jax.named_scope("decavg.batch"):
                idx_all = jax.vmap(
                    lambda r: round_batch_indices(
                        data.key, r, steps, self.loader.batch, data.sizes
                    )
                )(rs)
            xs = (rs, idx_all)
        else:
            xs = rs
        (params, opt_state, cstate, hist), _ = jax.lax.scan(
            one_round, (params, opt_state, cstate, hist), xs
        )
        if not do_eval:
            return params, opt_state, cstate, hist, None
        metrics = self._chunk_eval(params, x_test, y_test)
        return params, opt_state, cstate, hist, metrics

    def _scan_rounds_sharded(
        self, program, data, params, opt_state, cstate, hist, start,
        *, length, steps,
    ):
        """``length`` rounds with the node axis sharded END TO END.

        ONE ``shard_map`` wraps the whole ``lax.scan``: each device trains
        its N/S-node slab and the only cross-device traffic per round is the
        mix's halo exchange (``program.apply_local``). The alternative — a
        shard_map per mix *inside* the scan — turns the chunk into an SPMD
        program whose train step runs replicated on every device and whose
        carry is resharded at each iteration boundary: measured ~5x slower
        than the Python loop at N=256 over 8 host devices, where this layout
        is faster than the loop. Numerics are unchanged: the per-node train
        step is elementwise over nodes, batch indices are the same
        replicated draws sliced per slab, and the mix body is the same code
        the loop path runs.
        """
        axes = (
            (program.node_axis,) if isinstance(program.node_axis, str)
            else tuple(program.node_axis)
        )
        blk = self.num_nodes // program.shards
        batch = self.loader.batch

        hoist = length * steps * self.num_nodes * batch <= _IDX_HOIST_MAX_ELEMS

        def local_scan(program, data, start, params, opt, cstate, hist):
            sidx = jax.lax.axis_index(axes)
            gnode = sidx * blk + jnp.arange(blk)  # slab's global node ids
            if self.faulted:
                # Static per-node staleness, pre-sliced to this slab once.
                delay_s = jax.lax.dynamic_slice_in_dim(
                    program.f_delay, sidx * blk, blk
                )

            def one_round(carry, x):
                params, opt, cstate, hist = carry
                if hoist:
                    r, idx = x
                else:
                    # The full (steps, N, B) index tensor is integer-only
                    # and tiny; every device computes it replicated
                    # (identical to the host/loop draws) and slices its own
                    # slab's rows.
                    r = x
                    with jax.named_scope("decavg.batch"):
                        idx = round_batch_indices(
                            data.key, r, steps, batch, data.sizes
                        )
                        idx = jax.lax.dynamic_slice_in_dim(
                            idx, sidx * blk, blk, axis=1
                        )

                def one_step(c, idx_s):
                    x, y = self._gather_batch(data, gnode, idx_s)
                    return self._sgd_step(*c, x, y), None

                p_in, o_in = params, opt
                (params, opt), _ = jax.lax.scan(one_step, (params, opt), idx)
                if self.faulted:
                    # Slab view of the global masks; mixing still sees the
                    # full alive vector via mix_at_local's own slicing.
                    alive_s = jax.lax.dynamic_slice_in_dim(
                        program.f_alive[r], sidx * blk, blk
                    )
                    params, opt, pub, hist = self._fault_mask(
                        alive_s, r, delay_s, params, opt, p_in, o_in, hist
                    )
                    params = program.mix_at_local(params, r, pub)
                elif self.compress is None:
                    params = program.mix_at_local(params, r)
                else:
                    def do(args):
                        p, cs = args
                        return self._gossip(
                            lambda q: program.apply_local(q, r), p, cs
                        )

                    if program.cadence == "always":
                        params, cstate = do((params, cstate))
                    elif program.cadence == "mask":
                        params, cstate = jax.lax.cond(
                            program.gossip_mask[r], do, lambda a: a,
                            (params, cstate),
                        )
                return (params, opt, cstate, hist), None

            rs = start + jnp.arange(length)
            if hoist:
                # One vmapped draw for the whole chunk (bit-identical to
                # the per-round draws), pre-sliced to this device's slab so
                # the staged xs tensor is 1/S the replicated size.
                with jax.named_scope("decavg.batch"):
                    idx_all = jax.vmap(
                        lambda r: round_batch_indices(
                            data.key, r, steps, batch, data.sizes
                        )
                    )(rs)
                    idx_all = jax.lax.dynamic_slice_in_dim(
                        idx_all, sidx * blk, blk, axis=2
                    )
                xs = (rs, idx_all)
            else:
                xs = rs
            (params, opt, cstate, hist), _ = jax.lax.scan(
                one_round, (params, opt, cstate, hist), xs
            )
            return params, opt, cstate, hist

        def node_specs(tree):
            return jax.tree.map(
                lambda l: P(axes, *([None] * (l.ndim - 1))), tree
            )

        pspec = node_specs(params)
        ospec = node_specs(opt_state)
        cspec = node_specs(cstate)
        hspec = node_specs(hist)
        return jax.shard_map(
            local_scan, mesh=program.mesh,
            in_specs=(P(), P(), P(), pspec, ospec, cspec, hspec),
            out_specs=(pspec, ospec, cspec, hspec),
        )(program, data, start, params, opt_state, cstate, hist)

    def _jit_for_period(self, period: int):
        """The round step for a new schedule period.

        Operand backends reuse the one compiled program (the new W/CSR is
        just a new argument value; a different per-period nnz re-traces by
        shape, cached). Engine-held backends (sparse_pallas, sharded,
        permute, ...) bake period state in at trace time, so they get one
        jitted closure per period, cached across repeat visits/runs.
        """
        if self.mix_impl in _OPERAND_BACKENDS:
            return self._round_jit
        jitted = self._round_jit_cache.get(period)
        if jitted is None:
            # NOT jax.jit(self._round): equal bound methods share one pjit
            # cache entry, so a "fresh" jit after a period change would
            # silently reuse the executable traced with the previous
            # period's engine state. A nested function is a distinct cache
            # key, forcing the retrace that bakes in the new period.
            def _round_fn(op, params, opt_state, cstate, xs, ys):
                return self._round(op, params, opt_state, cstate, xs, ys)

            jitted = jax.jit(_round_fn, donate_argnums=(1, 2, 3))
            if len(self._round_jit_cache) >= 64:
                # Bound compiled-program memory on long @regen runs (same cap
                # as the engine's coloring cache); re-entering an evicted
                # period just pays one re-jit.
                self._round_jit_cache.pop(next(iter(self._round_jit_cache)))
            self._round_jit_cache[period] = jitted
        return jitted

    def _stage_fused(self, rounds, x_test, y_test):
        """What a ``run_fused`` call puts on the device before its first
        chunk: (mixing program, dataset, straggler history, test set)."""
        with jax.profiler.TraceAnnotation("trainer.stage"):
            program = self.engine.program(rounds, kind=self.mix_impl)
            data = self.loader.device_data()
            hist = ()
            if self.faulted and self._has_hist:
                from repro.core import faults as faults_mod

                hist = faults_mod.init_history(self.params, program.delay_max + 1)
            if program.kind == "sparse_sharded":
                # Commit the node-stacked state to its in-scan layout (node
                # axis sharded over the mesh) before the first chunk: the
                # fused chunk both consumes and produces this layout, so
                # without the upfront put the first call compiles for
                # replicated inputs and the second call recompiles for
                # sharded ones.
                from jax.sharding import NamedSharding

                axes = (
                    (program.node_axis,) if isinstance(program.node_axis, str)
                    else tuple(program.node_axis)
                )

                def put(tree):
                    return jax.tree.map(
                        lambda l: jax.device_put(
                            l, NamedSharding(program.mesh, P(axes, *([None] * (l.ndim - 1))))
                        ),
                        tree,
                    )

                self.params = put(self.params)
                self.opt_state = put(self.opt_state)
                self.cstate = put(self.cstate)
                hist = put(hist)
            x_t, y_t = (
                (None, None) if x_test is None else (jnp.asarray(x_test), jnp.asarray(y_test))
            )
        return program, data, hist, x_t, y_t

    def _fused_chunks(self, rounds: int, eval_every: int, do_eval: bool) -> list[tuple[int, int, int]]:
        """(last round, first round, length) of each fused chunk: chunks end
        at the eval rounds, and without an eval the run is one chunk."""
        ends = self._eval_rounds(rounds, eval_every) if do_eval else [rounds - 1]
        return [(end, prev + 1, end - prev) for prev, end in zip([-1, *ends], ends)]

    # -- public API ---------------------------------------------------------

    @property
    def supports_fused(self) -> bool:
        """True when ``run_fused`` can execute this trainer's backend."""
        return self.mix_impl in _FUSED_BACKENDS

    def eval_round(self, r: int, x_test, y_test, t0: float) -> RoundMetrics:
        """One evaluation pass over the current params as a RoundMetrics."""
        x_test, y_test = jnp.asarray(x_test), jnp.asarray(y_test)
        group_acc = None
        if self.class_groups is not None:
            accs, gaccs = self._group_eval_jit(self.params, x_test, y_test)
            group_acc = np.asarray(gaccs)
        else:
            accs, _ = self._eval_jit(self.params, x_test, y_test)
        accs = np.asarray(accs)
        cons = np.asarray(self._consensus_jit(self.params))
        return RoundMetrics(
            r, accs, float(accs.mean()), float(accs.std()),
            group_acc=group_acc, consensus=cons, wall_s=time.perf_counter() - t0,
        )

    @staticmethod
    def _eval_rounds(rounds: int, eval_every: int) -> list[int]:
        """Rounds after which both run paths evaluate/stream metrics."""
        return [r for r in range(rounds) if r % eval_every == 0 or r == rounds - 1]

    def run(
        self,
        rounds: int,
        *,
        eval_every: int = 1,
        x_test: np.ndarray | None = None,
        y_test: np.ndarray | None = None,
        gossip_first: bool = False,
        verbose: bool = False,
        on_round: Callable[[RoundMetrics], None] | None = None,
    ) -> list[RoundMetrics]:
        """Run communication rounds; returns per-round metrics history.

        ``on_round`` fires after every evaluated round (the experiment
        harness streams each RoundMetrics to its results store instead of
        waiting for the full history).
        """
        history: list[RoundMetrics] = []
        steps = self.loader.steps_per_epoch() * self.local_epochs
        t0 = time.perf_counter()
        if gossip_first:
            if self.faulted:
                raise ValueError(
                    "gossip_first does not compose with faults= (there is no "
                    "round index for the pre-round mix to draw masks from)"
                )
            self.params = self._mix(self._mix_op(), self.params)
        round_jit = self._round_jit
        hist = ()
        if self.faulted:
            from repro.core import faults as faults_mod

            trace = self.engine.fault_trace
            trace.ensure(rounds)
            if self._has_hist:
                hist = faults_mod.init_history(self.params, trace.delay_max + 1)
        for r in range(rounds):
            if self.engine.schedule.is_time_varying and self.engine.refresh(r):
                # New schedule period: fresh W/CSR; one compiled program for
                # operand backends, per-period cached closures for the rest.
                self.w = self.engine.w
                self.graph = self.engine.graph
                round_jit = self._jit_for_period(self.engine.schedule.period_of(r))
            xs, ys = self.loader.sample_round(steps, round=r)
            if self.faulted:
                alive = jnp.asarray(trace.alive(r))
                if self.engine.is_gossip_round(r):
                    self.params, self.opt_state, hist = self._round_faulted_jit(
                        self._mix_op_faulted(), jnp.asarray(self._fault_keep(r)),
                        alive, jnp.int32(r), self.params, self.opt_state, hist,
                        jnp.asarray(xs), jnp.asarray(ys),
                    )
                else:
                    self.params, self.opt_state, hist = self._local_faulted_jit(
                        jnp.int32(r), alive, self.params, self.opt_state, hist,
                        jnp.asarray(xs), jnp.asarray(ys),
                    )
            elif self.engine.is_gossip_round(r):
                self.params, self.opt_state, self.cstate = round_jit(
                    self._mix_op(), self.params, self.opt_state, self.cstate,
                    jnp.asarray(xs), jnp.asarray(ys),
                )
            else:
                self.params, self.opt_state = self._local_jit(
                    self.params, self.opt_state, jnp.asarray(xs), jnp.asarray(ys)
                )
            if x_test is not None and (r % eval_every == 0 or r == rounds - 1):
                m = self.eval_round(r, x_test, y_test, t0)
                history.append(m)
                if on_round is not None:
                    on_round(m)
                if verbose:
                    accs = m.per_node_acc
                    print(
                        f"round {r:4d}  acc mean {accs.mean():.4f} "
                        f"std {accs.std():.4f} min {accs.min():.4f} max {accs.max():.4f}"
                    )
        return history

    def run_fused(
        self,
        rounds: int,
        *,
        eval_every: int = 1,
        x_test: np.ndarray | None = None,
        y_test: np.ndarray | None = None,
        gossip_first: bool = False,
        verbose: bool = False,
        on_round: Callable[[RoundMetrics], None] | None = None,
    ) -> list[RoundMetrics]:
        """``run`` compiled into lax.scan chunks — one dispatch per eval.

        The whole multi-round program runs on device: every schedule period
        is staged up front (``GossipEngine.program``), batches are sampled
        inside the scan from the staged dataset, and ``gossip_every`` is a
        select in the scan body. Rounds are scanned in chunks that end at
        the eval rounds (``r % eval_every == 0`` plus the final round —
        exactly ``run``'s cadence), and each chunk's RoundMetrics streams to
        ``on_round`` before the next chunk launches, so consumers see the
        same callback sequence as the Python loop. Without ``x_test`` the
        entire run is a single scan.

        Same seed => same params and metrics as ``run`` (allclose at f32
        1e-6, bit-identical for the sparse/sparse_sharded backends whose
        loop and fused paths share one CSR construction; pinned by
        tests/test_fused.py and tests/test_fused_sharded.py). Supported for
        the dense, sparse, sparse_pallas and sparse_sharded backends;
        others raise (use ``run``). For sparse_sharded the halo exchange
        (ring ppermutes or allgather) runs inside the scan body, so the
        whole multi-host run is one compiled SPMD program per chunk.
        """
        if not self.supports_fused:
            raise ValueError(
                f"run_fused supports backends {_FUSED_BACKENDS}, not "
                f"{self.mix_impl!r}; use run()"
            )
        if rounds < 1:
            return []
        if gossip_first and self.faulted:
            raise ValueError(
                "gossip_first does not compose with faults= (there is no "
                "round index for the pre-round mix to draw masks from)"
            )
        do_eval = x_test is not None
        program, data, hist, x_t, y_t = self._stage_fused(rounds, x_test, y_test)
        t0 = time.perf_counter()
        if gossip_first:
            self.params = self._mix(self._mix_op(), self.params)

        def fetch(a):
            obs.count("trainer.d2h_transfers")
            return np.asarray(a)

        kernel = self._local_kernel_ok(program.kind, self.params, self.opt_state, data.x)
        history: list[RoundMetrics] = []
        for end, start, length in self._fused_chunks(rounds, eval_every, do_eval):
            with jax.profiler.TraceAnnotation("trainer.dispatch"):
                (
                    self.params, self.opt_state, self.cstate, hist, metrics,
                ) = self._fused_chunk_jit(
                    program, data, self.params, self.opt_state, self.cstate, hist,
                    jnp.int32(start), x_t, y_t, length=length, do_eval=do_eval,
                )
            obs.count("trainer.rounds", length)
            if kernel:
                obs.count("trainer.local_kernel_rounds", length)
            if not do_eval:
                continue
            with jax.profiler.TraceAnnotation("trainer.fetch"):
                accs, gaccs, cons = metrics
                accs = fetch(accs)
                m = RoundMetrics(
                    end, accs, float(accs.mean()), float(accs.std()),
                    group_acc=None if gaccs is None else fetch(gaccs),
                    consensus=fetch(cons), wall_s=time.perf_counter() - t0,
                )
            history.append(m)
            if on_round is not None:
                on_round(m)
            if verbose:
                print(
                    f"round {end:4d}  acc mean {accs.mean():.4f} "
                    f"std {accs.std():.4f} min {accs.min():.4f} max {accs.max():.4f}"
                )
        return history

    def fused_chunk_hlo(
        self,
        rounds: int,
        *,
        eval_every: int = 1,
        x_test: np.ndarray | None = None,
        y_test: np.ndarray | None = None,
    ) -> dict[int, str]:
        """The compiled text of each chunk program ``run_fused`` runs with
        these arguments, by chunk length. Its instructions' ``op_name``
        metadata carries the ``decavg.*`` scopes (``repro/obs.py``), which a
        device trace does not: a trace reduction maps each traced op to its
        scope through this text.

        Compiles afresh: the persistent compilation cache keys a program
        without its metadata, so a cached executable of the same program
        built with other scopes, or none, would answer with its own."""
        do_eval = x_test is not None
        program, data, hist, x_t, y_t = self._stage_fused(rounds, x_test, y_test)
        out: dict[int, str] = {}
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            for _, start, length in self._fused_chunks(rounds, eval_every, do_eval):
                if length not in out:
                    out[length] = self._fused_chunk_jit.lower(
                        program, data, self.params, self.opt_state, self.cstate, hist,
                        jnp.int32(start), x_t, y_t, length=length, do_eval=do_eval,
                    ).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
        return out

    def confusion(self, x_test: np.ndarray, y_test: np.ndarray) -> np.ndarray:
        _, cms = self._eval_jit(self.params, jnp.asarray(x_test), jnp.asarray(y_test))
        return np.asarray(cms)


# ---------------------------------------------------------------------------
# LLM cohorts (model kind "lm"; experiments/runner.py dispatches here)
# ---------------------------------------------------------------------------

# Backends the fused lm scan supports: the program-stageable single-host
# kinds. sparse_sharded's shard_map'd scan is mlp-specific today (the lm
# runner falls back to the loop for it). Must stay a subset of
# _FUSED_BACKENDS (lint rule C001).
_LM_FUSED_BACKENDS = ("dense", "sparse", "sparse_pallas")

# compress="auto" threshold: members whose gossiped pytree exceeds this many
# bytes default to CHOCO top-k gossip so wire volume stays sane (~1 MB — a
# reduced 1B-class member is ~6 MB f32, the tiny test transformers ~100 KB).
_COMPRESS_AUTO_BYTES = 1 << 20
_COMPRESS_AUTO_K = 0.1


class LMCohortTrainer:
    """DecAvg over a cohort of transformer LMs on domain-skewed token streams.

    The lm analogue of ``DecentralizedTrainer``: node-stacked transformer
    params, per-round next-token training (AdamW or SGD under an LR
    schedule), gossip through one ``GossipEngine``. Token batches are a pure
    function of ``(seed, node, round)`` (data/tokens.py), so the two
    execution paths draw bit-identical data:

    - ``run``: one Python iteration per round (jitted train step + eager
      ``engine.mix``) — the debug/fallback path, and the only path for
      backends the MixingProgram can't stage.
    - ``run_fused``: ``lax.scan`` chunks with the schedule's LR, the
      optimizer update, fault freezes and the staged mixing program all
      inside the scan body; each chunk's token slab is staged on device as
      the scan's xs (O(chunk) rounds of tokens live at once). Chunks end at
      eval and checkpoint rounds. Same seed => same params/loss as ``run``
      (tests pin allclose at 1e-6).

    ``compress="auto"`` (default) turns on CHOCO top-k gossip when the
    member pytree exceeds ~1 MB (``_COMPRESS_AUTO_BYTES``); pass a float for
    an explicit k fraction or ``None`` to force raw DecAvg. Faults never
    compose with compression — "auto" resolves to off for faulted runs, an
    explicit fraction raises.

    With ``faults=`` set, dead nodes are frozen bit-exactly — params AND
    optimizer moments (``where_alive_stacked``; AdamW's shared step count
    passes through) — in both paths, matching ``DecentralizedTrainer``'s
    PR 7 contract. Checkpoints save ``(params, opt[, cstate])`` plus the
    step, and ``restore`` resumes bit-identically (round-keyed batches +
    restored moments + the schedule being a pure function of the round).
    """

    def __init__(
        self,
        topology: Graph | TopologySchedule | str,
        cfg,
        *,
        nodes: int,
        batch: int = 4,
        seq: int = 128,
        lr: float = 3e-4,
        schedule: str = "cosine",
        backend: str = "auto",
        matrix: str = "decavg",
        gossip_every: int = 1,
        compress: float | str | None = "auto",
        faults: str | None = None,
        seed: int = 0,
        data_kwargs: dict | None = None,
    ):
        from repro.launch import steps as ST
        from repro.models import transformer as TF
        from repro.optim import adamw

        self.cfg = cfg
        self.num_nodes = int(nodes)
        self.batch, self.seq = int(batch), int(seq)
        self.lr, self.schedule_name, self.seed = lr, schedule, seed
        self.data_kwargs = dict(data_kwargs or {})
        self.engine = decavg.GossipEngine(
            topology, backend=backend, matrix=matrix, gossip_every=gossip_every,
            faults=faults, seed=seed, n=self.num_nodes,
        )
        if self.engine.num_nodes != self.num_nodes:
            raise ValueError(
                f"topology spec pins n={self.engine.num_nodes} but nodes is "
                f"{self.num_nodes}"
            )
        self.mix_impl = self.engine.backend
        self.graph = self.engine.graph
        self.faulted = self.engine.faults is not None

        key = jax.random.PRNGKey(seed)
        per_node = TF.init_params(key, cfg)
        self.member_params = TF.param_count(per_node)
        self.member_bytes = int(
            sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(per_node))
        )
        self.compress = self._resolve_compress(compress)
        self.params = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.num_nodes,) + x.shape).copy(),
            per_node,
        )
        use_adamw = cfg.optimizer == "adamw"
        from repro.optim import sgd as _sgd  # noqa: F401 (module-level import above)

        self.opt_state = adamw.init(self.params) if use_adamw else sgd.init(self.params)
        self.cstate = (
            None if self.compress is None else compress_mod.init(self.params)
        )
        self.start_round = 0  # advanced by restore()
        self._loss_fn = ST.node_loss_fn(cfg)
        self._opt_update = adamw.update if use_adamw else sgd.update
        self._sched = None  # built per run (total_steps = that run's rounds)
        self._eval_data = None
        self._train_jit = jax.jit(self._train, donate_argnums=(0, 1))
        self._train_faulted_jit = jax.jit(self._train_faulted, donate_argnums=(1, 2))
        self._compress_jit = jax.jit(self._compress_refs, donate_argnums=(1,))
        self._choco_apply_jit = jax.jit(self._choco_apply, donate_argnums=(0,))
        self._domain_eval_jit = jax.jit(self._domain_eval)
        self._consensus_jit = jax.jit(consensus_distance)
        self._fused_chunk_jit = jax.jit(
            self._fused_chunk, donate_argnums=(1, 2, 3, 4)
        )
        if self.faulted:
            self._has_hist = self.engine.fault_trace.delay_max > 0

    def _resolve_compress(self, compress) -> float | None:
        if compress == "auto":
            if self.faulted or self.member_bytes <= _COMPRESS_AUTO_BYTES:
                return None
            return _COMPRESS_AUTO_K
        if compress is None or compress is False:
            return None
        k = float(compress)
        if not 0.0 < k <= 1.0:
            raise ValueError(
                f"compress (top-k fraction) must be in (0, 1], got {compress}"
            )
        if self.faulted:
            raise ValueError(
                "faults do not compose with compress= gossip: the CHOCO "
                "reference update assumes every published model is current"
            )
        return k

    # -- jitted bodies ------------------------------------------------------

    def _train(self, params, opt, toks, labels, lr):
        losses, grads = jax.vmap(jax.value_and_grad(self._loss_fn))(
            params, {"tokens": toks, "labels": labels}
        )
        params, opt = self._opt_update(grads, opt, params, lr=lr)
        return params, opt, losses.mean()

    def _train_faulted(self, alive, params, opt, toks, labels, lr):
        """Train + freeze: dead nodes keep pre-round params AND moments
        bit-exactly (equivalent to never training them this round)."""
        from repro.core import faults as faults_mod

        p_in, o_in = params, opt
        params, opt, loss = self._train(params, opt, toks, labels, lr)
        params = faults_mod.where_alive(alive, params, p_in)
        opt = faults_mod.where_alive_stacked(alive, opt, o_in)
        return params, opt, loss

    def _compress_refs(self, params, cstate):
        _, cstate = jax.vmap(
            functools.partial(compress_mod.compress, k_frac=self.compress)
        )(params, cstate)
        return cstate

    @staticmethod
    def _choco_apply(params, mixed, ref):
        return jax.tree.map(
            lambda p, m, r: (p.astype(jnp.float32) + (m - r)).astype(p.dtype),
            params, mixed, ref,
        )

    def _choco_step(self, mix, params, cstate):
        """One CHOCO gossip exchange (cf. DecentralizedTrainer._gossip)."""
        cstate = self._compress_refs(params, cstate)
        ref = cstate.reference
        mixed = mix(ref)
        return self._choco_apply(params, mixed, ref), cstate

    def _domain_eval(self, params, toks, labels):
        """Per-node mean true-token probability on the held-out foreign-domain
        eval batch — ``domain_acc``: expected next-token accuracy under
        sampling decode, the quantity that rises as other nodes' domain
        knowledge reaches this member through gossip."""
        from repro.models import transformer as TF

        def node_eval(p, tk, lb):
            logits, _ = TF.forward(p, self.cfg, tk, remat=False)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            ll = jnp.take_along_axis(logp, lb[..., None], axis=-1)[..., 0]
            return jnp.exp(ll).mean()

        return jax.vmap(node_eval)(params, toks, labels)

    def _fused_chunk(self, program, params, opt, cstate, hist, start, toks, labels):
        """One scan over ``toks.shape[0]`` rounds: grads + optimizer + LR
        schedule + (fault freeze | staged mix | CHOCO gossip) per step.
        Returns the carried state and the per-round mean losses."""
        from repro.core import faults as faults_mod

        def one_round(carry, x):
            params, opt, cstate, hist = carry
            r, tk, lb = x
            lr = self._sched(r)
            p_in, o_in = params, opt
            losses, grads = jax.vmap(jax.value_and_grad(self._loss_fn))(
                params, {"tokens": tk, "labels": lb}
            )
            params, opt = self._opt_update(grads, opt, params, lr=lr)
            if self.faulted:
                alive = program.f_alive[r]
                params = faults_mod.where_alive(alive, params, p_in)
                opt = faults_mod.where_alive_stacked(alive, opt, o_in)
                pub = None
                if self._has_hist:
                    pub, hist = faults_mod.push_and_publish(
                        params, hist, r, program.f_delay
                    )
                params = program.mix_at(params, r, pub)
            elif self.compress is None:
                params = program.mix_at(params, r)
            else:
                # Compression state advances only on gossip rounds (the loop
                # path's non-gossip rounds never touch it).
                def do(args):
                    p, cs = args
                    return self._choco_step(lambda q: program.apply(q, r), p, cs)

                if program.cadence == "always":
                    params, cstate = do((params, cstate))
                elif program.cadence == "mask":
                    params, cstate = jax.lax.cond(
                        program.gossip_mask[r], do, lambda a: a, (params, cstate)
                    )
            return (params, opt, cstate, hist), losses.mean()

        rs = start + jnp.arange(toks.shape[0])
        (params, opt, cstate, hist), losses = jax.lax.scan(
            one_round, (params, opt, cstate, hist), (rs, toks, labels)
        )
        return (params, opt, cstate, hist), losses

    # -- metrics / checkpoint ------------------------------------------------

    def consensus(self) -> np.ndarray:
        return np.asarray(self._consensus_jit(self.params))

    def domain_metrics(self) -> dict:
        """G2-style knowledge-spread metrics on the token task: per-node
        ``domain_acc`` on *other* nodes' domain tokens, and their cohort
        mean ``g2_token_spread`` (the store/analysis join key)."""
        if self.num_nodes < 2:
            return {}
        from repro.data import tokens as tok

        if self._eval_data is None:
            toks, labels = tok.domain_eval_batch(
                self.num_nodes, self.batch, self.seq, self.cfg.vocab_size,
                seed=self.seed,
                **{k: v for k, v in self.data_kwargs.items() if k == "domain_size"},
            )
            self._eval_data = (jnp.asarray(toks), jnp.asarray(labels))
        accs = np.asarray(self._domain_eval_jit(self.params, *self._eval_data))
        return {
            "domain_acc": [round(float(a), 6) for a in accs],
            "g2_token_spread": float(accs.mean()),
        }

    def save(self, path: str, *, step: int) -> None:
        """Checkpoint ``(params, opt[, cstate])`` + step — everything a
        bit-identical resume needs (pre-PR-8 checkpoints saved params only,
        silently restarting AdamW moments on restore)."""
        from repro.checkpoint import ckpt

        tree = {"params": self.params, "opt": self.opt_state}
        if self.cstate is not None:
            tree["cstate"] = self.cstate
        ckpt.save(path, tree, step=step)

    def restore(self, path: str) -> int:
        """Restore a ``save`` checkpoint; the next ``run``/``run_fused``
        continues from the round after the saved step, re-deriving the same
        batches and LR the uninterrupted run would have seen."""
        from repro.checkpoint import ckpt

        if self.faulted and self._has_hist:
            raise ValueError(
                "resume does not compose with straggler faults: the "
                "delayed-snapshot ring buffer is not checkpointed"
            )
        like = {"params": self.params, "opt": self.opt_state}
        if self.cstate is not None:
            like["cstate"] = self.cstate
        tree, step = ckpt.restore(path, like)
        if step is None:
            raise ValueError(f"checkpoint {path!r} carries no step")
        self.params, self.opt_state = tree["params"], tree["opt"]
        if self.cstate is not None:
            self.cstate = tree["cstate"]
        self.start_round = int(step) + 1
        return self.start_round

    @staticmethod
    def _ckpt_rounds(rounds: int, ckpt_every: int) -> set[int]:
        """Checkpoint cadence: every ``ckpt_every`` rounds AND the final
        round (pre-PR-8 the final round was skipped unless divisible)."""
        if not ckpt_every:
            return set()
        s = {r for r in range(1, rounds) if r % ckpt_every == 0}
        s.add(rounds - 1)
        return s

    @property
    def supports_fused(self) -> bool:
        """True when ``run_fused`` can execute this trainer's backend."""
        return self.mix_impl in _LM_FUSED_BACKENDS

    def _round_record(self, r: int, loss, lr, t0: float) -> dict:
        rec = {
            "round": r,
            "loss": float(loss),
            "lr": float(lr),
            "wall_s": round(time.perf_counter() - t0, 4),
            **self.domain_metrics(),
        }
        if self.faulted:
            rec["alive_count"] = int(self.engine.fault_trace.alive(r).sum())
        return rec

    def _finished_resume(self, rounds, on_round, verbose, t0) -> list[dict]:
        """A resume that restored the final checkpoint has nothing left to
        train; still emit one eval record at the restored state so the run's
        final record (loss, spread metrics, wall clock) exists."""
        from repro.data import tokens as tok

        toks, labels = tok.round_token_batch(
            self.num_nodes, rounds - 1, self.batch, self.seq,
            self.cfg.vocab_size, seed=self.seed, **self.data_kwargs,
        )
        losses = jax.vmap(self._loss_fn)(
            self.params,
            {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)},
        )
        rec = self._round_record(
            rounds - 1, losses.mean(), self._sched(rounds - 1), t0
        )
        if on_round is not None:
            on_round(rec)
        if verbose:
            print(
                f"step {rounds - 1:4d}  loss {rec['loss']:.4f}  "
                f"lr {rec['lr']:.2e}  (resume already complete)"
            )
        return [rec]

    # -- run paths ----------------------------------------------------------

    def run(
        self,
        rounds: int,
        *,
        eval_every: int = 1,
        on_round: Callable[[dict], None] | None = None,
        ckpt_every: int = 0,
        ckpt_path: str = "",
        verbose: bool = False,
    ) -> list[dict]:
        """Per-round Python loop (jitted train step + eager engine.mix)."""
        from repro.data import tokens as tok
        from repro.optim import schedules

        self._sched = schedules.get(self.schedule_name, self.lr, rounds)
        if self.start_round >= rounds:
            return self._finished_resume(
                rounds, on_round, verbose, time.perf_counter()
            )
        evals = set(DecentralizedTrainer._eval_rounds(rounds, eval_every))
        cpts = self._ckpt_rounds(rounds, ckpt_every)
        trace = None
        if self.faulted:
            trace = self.engine.fault_trace
            trace.ensure(rounds)
        history: list[dict] = []
        t0 = time.perf_counter()
        for r in range(self.start_round, rounds):
            toks, labels = tok.round_token_batch(
                self.num_nodes, r, self.batch, self.seq, self.cfg.vocab_size,
                seed=self.seed, **self.data_kwargs,
            )
            toks, labels = jnp.asarray(toks), jnp.asarray(labels)
            lr = self._sched(r)
            if self.faulted:
                alive = jnp.asarray(trace.alive(r))
                self.params, self.opt_state, loss = self._train_faulted_jit(
                    alive, self.params, self.opt_state, toks, labels, lr
                )
                # Renormalized faulted mixing + the engine's internal
                # straggler buffer (one mix per round, in order).
                self.params = self.engine.mix(self.params, round=r)
            else:
                self.params, self.opt_state, loss = self._train_jit(
                    self.params, self.opt_state, toks, labels, lr
                )
                if self.compress is None:
                    self.params = self.engine.mix(self.params, round=r)
                elif self.engine.is_gossip_round(r):
                    self.cstate = self._compress_jit(self.params, self.cstate)
                    ref = self.cstate.reference
                    mixed = self.engine.mix(ref, round=r)
                    self.params = self._choco_apply_jit(self.params, mixed, ref)
            if r in evals:
                rec = self._round_record(r, loss, lr, t0)
                history.append(rec)
                if on_round is not None:
                    on_round(rec)
                if verbose:
                    print(
                        f"step {r:4d}  loss {rec['loss']:.4f}  "
                        f"lr {rec['lr']:.2e}  ({rec['wall_s']:.0f}s)"
                    )
            if r in cpts:
                self.save(ckpt_path, step=r)
        return history

    def run_fused(
        self,
        rounds: int,
        *,
        eval_every: int = 1,
        on_round: Callable[[dict], None] | None = None,
        ckpt_every: int = 0,
        ckpt_path: str = "",
        verbose: bool = False,
    ) -> list[dict]:
        """``run`` compiled into ``lax.scan`` chunks — one dispatch per
        eval/checkpoint boundary. Each chunk's token slab is generated on
        the host for just that chunk's rounds and staged as the scan's xs
        (never the full O(rounds·N·B·S) stream)."""
        if not self.supports_fused:
            raise ValueError(
                f"run_fused supports backends {_LM_FUSED_BACKENDS}, not "
                f"{self.mix_impl!r}; use run()"
            )
        from repro.data import tokens as tok
        from repro.optim import schedules

        self._sched = schedules.get(self.schedule_name, self.lr, rounds)
        if self.start_round >= rounds:
            return self._finished_resume(
                rounds, on_round, verbose, time.perf_counter()
            )
        program = self.engine.program(rounds, kind=self.mix_impl)
        hist = ()
        if self.faulted and self._has_hist:
            from repro.core import faults as faults_mod

            hist = faults_mod.init_history(self.params, program.delay_max + 1)
        evals = set(DecentralizedTrainer._eval_rounds(rounds, eval_every))
        cpts = self._ckpt_rounds(rounds, ckpt_every)
        # Chunks end at eval AND checkpoint rounds, so fused checkpoints
        # land at exact round boundaries (bit-identical resume).
        ends = sorted(evals | cpts)
        history: list[dict] = []
        t0 = time.perf_counter()
        prev = self.start_round - 1
        for end in ends:
            if end < self.start_round:
                continue
            start, length = prev + 1, end - prev
            prev = end
            toks, labels = tok.round_token_slab(
                self.num_nodes, range(start, end + 1), self.batch, self.seq,
                self.cfg.vocab_size, seed=self.seed, **self.data_kwargs,
            )
            (
                (self.params, self.opt_state, self.cstate, hist), losses
            ) = self._fused_chunk_jit(
                program, self.params, self.opt_state, self.cstate, hist,
                jnp.int32(start), jnp.asarray(toks), jnp.asarray(labels),
            )
            if end in evals:
                rec = self._round_record(
                    end, np.asarray(losses)[-1], self._sched(end), t0
                )
                history.append(rec)
                if on_round is not None:
                    on_round(rec)
                if verbose:
                    print(
                        f"step {end:4d}  loss {rec['loss']:.4f}  "
                        f"lr {rec['lr']:.2e}  ({rec['wall_s']:.0f}s)"
                    )
            if end in cpts:
                self.save(ckpt_path, step=end)
        return history
