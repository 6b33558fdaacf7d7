"""The program's own names for what a DecAvg round does, and its counters.

Three kinds of name, all stable across refactors so a trace reduction can
find them:

- Device scopes (``jax.named_scope``; they only add HLO metadata, so each
  compiled op's ``op_name`` carries the innermost one):
  ``decavg.batch`` (index draw and batch gather), ``decavg.local_grad``
  (vmapped forward and backward; on a TPU, where the local-round kernel
  runs, the whole round's local steps with their updates and image
  fetches), ``decavg.sgd_update``,
  ``decavg.fault_mask`` (dead-node freeze, straggler snapshots),
  ``decavg.mix`` (every ``MixingProgram`` backend), ``decavg.halo_exchange``
  (the sharded mix's ppermute ring or all-gather, inside ``decavg.mix``) and
  ``decavg.eval`` (accuracies and consensus distance).
- Host spans (``jax.profiler.TraceAnnotation``, on the profiler's clock,
  a few hundred nanoseconds each when no profiler runs), siblings in
  ``DecentralizedTrainer.run_fused``: ``trainer.stage`` (mixing program,
  dataset and test set put on the device), ``trainer.dispatch`` (one fused
  chunk enqueued) and ``trainer.fetch`` (a chunk's metrics brought to the
  host).
- Counters, process-wide, read with ``counters()``: ``trainer.rounds``
  (rounds ``run_fused`` ran), ``trainer.local_kernel_rounds`` (those of
  them whose local steps ran in the Pallas kernel,
  ``kernels/local_round.py``, rather than XLA's scan) and
  ``trainer.d2h_transfers`` (blocking device-to-host conversions in its
  fetch).
"""

from __future__ import annotations

import collections

_COUNTS: collections.Counter = collections.Counter()


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the process-wide counter ``name``."""
    _COUNTS[name] += n


def counters() -> dict[str, int]:
    """A snapshot of every counter so far in this process."""
    return dict(_COUNTS)
