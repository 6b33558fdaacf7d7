"""The program names each layer of a DecAvg round (``repro/obs.py``).

Device scopes reach the compiled fused chunk's ``op_name`` metadata on every
fused backend; ``run_fused``'s host spans land in a profiler trace as
siblings, once per call, chunk and eval; its counters advance by the rounds
and the device-to-host transfers the code makes.
"""

import glob
import re
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import partition as P
from repro.data.loader import NodeLoader
from repro.train.trainer import DecentralizedTrainer

N_NODES, DIM = 8, 32
ROUND_SCOPES = ("decavg.batch", "decavg.local_grad", "decavg.sgd_update", "decavg.mix", "decavg.eval")
FAULTS = "churn:p_leave=0.15,p_join=0.5;straggler:frac=0.3,delay=3"


@pytest.fixture(scope="module")
def data():
    from repro.data.synthetic import make_mnist_like

    ds = make_mnist_like(train_per_class=40, test_per_class=10, dim=DIM, seed=0)
    return ds, P.iid(ds.y_train, N_NODES, seed=1)


def make_trainer(data, **kw):
    ds, parts = data
    loader = NodeLoader(ds.x_train, ds.y_train, parts, batch_size=8, seed=2)
    return DecentralizedTrainer(
        "ring:n=8", loader, lr=0.05, momentum=0.9, seed=0, in_dim=DIM, **kw
    )


def chunk_hlo(tr, ds) -> str:
    """The compiled text of ``tr``'s fused chunk of two rounds and an eval."""
    return tr.fused_chunk_hlo(3, eval_every=2, x_test=ds.x_test, y_test=ds.y_test)[2]


def has_scope(hlo: str, scope: str) -> bool:
    return re.search(r'op_name="[^"]*\b' + re.escape(scope) + r'[/"]', hlo) is not None


_COMPILED: dict = {}


@pytest.mark.parametrize(
    "case, scope",
    [("dense", s) for s in ROUND_SCOPES]
    + [("sparse", s) for s in ROUND_SCOPES]
    + [("dense_faulted", "decavg.fault_mask"), ("sparse_faulted", "decavg.fault_mask")],
)
def test_scope_in_compiled_chunk(data, case, scope):
    if case not in _COMPILED:
        backend, _, faulted = case.partition("_")
        tr = make_trainer(data, mix_impl=backend, faults=FAULTS if faulted else None)
        _COMPILED[case] = chunk_hlo(tr, data[0])
    assert has_scope(_COMPILED[case], scope), f"{scope} missing from the {case} chunk"


_SHARDED = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.core import partition as P
from repro.data.loader import NodeLoader
from repro.data.synthetic import make_mnist_like
from repro.train.trainer import DecentralizedTrainer
sys_path = {path!r}
import sys; sys.path.insert(0, sys_path)
from test_obs import chunk_hlo, has_scope

assert jax.device_count() == 4
ds = make_mnist_like(train_per_class=40, test_per_class=10, dim=32, seed=0)
parts = P.iid(ds.y_train, 16, seed=1)
for sched in ("ring", "allgather"):
    loader = NodeLoader(ds.x_train, ds.y_train, parts, batch_size=8, seed=2)
    tr = DecentralizedTrainer("ws:n=16,k=4,beta=0.2", loader, lr=0.05, momentum=0.9,
                              seed=0, in_dim=32, mix_impl="sparse_sharded")
    tr.engine.halo_schedule = sched
    hlo = chunk_hlo(tr, ds)
    for scope in ("decavg.halo_exchange", "decavg.mix", "decavg.sgd_update"):
        assert has_scope(hlo, scope), (sched, scope)
    assert re.search(r"(collective-permute|all-gather)[^\\n]*decavg.halo_exchange", hlo), sched
print("OK")
"""


def test_halo_exchange_scope_on_4_shards():
    """On ``sparse_sharded`` over four virtual devices, under both halo
    schedules, the collectives themselves carry ``decavg.halo_exchange``."""
    import os

    code = "import re\n" + textwrap.dedent(_SHARDED.format(path=os.path.dirname(__file__)))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=500)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.parametrize("rounds, eval_every, evals, lengths", [
    (5, 2, True, {1, 2}), (40, 40, True, {1, 39}), (6, 1, False, {6}),
])
def test_fused_chunk_hlo_one_text_per_chunk_length(data, rounds, eval_every, evals, lengths):
    ds, _ = data
    tr = make_trainer(data)
    kw = dict(eval_every=eval_every, x_test=ds.x_test, y_test=ds.y_test) if evals else {}
    before = [np.asarray(x) for x in jax.tree.leaves(tr.params)]
    texts = tr.fused_chunk_hlo(rounds, **kw)
    assert set(texts) == lengths
    assert all(t.startswith("HloModule jit__fused_chunk") for t in texts.values())
    # Compiling runs nothing: the trainer's parameters are still alive and unchanged.
    for a, b in zip(before, jax.tree.leaves(tr.params)):
        np.testing.assert_array_equal(a, np.asarray(b))


_WARM_CACHE = """
import contextlib, os, sys
import jax
jax.config.update("jax_compilation_cache_dir", {cache!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
sys.path.insert(0, {path!r})
import test_obs
from repro.core import partition as P
from repro.data.synthetic import make_mnist_like

ds = make_mnist_like(train_per_class=40, test_per_class=10, dim=32, seed=0)
data = (ds, P.iid(ds.y_train, 8, seed=1))
# The same chunk programs with every scope taken out, run into the cache.
named_scope = jax.named_scope
jax.named_scope = lambda name: contextlib.nullcontext()
test_obs.make_trainer(data).run_fused(3, eval_every=2, x_test=ds.x_test, y_test=ds.y_test)
jax.named_scope = named_scope
assert any("_fused_chunk" in f for f in os.listdir({cache!r}))
hlo = test_obs.chunk_hlo(test_obs.make_trainer(data), ds)
assert all(test_obs.has_scope(hlo, s) for s in test_obs.ROUND_SCOPES)
assert jax.config.jax_enable_compilation_cache
print("OK")
"""


def test_fused_chunk_hlo_reads_past_a_warm_cache(tmp_path):
    """The persistent compilation cache keys a program without its metadata:
    with a scope-less build of the same chunk programs cached, the text
    still carries every scope, and the cache is left on."""
    import os

    code = textwrap.dedent(_WARM_CACHE.format(cache=str(tmp_path), path=os.path.dirname(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def _spans(trace_dir) -> list[tuple[str, int, int]]:
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events if e.name.startswith("trainer.")]
    return sorted(out, key=lambda s: s[1])


@pytest.mark.parametrize("rounds, eval_every, evals", [(5, 2, True), (4, 4, True), (3, 1, False)])
def test_host_spans_in_profile(data, tmp_path, rounds, eval_every, evals):
    """Two calls under the profiler: one ``trainer.stage`` a call, one
    ``trainer.dispatch`` a chunk, one ``trainer.fetch`` an eval chunk, and
    no two of them overlap."""
    ds, _ = data
    tr = make_trainer(data)
    kw = dict(eval_every=eval_every, x_test=ds.x_test, y_test=ds.y_test) if evals else {}
    tr.run_fused(rounds, **kw)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            tr.run_fused(rounds, **kw)
        jax.block_until_ready(tr.params)
    spans = _spans(tmp_path)
    chunks = len(tr._eval_rounds(rounds, eval_every)) if evals else 1
    names = [s[0] for s in spans]
    assert names.count("trainer.stage") == 2
    assert names.count("trainer.dispatch") == 2 * chunks
    assert names.count("trainer.fetch") == (2 * chunks if evals else 0)
    assert set(names) <= {"trainer.stage", "trainer.dispatch", "trainer.fetch"}
    for (_, _, end), (_, start, _) in zip(spans, spans[1:]):
        assert end <= start
    # Each call stages first, then alternates dispatch and fetch.
    call = ["trainer.stage"] + ["trainer.dispatch", "trainer.fetch"][: 1 + evals] * chunks
    assert names == call * 2


@pytest.mark.parametrize(
    "groups, evals, d2h_per_chunk",
    [(True, True, 3), (False, True, 2), (True, False, 0)],
)
def test_counters_advance(data, groups, evals, d2h_per_chunk):
    """``trainer.rounds`` by the rounds run; ``trainer.d2h_transfers`` by one
    per fetched array: accuracies, consensus, and group accuracies when the
    trainer has class groups."""
    ds, _ = data
    kw = {"class_groups": np.arange(10) >= 5} if groups else {}
    tr = make_trainer(data, **kw)
    run = dict(eval_every=2, x_test=ds.x_test, y_test=ds.y_test) if evals else {}
    before = obs.counters()
    tr.run_fused(5, **run)
    after = obs.counters()
    chunks = 3 if evals else 1  # ends after rounds 0, 2 and 4

    def moved(k):
        return after.get(k, 0) - before.get(k, 0)

    assert moved("trainer.rounds") == 5
    assert moved("trainer.d2h_transfers") == d2h_per_chunk * chunks
