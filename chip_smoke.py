"""Smoke run of the system's main paths on a TPU, at full width.

    python chip_smoke.py             # one chip: gossip training, kernels, serving
    python chip_smoke.py --chips 4   # node-sharded gossip over four chips only

One process, phases in order; a failed check raises and the script exits
non-zero without printing a result line.

1. device    — refuses to run unless JAX's first device is a TPU.
2. gossip    — the ``paper`` preset's ``ba:n=100,m=2`` x ``hub_focused``
               spec through ``experiments.runner.run_spec``: the paper MLP
               (784-512-256-128-10) on 100 nodes, fused ``run_fused`` path,
               6 rounds evaluated every 2. Accuracy and ``g2_acc_spread``
               must be finite.
3. kernels   — each Pallas kernel once at real widths against its XLA
               reference; each compiled program must hold the kernel
               (``tpu_custom_call``), not a fallback.
4. serving   — ``serve.engine.Engine`` over unreduced llama3.2-1b in bf16,
               weights from ``TF.init_params`` and a seed; four prompts of
               mixed length up to 512, 16 new tokens each.

``--chips 4`` runs only the ``large_n`` preset's ``ba:n=4096,m=2`` x
``hub_focused`` spec on ``sparse_sharded`` over a 4-device node mesh and the
same spec on ``sparse`` on one device, and compares the trained parameters.

The last line of standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "results"  # gitignored, like the sweep stores
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def device_check(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: found no TPU (JAX's first device is {d.platform!r}); "
            "this script measures nothing off the chip"
        )
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def param_count(init) -> int:
    import jax
    import numpy as np

    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))


def holds_kernel(compiled, name: str) -> None:
    require(
        "tpu_custom_call" in compiled.as_text(),
        f"{name}: the compiled program holds no tpu_custom_call — the Pallas "
        "kernel was not lowered for the chip",
    )


def check_close(name: str, got, want, tol: float) -> None:
    import jax.numpy as jnp

    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))
    log(f"{name}: max_abs_err={err!r} tol={tol!r}")
    require(err <= tol, f"{name}: max abs error {err} exceeds {tol}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_gossip() -> None:
    import math

    from repro.experiments import presets, runner
    from repro.experiments.store import ResultsStore

    spec = next(
        s for s in presets.get_preset("paper")
        if s.topology == "ba:n=100,m=2" and s.partitioner == "hub_focused"
        and s.seed == SEED
    )
    spec = dataclasses.replace(spec, rounds=6, eval_every=2)
    OUT.mkdir(exist_ok=True)
    path = OUT / "chip_smoke_gossip.jsonl"
    path.unlink(missing_ok=True)
    store = ResultsStore(str(path))
    res = runner.run_spec(spec, store, verbose=True)
    final = res["final"]
    require(
        final["fused"] and final["backend"] == "dense",
        f"gossip: expected the fused dense path, got {final['backend']} "
        f"fused={final['fused']}",
    )
    curve = store.curves(res["run_id"])
    for rec in curve:
        log(
            f"gossip round {rec['round']}: mean_acc={rec['mean_acc']!r} "
            f"g2_acc_spread={rec['g2_acc_spread']!r} wall_s={rec['wall_s']!r}"
        )
        for key in ("mean_acc", "g2_acc_spread"):
            v = rec[key]
            require(
                v is not None and math.isfinite(v),
                f"gossip round {rec['round']}: {key}={v!r} is not finite",
            )
    # The first chunk (round 0) compiles the length-1 chunk program; the
    # next one (rounds 1-2) compiles the length-2 program, the rest reuse.
    for a, b in zip(curve, curve[1:]):
        log(
            f"gossip chunk rounds {a['round'] + 1}-{b['round']}: "
            f"{b['wall_s'] - a['wall_s']!r} s"
        )
    log(
        f"gossip rounds {curve[0]['round'] + 1}-{curve[-1]['round']} after the "
        f"first chunk: {curve[-1]['wall_s'] - curve[0]['wall_s']!r} s"
    )


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import base as cfgbase
    from repro.configs.paper_mlp import CONFIG as PAPER_MLP
    from repro.core import decavg, sparse
    from repro.core import topology as T
    from repro.experiments import presets
    from repro.kernels import ops, ref
    from repro.models.mlp import init_mlp

    key = jax.random.PRNGKey(SEED)

    # Flash attention at llama3.2-1b heads (32 query / 8 kv heads, hd 64).
    # bf16 in and out: the bound is tests/test_kernels.py's bf16 tolerance —
    # one bf16 ulp of an O(1) output (2^-7) plus bf16 rounding of the
    # softmax weights against |v| <= ~5 (2^-9 * 5) sit well inside it.
    cfg = cfgbase.get("llama3.2-1b")
    s = 1024
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, s, cfg.num_heads, cfg.head_dim), jnp.bfloat16)
    k = jax.random.normal(kk, (1, s, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16)
    v = jax.random.normal(kv, (1, s, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16)
    flash = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal=True))
    compiled = flash.lower(q, k, v).compile()
    holds_kernel(compiled, "flash_attention")
    t0 = time.perf_counter()
    got = compiled(q, k, v).block_until_ready()
    log(f"flash_attention S=T={s} run: {time.perf_counter() - t0!r} s")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda q, k, v: ref.flash_attention_ref(q[0], k[0], v[0])[None])(
            q, k, v
        )
    check_close("flash_attention", got, want, 3e-2)

    # The f32 gossip kernels: the MXU may take a single bf16 pass over f32
    # operands (relative rounding 2^-9 on each factor). Rows of W are
    # non-negative and sum to 1, so |err| <= 2 * 2^-9 * max|P| — the bound.
    # References run at "highest" precision (true f32).
    eng = decavg.GossipEngine("ba:n=100,m=2", seed=SEED)
    w = eng.w
    n = PAPER_MLP.num_nodes
    p = param_count(lambda k: init_mlp(
        k, in_dim=PAPER_MLP.in_dim, hidden=PAPER_MLP.hidden,
        num_classes=PAPER_MLP.num_classes,
    ))
    x = jax.random.normal(jax.random.fold_in(key, 1), (n, p), jnp.float32)
    mix = jax.jit(lambda w, x: ops.gossip_mix(w, x))
    compiled = mix.lower(w, x).compile()
    holds_kernel(compiled, "gossip_mix")
    t0 = time.perf_counter()
    got = compiled(w, x).block_until_ready()
    log(f"gossip_mix N={n} P={p} run: {time.perf_counter() - t0!r} s")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.gossip_mix_ref)(w, x)
    check_close("gossip_mix", got, want, 2 * 2.0**-9 * float(jnp.max(jnp.abs(x))))
    del x, got, want

    spec = next(
        s for s in presets.get_preset("large_n") if s.backend == "sparse_sharded"
    )
    g = T.make(spec.topology, seed=spec.seed)
    csr = sparse.csr_from_graph(g)
    bell = sparse.block_ell_from_csr(csr)
    p = param_count(lambda k: init_mlp(k, hidden=tuple(spec.model["hidden"])))
    x = jax.random.normal(jax.random.fold_in(key, 2), (g.num_nodes, p), jnp.float32)
    idx, val = jnp.asarray(bell.idx), jnp.asarray(bell.val)
    mix = jax.jit(lambda i, v, x: ops.gossip_mix_sparse_blocked(i, v, x))
    compiled = mix.lower(idx, val, x).compile()
    holds_kernel(compiled, "blocked ELL")
    t0 = time.perf_counter()
    got = compiled(idx, val, x).block_until_ready()
    log(
        f"blocked ELL {spec.topology} P={p} (NB={bell.num_blocks}, "
        f"KB={bell.max_blocks_per_row}) run: {time.perf_counter() - t0!r} s"
    )
    want = sparse.mix_sparse(csr, x, p_chunk=sparse.auto_p_chunk(csr.nnz))
    check_close("blocked ELL", got, want, 2 * 2.0**-9 * float(jnp.max(jnp.abs(x))))


def phase_serving() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import base as cfgbase
    from repro.kernels import ops
    from repro.models import transformer as TF
    from repro.serve import decode as SD
    from repro.serve.engine import Engine

    cfg = cfgbase.get("llama3.2-1b")
    require(cfg.param_dtype == "bfloat16", f"expected bf16 params, got {cfg.param_dtype}")
    t0 = time.perf_counter()
    params = jax.jit(TF.init_params, static_argnums=1)(jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    log(f"serving: llama3.2-1b params {nbytes} bytes, init {time.perf_counter() - t0!r} s")

    cache_len, max_new = 1024, 16
    lengths = (512, 300, 77, 9)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32) for n in lengths]

    # The engine's flash="auto" takes the kernel here; its prefill program
    # must hold it, and agree with the reference attention path. bf16 keeps
    # 8 significant bits (2^-8 per rounding); the two attention paths round
    # at different points in each of 16 layers, so the last-token logits may
    # differ by a few percent in L2 — a wrong kernel misses by O(1).
    require(ops.on_tpu() and SD.flash_ok(cfg), "flash='auto' would not take the kernel")
    row = TF.init_cache(cfg, 1, cache_len, per_slot=True)
    tok = jnp.asarray(prompts[0][None])
    length = jnp.array([lengths[0]], jnp.int32)
    lowered = TF.prefill_forward.lower(
        params, cfg, tok, row, length=length, memory=None, window=None, flash=True
    )
    holds_kernel(lowered.compile(), "prefill (flash)")
    lg_flash, _ = SD.prefill(params, cfg, tok, row, length=length, flash=True)
    lg_ref, _ = SD.prefill(params, cfg, tok, row, length=length, flash=False)
    rel = float(jnp.linalg.norm(lg_flash - lg_ref) / jnp.linalg.norm(lg_ref))
    log(f"prefill flash vs reference: rel_l2_err={rel!r} tol=0.05")
    require(bool(jnp.all(jnp.isfinite(lg_flash))), "prefill logits not finite")
    require(rel <= 0.05, f"prefill flash vs reference: rel L2 {rel} > 0.05")
    t0 = time.perf_counter()
    SD.prefill(params, cfg, tok, row, length=length, flash=True)[0].block_until_ready()
    log(f"prefill {lengths[0]} tokens (flash, compiled): {time.perf_counter() - t0!r} s")

    eng = Engine(params, cfg, slots=len(prompts), cache_len=cache_len)
    first = None
    for label in ("warm-up (compiles)", "timed"):
        rids = [eng.submit(p, max_new=max_new) for p in prompts]
        steps = []
        done = {}
        while True:
            t0 = time.perf_counter()
            events = eng.step()  # host-synchronous: tokens land in numpy
            if not events:
                break
            steps.append(time.perf_counter() - t0)
            for ev in events:
                if ev["done"]:
                    done[ev["rid"]] = True
        out = eng.run()
        require(sorted(out) == sorted(rids) == sorted(done), f"{label}: lost requests")
        for rid, n in zip(rids, lengths):
            toks = out[rid]
            require(
                toks.shape == (max_new,) and bool(np.all((toks >= 0) & (toks < cfg.vocab_size))),
                f"{label}: request {rid} returned {toks!r}",
            )
            log(f"serving {label}: request {rid} prompt {n} -> {toks.tolist()}")
        # Greedy decoding of the same prompts must repeat itself exactly.
        got = [out[rid].tolist() for rid in rids]
        require(first is None or got == first, f"{label}: tokens differ from warm-up")
        first = got
        decode = sorted(steps[1:])
        log(
            f"serving {label}: first step (admits {len(prompts)} prompts + one "
            f"decode step) {steps[0]!r} s; decode steps {len(decode)}, median "
            f"{decode[len(decode) // 2]!r} s ({len(prompts)} slots per step)"
        )


def phase_sharded(chips: int) -> None:
    import jax
    import numpy as np

    from repro.experiments import presets, runner

    # Rounds kept short: the two runs must see the same arithmetic, and
    # SGD amplifies any reordering round over round.
    spec = next(
        s for s in presets.get_preset("large_n") if s.backend == "sparse_sharded"
    )
    spec = dataclasses.replace(spec, rounds=3, eval_every=1)
    params = {}
    for backend in ("sparse_sharded", "sparse"):
        trainer, ds, _ = runner.build_mlp_trainer(
            dataclasses.replace(spec, backend=backend)
        )
        if backend == "sparse_sharded":
            mesh = trainer.engine.mesh
            require(
                mesh.devices.size == chips,
                f"node mesh spans {mesh.devices.size} devices, not {chips}",
            )
        t0 = time.perf_counter()
        hist = trainer.run_fused(
            spec.rounds, eval_every=spec.eval_every, x_test=ds.x_test, y_test=ds.y_test
        )
        log(f"{backend}: {spec.topology} {spec.rounds} rounds in {time.perf_counter() - t0!r} s")
        for m in hist:
            log(f"{backend} round {m.round}: mean_acc={m.mean_acc!r} std_acc={m.std_acc!r}")
            require(np.isfinite(m.mean_acc), f"{backend}: accuracy not finite")
        params[backend] = [np.asarray(leaf) for leaf in jax.tree.leaves(trainer.params)]
    # Same spec and seed; the sharded run sums each row's halo entries in the
    # same CSR order, but the per-node SGD steps run as 1024- instead of
    # 4096-node batches, and the compiler may tile those matmuls differently
    # (f32 accumulation order, ~1e-7 relative per op). Three rounds of SGD
    # at lr 0.05 / momentum 0.9 keep that far below 1e-4 on O(0.1) weights.
    tol = 1e-4
    err = max(
        float(np.max(np.abs(a - b)))
        for a, b in zip(params["sparse_sharded"], params["sparse"])
    )
    log(f"sparse_sharded ({chips} chips) vs sparse (1 chip): max_abs_param_diff={err!r} tol={tol!r}")
    require(err <= tol, f"sharded vs one-device params differ by {err} > {tol}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="1: the one-chip phases; 4: only the node-sharded gossip path",
    )
    args = ap.parse_args()
    device = device_check(args.chips)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import machine

    log(f"compile cache: {machine.use_compile_cache()}")
    phases = (
        [("sharded", lambda: phase_sharded(args.chips))] if args.chips == 4
        else [("gossip", phase_gossip), ("kernels", phase_kernels),
              ("serving", phase_serving)]
    )
    for name, fn in phases:
        t0 = time.perf_counter()
        log(f"== phase {name}")
        fn()
        log(f"== phase {name} ok in {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
