"""§Perf optimization correctness: sparse permute gossip, int8 KV cache,
manual pipeline-parallel decode (subprocess with fake devices)."""

import subprocess
import sys
import textwrap

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cfgbase
from repro.models import transformer as TF


def test_int8_kv_cache_close_to_bf16():
    """int8 decode logits stay within quantization tolerance of exact."""
    cfg = cfgbase.get("llama32_1b").reduced()
    params = TF.init_params(jax.random.PRNGKey(0), cfg)
    B, T = 2, 12
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    exact = TF.init_cache(cfg, B, T)
    quant = TF.init_cache(cfg, B, T, kv_quant=True)
    errs = []
    for t in range(T):
        le, exact = TF.decode_step(params, cfg, tokens[:, t], exact)
        lq, quant = TF.decode_step(params, cfg, tokens[:, t], quant)
        errs.append(float(jnp.max(jnp.abs(le - lq))))
    scale = float(jnp.max(jnp.abs(le)))
    assert max(errs) < 0.05 * max(scale, 1.0), f"int8 err {max(errs)} vs scale {scale}"


def test_sparse_gossip_equals_dense_subprocess():
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import topology as T, mixing as M, decavg as D
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        g = T.erdos_renyi(4, 0.6, seed=2)
        sizes = np.array([3.0, 1.0, 2.0, 4.0])
        w = jnp.asarray(M.decavg_matrix(g, sizes), jnp.float32)
        colors = M.edge_coloring(g)
        params = {"a": jax.random.normal(jax.random.PRNGKey(0), (4, 9, 5))}
        dense = D.mix_dense(w, params)
        sparse = D.mix_permute(w, params, colors, mesh=mesh, node_axis="data")
        np.testing.assert_allclose(np.asarray(sparse["a"]), np.asarray(dense["a"]),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
        """
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_edge_coloring_is_proper():
    from hypothesis import given, settings, strategies as st

    from repro.core import mixing as M, topology as T

    @given(st.integers(4, 24), st.floats(0.1, 0.9), st.integers(0, 1000))
    @settings(max_examples=15, deadline=None)
    def inner(n, p, seed):
        g = T.erdos_renyi(n, p, seed=seed)
        colors = M.edge_coloring(g)
        seen = set()
        for pairs in colors:
            srcs = [s for s, _ in pairs]
            dsts = [d for _, d in pairs]
            assert len(set(srcs)) == len(srcs), "color class has duplicate sources"
            assert len(set(dsts)) == len(dsts), "color class has duplicate dests"
            seen.update((s, d) for s, d in pairs)
        # every edge covered in both directions
        ii, jj = np.nonzero(g.adj)
        assert seen == {(int(a), int(b)) for a, b in zip(ii, jj)}

    inner()


def test_manual_pipeline_matches_decode_subprocess():
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import base as cfgbase
        from repro.models import transformer as TF
        from repro.serve import pipeline_manual as PM
        cfg = dataclasses.replace(
            cfgbase.get("llama32_1b").reduced(),
            num_layers=4, num_heads=4, num_kv_heads=2, head_dim=32,
            d_model=128, d_ff=256, vocab_size=512)
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        params = TF.init_params(jax.random.PRNGKey(0), cfg)
        B, T = 4, 16
        ref_cache = TF.init_cache(cfg, B, T, kv_quant=True)
        tok = jnp.array([1, 2, 3, 4], jnp.int32)
        refs, t = [], tok
        for _ in range(4):
            logits, ref_cache = TF.decode_step(params, cfg, t, ref_cache)
            t = jnp.argmax(logits, -1).astype(jnp.int32)
            refs.append(t)
        step = PM.build_manual_pipeline_step(cfg, mesh)
        cache = PM.init_kv_cache(cfg, B, T, tp=2)
        t = tok
        for i in range(4):
            t, cache = jax.jit(step)(params, t, cache)
            assert np.array_equal(np.asarray(t), np.asarray(refs[i])), i
        print("OK")
        """
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=400)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_gpipe_microbatch_slice_and_write():
    """The shared GPipe helpers are plain JAX — testable without shard_map."""
    from repro.serve import gpipe

    def is_index(path):
        last = path[-1]
        return str(getattr(last, "key", last)) == "index"

    tree = {
        "k": jnp.arange(24, dtype=jnp.float32).reshape(2, 4, 3),
        "index": jnp.array([5, 5], jnp.int32),
    }
    sub = gpipe.microbatch_slice(tree, 1, 2, skip=is_index)
    np.testing.assert_array_equal(np.asarray(sub["k"]), np.asarray(tree["k"][:, 2:4]))
    np.testing.assert_array_equal(np.asarray(sub["index"]), [5, 5])  # passed whole

    new = {"k": jnp.full((2, 2, 3), -1.0), "index": jnp.array([9, 9], jnp.int32)}
    wrote = gpipe.microbatch_write(tree, new, 1, 2, jnp.asarray(True), skip=is_index)
    np.testing.assert_array_equal(np.asarray(wrote["k"][:, 2:4]), np.asarray(new["k"]))
    np.testing.assert_array_equal(np.asarray(wrote["k"][:, :2]), np.asarray(tree["k"][:, :2]))
    np.testing.assert_array_equal(np.asarray(wrote["index"]), [5, 5])  # skip wins

    # the warm-up/drain bubble: inactive ticks keep the old rows
    kept = gpipe.microbatch_write(tree, new, 1, 2, jnp.asarray(False), skip=is_index)
    np.testing.assert_array_equal(np.asarray(kept["k"]), np.asarray(tree["k"]))


def test_pipeline_entry_point_dispatch():
    """build_pipeline_step validates configs for both variants up front."""
    from repro.serve import pipeline as PL

    cfg = cfgbase.get("whisper_base").reduced()
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="decoder-only"):
        PL.build_pipeline_step(cfg, mesh)
    with pytest.raises(ValueError, match="dense decoder-only"):
        PL.build_pipeline_step(cfg, mesh, manual=True)
