"""Small runs of a real cell on the CPU, for the tests."""

import json
import os
import subprocess
import sys

import run


def small_files(cell: str, nodes: int, sharded: bool = False) -> dict:
    """The cell's files with fewer nodes and images; widths and limits as
    the cell has them. ``sharded`` puts the cohort on the node-sharded
    backend over four devices."""
    files = run.cell_files(cell)
    cfg = dict(files["config"])
    cfg.update(nodes=nodes, data={"train_per_class": 40, "test_per_class": 20})
    if sharded:
        cfg.update(backend="sparse_sharded", sparse_p_chunk="auto", chips=4)
        files["cell"] = dict(files["cell"], chips=4)
    files["config"] = cfg
    return files


def run_small(cell: str, nodes: int, seed: int = 3, sharded: bool = False) -> dict:
    """A run on whatever devices JAX has: the look for a TPU is skipped, the
    compile cache left alone, and the first device's kind given the peaks
    of the cell's chip."""
    import jax

    args = run.parse_args(["--workload", cell, "--seed", str(seed), "--seconds", "0.2"])
    files = small_files(cell, nodes, sharded)
    files["peaks"] = {jax.devices()[0].device_kind: files["peaks"]["TPU v5 lite"]}
    saved = run.check_devices, run.use_compile_cache
    run.check_devices = lambda chips, peaks: jax.devices()
    run.use_compile_cache = lambda: None
    try:
        return run.run(args, files=files)
    finally:
        run.check_devices, run.use_compile_cache = saved


def run_small_on_4_devices(cell: str, nodes: int, patch: str = "") -> dict:
    """``run_small`` of the cell on the node-sharded backend, in a child
    process with four virtual CPU devices; ``patch`` is Python run in the
    child before the cell."""
    code = (
        "import json, sys\n"
        f"sys.path[:0] = {[str(run.BENCH), str(run.ROOT / 'src'), os.path.dirname(__file__)]!r}\n"
        "import jax, jax.numpy as jnp\n"
        f"{patch}\n"
        "from helpers import run_small\n"
        f"print(json.dumps(run_small({cell!r}, {nodes}, sharded=True)))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=900, cwd=run.ROOT)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])
