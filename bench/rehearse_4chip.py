"""Compile the four-chip cell's fused chunks for a described v5e:2x2.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python3 bench/rehearse_4chip.py --workload <four-chip cell>

No chip is needed. The cell's inputs and program staging are built on four
virtual CPU devices with a one-layer stand-in for the weights (so nothing
of the model's size is allocated here); then each chunk length the window
runs is lowered with the configuration's full-size parameter shapes,
sharded over the nodes of a described 2x2 v5e mesh, and compiled by the
TPU compiler. Prints each program's ``memory_analysis()`` per device as
one JSON line; the compiler refuses a program that does not fit.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import run  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--nodes", type=int, default=None, help="try another N")
    p.add_argument("--one-chip", action="store_true",
                   help="the same cohort on the one-device sparse backend, for one chip")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    import repro.models.mlp as mlp
    from repro.optim import sgd

    jax.config.update("jax_enable_compilation_cache", False)
    files = run.cell_files(args.workload)
    cfg, traffic = dict(files["config"]), files["traffic"]
    if args.nodes:
        cfg["nodes"] = args.nodes
    chips = int(files["cell"]["chips"])
    if args.one_chip:
        cfg["backend"], chips = "sparse", 1
    if not args.one_chip and len(jax.devices()) != chips:
        raise SystemExit(f"needs {chips} virtual devices (XLA_FLAGS), has {len(jax.devices())}")
    full_init = mlp.init_mlp
    mlp.init_mlp = lambda k, in_dim, hidden, num_classes: full_init(
        k, in_dim=in_dim, hidden=(8,), num_classes=num_classes)
    system = run.load_module("systems", cfg["system"])
    cell = system.Cell(cfg, traffic, args.seed)
    tr = cell.trainer
    program = tr.engine.program(cell.rounds, kind=tr.mix_impl)
    data = tr.loader.device_data()

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    axis = program.node_axis or "nodes"
    mesh = Mesh(np.array(topo.devices[:chips]), (axis,))
    rep = NamedSharding(mesh, P())

    def sds(x, sharding=rep):
        if not hasattr(x, "shape"):  # Python scalars in the staged program
            return x
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    program_t = jax.tree.map(
        sds, program if args.one_chip else dataclasses.replace(program, mesh=mesh))
    data_t = jax.tree.map(sds, data)
    shapes = jax.eval_shape(
        lambda k: full_init(k, in_dim=cell.dims[0], hidden=tuple(cell.dims[1:-1]),
                            num_classes=cell.dims[-1]),
        jax.random.PRNGKey(0))
    n = int(cfg["nodes"])
    node = lambda l: jax.ShapeDtypeStruct(  # noqa: E731
        (n,) + l.shape, l.dtype,
        sharding=NamedSharding(mesh, P(axis, *([None] * l.ndim))))
    params = jax.tree.map(node, shapes)
    opt = sgd.SGDState(momentum=params)
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    x_t = jax.ShapeDtypeStruct(cell.x_test.shape, jnp.float32, sharding=rep)
    y_t = jax.ShapeDtypeStruct(cell.y_test.shape, jnp.int32, sharding=rep)
    ends = system.eval_rounds(cell.rounds, cell.eval_every)
    lengths = sorted({b - a for a, b in zip([-1] + ends[:-1], ends)})
    for length in lengths:
        compiled = tr._fused_chunk_jit.lower(
            program_t, data_t, params, opt, None, (), start, x_t, y_t,
            length=length, do_eval=True,
        ).compile()
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        print(json.dumps({
            "workload": args.workload, "nodes": n, "chunk_rounds": length, "devices": chips,
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "generated_code_bytes": ma.generated_code_size_in_bytes,
            "collective_permutes": text.count("collective-permute-start"),
            "all_gathers": text.count("all-gather-start"),
            "halo_rows": None if program.sh_halo is None else int(program.sh_halo.shape[2]),
            "per_device_gb": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                              - ma.alias_size_in_bytes + ma.temp_size_in_bytes) / 1e9,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
