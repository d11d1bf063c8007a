#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide, run by hand:

    JAX_PLATFORMS=cpu python3 benchmark/tests/compile_v5e.py <cell> [<cell> ...]

Compiles a cell's client-round and server-round programs at the real
sizes for a described (not attached) TPU v5e chip, with the sketch
backend and rotation lanes forced to what the chip resolves them to,
and prints each program's memory analysis and Mosaic-kernel count.
Nothing runs; a compile that passes is not a chip run.
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(cells):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.flatten_util import ravel_pytree
    from jax.sharding import Mesh

    from benchmark import run as harness
    from commefficient_tpu.core.rounds import (ClientStates,
                                               build_client_round,
                                               build_server_round)
    from commefficient_tpu.core.server import ServerState
    from commefficient_tpu.ops.sketch import CountSketch
    from commefficient_tpu.parallel.mesh import client_sharding, replicated

    jax.config.update("jax_enable_compilation_cache", False)
    CountSketch._resolve_backend = lambda self: "pallas"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in cells:
        cell = harness.read_json(ROOT, "benchmark", "workloads",
                                 name + ".json")
        config = harness.read_json(ROOT, "benchmark", "configs",
                                   cell["config"] + ".json")
        n_dev = int(cell["chips"])
        mesh = Mesh(np.array(topo.devices[:n_dev]), ("clients",))
        builder = harness.load("builders", config["builder"])
        ref = harness.load("reference", config["reference"])
        args, loss_tree, params_shape, batch_shape = builder.abstract(
            cell, config, ref)
        zeros = jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, np.float32), params_shape)
        flat, unravel = ravel_pytree(zeros)
        args.grad_size = int(flat.size)
        args.sketch_rot_lanes = 1024 if args.grad_size >= 1 << 20 else 0
        W, rep = args.num_workers, replicated(mesh)
        bsh = client_sharding(mesh)

        def sds(shape, dtype=jnp.float32, sharding=None):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        batch = {k: sds(s.shape, s.dtype, bsh)
                 for k, s in batch_shape.items()}
        ps = sds((args.grad_size,), sharding=rep)
        table = sds(tuple(args.transmit_shape), sharding=rep)
        B = next(iter(batch_shape.values())).shape[1]
        client = jax.jit(build_client_round(
            args, None, B, mesh=mesh, tree_loss=loss_tree,
            unravel=unravel), donate_argnums=(1,)).lower(
            ps, ClientStates(None, None, None), batch,
            sds((W,), jnp.int32, rep), sds((2,), jnp.uint32),
            sds((), jnp.float32)).compile()
        server = jax.jit(build_server_round(args, mesh=mesh),
                         donate_argnums=(0, 1)).lower(
            ps, ServerState(table, table), table, sds(()), None,
            sds((W,), jnp.int32, rep), sds((2,), jnp.uint32)).compile()
        for what, exe in (("client", client), ("server", server)):
            ma = exe.memory_analysis()
            print(json.dumps({
                "cell": name, "program": what,
                "tpu_custom_call": exe.as_text().count("tpu_custom_call"),
                "argument_GB": ma.argument_size_in_bytes / 1e9,
                "output_GB": ma.output_size_in_bytes / 1e9,
                "temp_GB": ma.temp_size_in_bytes / 1e9,
                "code_MB": ma.generated_code_size_in_bytes / 1e6}))


if __name__ == "__main__":
    main(sys.argv[1:])
