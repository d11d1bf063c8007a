#!/usr/bin/env python3
"""Step 0 of a large configuration: what does the comparison that
decides ``correct`` (``lib/fetchsgd_ref.follow``) take on the chip?

    python3 benchmark/tests/follow_fit.py --config joyai-llm-flash-ep32 \
        [--set num_attention_heads=32 --set num_hidden_layers=9 ...] \
        [--clients 8 --batch 4 --seq 1024 --rounds 2] [--compile]

A process of its own, as empty as ``run.py`` leaves the chip before the
comparison: makes the reference's weights on the host (``--set``
overrides keys of the configuration, so no scratch file is needed),
follows one FetchSGD round on random ids (which compiles, into a cache
under ``benchmark/.cache``), then ``--rounds`` more from the start with
every program in that cache, and prints one JSON line: d, the device's
peak (in use + reserved), seconds a warm round, the host's peak (the
compiler's included). Needs a TPU; by hand, not a test.

``--compile`` needs none (``JAX_PLATFORMS=cpu``): it compiles the
comparison's three programs at that size for a described v5e chip and
prints each one's memory analysis. Nothing runs.
"""

import argparse
import json
import os
import resource
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def compile_for_v5e(fr, ref, config, shapes, batch, sk, k):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    tree = jax.tree_util.tree_map(lambda s: sds(s.shape), shapes)
    cb = {name: sds((1,) + x.shape[1:], x.dtype) for name, x in batch.items()}
    table = sds((sk.r, sk.c))
    nb, _ = fr._blocks(sk)
    with jax.default_matmul_precision("highest"):
        programs = {
            "client_step": fr.client_step(
                ref, config, fr.quantizer(None)).lower(tree, tree, cb),
            "add_block": fr._add_block.lower(
                sk, table, sds((nb, sk.c)), sds((nb, sk.r), jnp.int32),
                sds((), jnp.uint32)),
            "server": fr._server.lower(sk, k, 0.9, table, table, table)}
    for name, lowered in programs.items():
        t = time.perf_counter()
        ma = lowered.compile().memory_analysis()
        print(json.dumps({
            "program": name, "d": sk.d,
            "argument_GB": ma.argument_size_in_bytes / 1e9,
            "output_GB": ma.output_size_in_bytes / 1e9,
            "alias_GB": ma.alias_size_in_bytes / 1e9,
            "temp_GB": ma.temp_size_in_bytes / 1e9,
            "held_GB": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                        - ma.alias_size_in_bytes
                        + ma.temp_size_in_bytes) / 1e9,
            "compile_s": time.perf_counter() - t}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--set", action="append", default=[],
                    metavar="key=value", help="override a configuration key")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--compile", action="store_true")
    a = ap.parse_args()

    import jax
    import numpy as np
    from benchmark.lib import fetchsgd_ref as fr
    from benchmark.run import load, read_json

    config = read_json(ROOT, "benchmark", "configs", a.config + ".json")
    for item in a.set:
        key, value = item.split("=", 1)
        if key not in config:
            raise SystemExit(f"{a.config} has no key {key!r}")
        config[key] = json.loads(value)
    ref = load("reference", config["reference"])
    comp = config["compression"]
    rng = np.random.RandomState(3)
    batches = [{"input_ids": rng.randint(
        1, config["vocab_size"], (a.clients, a.batch, a.seq)).astype(np.int32),
        "mask": np.ones((a.clients, a.batch), np.float32)}
        for _ in range(a.rounds)]
    make = jax.jit(lambda k: ref.init_params(k, config))
    if a.compile:
        shapes = jax.eval_shape(make, jax.random.PRNGKey(7))
        d = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
        sk = fr.SketchSpec(d=d, c=comp["num_cols"], r=comp["num_rows"],
                           seed=21)
        compile_for_v5e(fr, ref, config, shapes, batches[0], sk,
                        min(comp["k"], d))
        return 0

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX sees {dev.platform}")
    t = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        params = jax.tree_util.tree_map(np.asarray,
                                        make(jax.random.PRNGKey(7)))
    d = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    print(f"d = {d}; weights made on the host in "
          f"{time.perf_counter() - t:.1f} s; before follow:",
          json.dumps(dev.memory_stats()), flush=True)
    sk = fr.SketchSpec(d=d, c=comp["num_cols"], r=comp["num_rows"], seed=21)
    # each ``follow`` jits anew: the second finds the first's programs
    jax.config.update("jax_compilation_cache_dir", os.path.join(
        ROOT, "benchmark", ".cache", "follow_fit_jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    def follow(n):
        t = time.perf_counter()
        out = fr.follow(ref=ref, spec_model=config, params=params,
                        batches=batches[:n], lrs=[0.01] * n,
                        hyper={"k": comp["k"], "rho": 0.9,
                               "weight_decay": comp["weight_decay"],
                               "num_workers": a.clients}, sk=sk)
        return out, time.perf_counter() - t

    _, first = follow(1)
    out, took = follow(a.rounds)
    stats = dev.memory_stats()
    print("after follow:", json.dumps(stats))
    in_use = stats.get("peak_bytes_in_use", 0)
    reserved = stats.get("peak_bytes_reserved", 0)
    print(json.dumps({
        "config": a.config, "set": a.set, "d": d,
        "tokens_a_round": a.clients * a.batch * a.seq,
        "device_peak_in_use_GB": in_use / 1e9,
        "device_peak_reserved_GB": reserved / 1e9,
        "device_peak_GB": (in_use + reserved) / 1e9,
        "device_limit_GB": stats.get("bytes_limit", 0) / 1e9,
        "bytes_a_parameter": (in_use + reserved) / d,
        "first_round_with_compiling_s": first, "rounds": a.rounds,
        "follow_s": took, "seconds_a_round": took / a.rounds,
        "host_peak_GB": resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9,
        "losses_round0": [float(x) for x in out["losses"][0]],
        "changed": int(np.count_nonzero(out["delta"]))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
