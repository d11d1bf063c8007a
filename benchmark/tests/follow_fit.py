#!/usr/bin/env python3
"""Step 0 of a large configuration: does ``lib/fetchsgd_ref.follow`` fit
the chip beside what the program leaves there?

    python3 benchmark/tests/follow_fit.py --config joyai-llm-flash-ep32 \
        [--clients 8 --batch 4 --seq 1024] [--device_params]

Holds a float32 vector of d (the program's ``ps_weights``) and two
sketch tables on the device, makes the reference's weights (on the host
unless ``--device_params``), follows one FetchSGD round on random ids
and prints the allocator's peak. Needs a TPU; by hand, not a test.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--rounds", type=int, default=1)
    ap.add_argument("--device_params", action="store_true")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.lib import fetchsgd_ref as fr
    from benchmark.run import load, read_json

    config = read_json(ROOT, "benchmark", "configs", a.config + ".json")
    ref = load("reference", config["reference"])
    dev = jax.devices()[0]
    print("device:", dev.platform, dev.device_kind)
    key = jax.random.PRNGKey(7)
    if a.device_params:
        params = jax.jit(lambda k: ref.init_params(k, config))(key)
    else:
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            params = jax.tree_util.tree_map(
                np.asarray, jax.jit(lambda k: ref.init_params(k, config))(key))
    d = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    comp = config["compression"]
    # what the program keeps alive through the comparison
    ps_weights = jnp.zeros((d,), jnp.float32) + 1.0
    tables = jnp.zeros((2, comp["num_rows"], comp["num_cols"]), jnp.float32)
    jax.block_until_ready((ps_weights, tables))
    print(f"d = {d}; before follow:", json.dumps(dev.memory_stats()))
    rng = np.random.RandomState(3)
    batches = [{"input_ids": rng.randint(
        1, config["vocab_size"], (a.clients, a.batch, a.seq)).astype(np.int32),
        "mask": np.ones((a.clients, a.batch), np.float32)}
        for _ in range(a.rounds)]
    sk = fr.SketchSpec(d=d, c=comp["num_cols"], r=comp["num_rows"], seed=21)
    t = time.perf_counter()
    out = fr.follow(ref=ref, spec_model=config, params=params,
                    batches=batches, lrs=[0.01] * a.rounds,
                    hyper={"k": comp["k"], "rho": 0.9,
                           "weight_decay": comp["weight_decay"],
                           "num_workers": a.clients}, sk=sk)
    stats = dev.memory_stats()
    print(f"follow took {time.perf_counter() - t:.1f} s; losses",
          [float(x) for x in out["losses"][0]])
    print("after follow:", json.dumps(stats))
    peak = stats.get("peak_bytes_in_use", 0)
    print(f"follow_peak_bytes {peak} ({peak / 1e9:.2f} GB of "
          f"{stats.get('bytes_limit', 0) / 1e9:.2f})")
    print("changed coordinates:", int(np.count_nonzero(out["delta"])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
