#!/usr/bin/env python3
"""By hand, on the chip: what does the server's exact selection cost as
one flat pass over all d estimates, and as the two-level form (block
maxima -> k candidate blocks -> the flat form over k·block candidates)?

    python3 scripts/select_probe.py [--cells gpt2,joyai,nemotron]
        [--d 25600000,51200000] [--blocks 128,256,512,1024]
        [--k 50000] [--reps 5] [--seed 7]

For each cell's geometry (d, the 5 x 524,288 sketch) it makes a Gaussian
table from the seed, takes its tail-zeroed padded estimates with the
program's own ``CountSketch.estimates`` (the Pallas kernel on the chip:
medians of five table entries, so squares repeat across chunks and the
ties are the ones a round sees), and compiles ``CountSketch._select``'s
exact branch, ``threshold_topk_indices(est, k, key=square)`` followed by
the gather of the values, once flat (``coarse=0``) and once for each
block size. It asserts ``idx`` and ``vals`` of every two-level form
equal to the flat form's element for element, times ``--reps`` calls of
each after a warm-up, and prints each program's
``memory_analysis().temp_size_in_bytes`` and the time of the block
maxima alone (the one d-sized read the two-level form keeps). PR 33's
step 0 (PERF.md section 6): the two-level form is worth wiring only if
it takes the selection under 40 ms at d = 701M.
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# d of the benchmark's three LM cells (PERF.md section 4)
CELLS = {"gpt2": 124_444_417, "joyai": 376_091_904,
         "nemotron": 700_865_520}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="gpt2,joyai,nemotron")
    ap.add_argument("--d", default="",
                    help="further sizes, e.g. 25600000,51200000: "
                    "where the rule's ratio is read from")
    ap.add_argument("--blocks", default="128,256,512,1024")
    ap.add_argument("--k", type=int, default=50_000)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend there is")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from commefficient_tpu.ops.sketch import CountSketch
    from commefficient_tpu.ops.topk import (select_block,
                                            threshold_topk_indices)

    if not a.rehearse and jax.devices()[0].platform != "tpu":
        print("needs a TPU chip", file=sys.stderr)
        return 2
    cells = {c: CELLS[c] for c in a.cells.split(",") if c}
    cells.update({"d" + d: int(d) for d in a.d.split(",") if d})
    k, c_cols = a.k, 524_288
    if a.rehearse:
        cells = {c: d // 1024 for c, d in cells.items()}
        k, c_cols = 48, 1024
    blocks = [int(b) for b in a.blocks.split(",")]

    def rows_of_128(e, b):
        """The two-level form with a block of ``b`` = g adjacent rows of
        128: only the (d/128, 128) view of a 1-D array is free on the
        TPU, a (d/b, b) view is a d-sized relayout (5.6 GB of
        temporaries at 701M, compiled for a described v5e). What the
        program's own form would be at another block size; 128 won
        (PERF.md section 6, PR 33) and the program has only that."""
        g = b // 128
        x = e.reshape(-1, 128)
        m = jnp.max(jax.lax.bitcast_convert_type(
            jax.lax.square(x), jnp.uint32), axis=1)
        m = jax.lax.reduce_window(m, jnp.uint32(0), jax.lax.max,
                                  (g,), (g,), "VALID")
        cand = threshold_topk_indices(
            jax.lax.bitcast_convert_type(m, jnp.float32), k, coarse=0)
        rows = (cand[:, None] * g
                + jnp.arange(g, dtype=cand.dtype)).reshape(-1)
        pos = threshold_topk_indices(
            jax.lax.square(x[rows]).reshape(-1), k, coarse=0)
        return cand[pos // b] * b + pos % b

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        ms = []
        for _ in range(a.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ms.append(1e3 * (time.perf_counter() - t0))
        return statistics.median(ms), min(ms)

    out = {"device": jax.devices()[0].device_kind, "k": k, "cells": {}}
    for cell, d in cells.items():
        cs = CountSketch(d=d, c=c_cols, r=5)
        table = jax.random.normal(jax.random.PRNGKey(a.seed),
                                  (5, c_cols), jnp.float32)
        est = jax.block_until_ready(
            jax.jit(lambda t: cs.estimates(t, padded=True))(table))
        res = {"d": d, "padded_d": int(est.shape[0]),
               "rule_picks_block": select_block(int(est.shape[0]), k)}
        print(cell, json.dumps(res), flush=True)
        # the one d-sized read the two-level form keeps
        res["block_maxima_of_128_ms"] = timed(jax.jit(
            lambda e: jnp.max(jax.lax.bitcast_convert_type(
                jax.lax.square(e), jnp.uint32).reshape(-1, 128),
                axis=1)), est)[0]
        flat = None
        for b in [0] + blocks:
            def select(e, b=b):
                if b > 128:
                    idx = rows_of_128(e, b)
                else:
                    idx = threshold_topk_indices(
                        e, k, key=jax.lax.square, coarse=b)
                return idx, e[idx]
            exe = jax.jit(select).lower(est).compile()
            idx, vals = (np.asarray(v) for v in exe(est))
            if flat is None:
                flat = (idx, vals)
                assert (np.diff(idx) > 0).all() and idx[-1] < d
            else:
                np.testing.assert_array_equal(idx, flat[0])
                np.testing.assert_array_equal(vals, flat[1])
            med, best = timed(exe, est)
            row = {"ms_median": med, "ms_min": best,
                   "temp_bytes":
                       exe.memory_analysis().temp_size_in_bytes,
                   "equal_to_flat": True}
            res["flat" if not b else f"block_{b}"] = row
            print(cell, "flat" if not b else f"block_{b}",
                  json.dumps(row), flush=True)
        ties = int(k - np.unique(np.square(flat[1])).size)
        res["repeated_squares_among_selected"] = ties
        out["cells"][cell] = res
        del est
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "select_probe.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
