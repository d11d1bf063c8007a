#!/usr/bin/env python3
"""By hand, on the chip: does a host-to-device copy issued from a second
thread run while a round program runs, or queue behind it?

    python3 scripts/h2d_overlap_probe.py [--workload resnet9_fetchsgd_w1250]
        [--seed 7] [--rounds 48] [--reps 2]

Assembles the cell as the benchmark's builder does and drives its round
loop (``next(loader)`` -> ``model(batch)`` -> ``opt.step()``) two ways,
in turn, ``--reps`` times each:

    inline   the loop as it is: the round's batch is fetched and placed
             on the round loop's thread, right before its program
    thread   a second thread fetches round r+1's batch and places its
             (W, ...) arrays (``shard_batch``) while round r runs; the
             round loop hands ``model`` arrays that are already on the
             device, for which its own placement is a no-op

and prints the median and mean round period of each. PR 28's step 0
(PERF.md section 6): the loader's read-ahead is worth building only if
``thread`` comes out shorter by about the copy's time.
"""

import argparse
import json
import os
import queue
import shutil
import statistics
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="resnet9_fetchsgd_w1250")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rounds", type=int, default=48)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from benchmark.run import Feed, load, read_json
    from commefficient_tpu.parallel.mesh import shard_batch

    cell = read_json(ROOT, "benchmark", "workloads", a.workload + ".json")
    config = read_json(ROOT, "benchmark", "configs",
                       cell["config"] + ".json")
    if a.rehearse:
        cell.update({k: v for k, v in cell["rehearse"].items()
                     if k != "data"})
    elif jax.devices()[0].platform != "tpu":
        print("needs a TPU chip", file=sys.stderr)
        return 2
    cell["num_devices"] = 1
    work = os.path.join(ROOT, "benchmark", ".cache", f"h2d-probe-{a.seed}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        run = load("builders", config["builder"]).build(
            cell, config, load("reference", config["reference"]),
            a.seed, work, rehearse=a.rehearse)
        feed = Feed(run.loader)
        mesh = run.model.mesh

        def fetch_placed():
            batch = feed.next()
            placed = shard_batch(mesh, jax.tree_util.tree_map(
                jnp.asarray, {k: v for k, v in batch.items()
                              if k not in ("client_ids", "mask")}))
            return {**batch, **placed}

        def inline(n):
            ends = []
            for _ in range(n):
                run.step(feed.next())
                ends.append(time.perf_counter())
            return ends

        def threaded(n):
            slot, want = queue.Queue(), threading.Semaphore(0)

            def produce():
                for _ in range(n):
                    want.acquire()
                    slot.put(fetch_placed())

            t = threading.Thread(target=produce, daemon=True)
            t.start()
            want.release()
            ends = []
            for _ in range(n):
                batch = slot.get()
                want.release()          # round r+1 is fetched and placed
                run.step(batch)         # while round r runs
                ends.append(time.perf_counter())
            t.join()
            return ends

        inline(3)                       # compiles, the ring, the pool
        out = {}
        for rep in range(a.reps):
            for name, loop in (("inline", inline), ("thread", threaded)):
                ends = loop(a.rounds)
                periods = [1e3 * (b - c) for b, c in zip(ends[1:], ends)]
                out.setdefault(name, []).append({
                    "median_ms": statistics.median(periods),
                    "mean_ms": statistics.fmean(periods),
                    "max_ms": max(periods)})
                print(name, rep, json.dumps(out[name][-1]), flush=True)
        print(json.dumps({"device": jax.devices()[0].device_kind,
                          "rounds": a.rounds, **out}))
        run.loader.close()
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
