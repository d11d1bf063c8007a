#!/usr/bin/env python3
"""By hand, on the chip: what does it cost or save to keep two programs
enqueued, client r+1 behind server r?

    python3 scripts/defer_support_probe.py [--workload resnet9_fetchsgd_w1250]
        [--seed 7] [--rounds 48] [--reps 2]

Assembles the cell as the benchmark's builder does and drives its round
loop (``next(loader)`` -> ``model(batch)`` -> ``opt.step()``) two ways,
in turn, ``--reps`` times each, on the same device programs:

    at_once   the server update's support is settled right after
              ``opt.step()`` (``FedModel.settle_update``): the wait for
              the server program, the copy and the unpacking sit between
              the two dispatches, and no two programs are ever enqueued
              (the loop as it was until PR 30)
    deferred  the loop as it is: the support is settled after the next
              round's program has been dispatched

and prints the median and mean round period of each, the rounds of over
a second with where each went, and the device's
memory peak (``peak_bytes_in_use`` + ``peak_bytes_reserved``, as the
benchmark reads it) after each leg; peaks only rise within a process, so
``at_once`` runs first and what ``deferred`` adds shows as a step. PR
30's step 0 (PERF.md section 6): the deferral is worth building only if
the peak does not move and no cell fails to allocate.
"""

import argparse
import json
import os
import resource
import shutil
import statistics
import sys
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
    import numpy as np
    from benchmark.run import Feed, load, read_json

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
    work = os.path.join(ROOT, "benchmark", ".cache",
                        f"defer-probe-{a.seed}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        run = load("builders", config["builder"]).build(
            cell, config, load("reference", config["reference"]),
            a.seed, work, rehearse=a.rehearse)
        feed = Feed(run.loader)
        model = run.model

        real_note = type(model).note_update
        waits = [0.0]

        def note_update(self, support=None):
            # the wait for the support to reach the host, apart from the
            # host's own work on it
            t0 = time.perf_counter()
            for leaf in jax.tree_util.tree_leaves(support):
                np.asarray(leaf)
            waits[0] = time.perf_counter() - t0
            return real_note(self, support)

        type(model).note_update = note_update

        def host_counts():
            ru = resource.getrusage(resource.RUSAGE_SELF)
            return (ru.ru_minflt, ru.ru_majflt, ru.ru_nvcsw, ru.ru_nivcsw,
                    ru.ru_utime, ru.ru_stime)

        def loop(n, at_once):
            """Round ends, and for each round of over a second (the rare
            long round, PERF.md section 6) where it went: the fetch, the
            step (``model(batch)`` + ``opt.step()``), within it the wait
            for the support, and what the process did meanwhile (minor and
            major faults, voluntary and forced context switches, user
            and system seconds of all its threads)."""
            ends, long_ = [], []
            for i in range(n):
                c0, t0 = host_counts(), time.perf_counter()
                batch = feed.next()
                t1 = time.perf_counter()
                run.step(batch)
                if at_once:
                    model.settle_update()
                t2 = time.perf_counter()
                ends.append(t2)
                if t2 - t0 > 1.0:
                    long_.append({
                        "round": i, "fetch_ms": round(1e3 * (t1 - t0)),
                        "step_ms": round(1e3 * (t2 - t1)),
                        "support_wait_ms": round(1e3 * waits[0]),
                        "minflt,majflt,nvcsw,nivcsw,utime,stime": [
                            round(b - c, 3) for b, c in
                            zip(host_counts(), c0)]})
            return ends, long_

        def peak_gb():
            s = jax.devices()[0].memory_stats() or {}
            return (s.get("peak_bytes_in_use", 0)
                    + s.get("peak_bytes_reserved", 0)) / 1e9

        loop(3, at_once=True)           # compiles, the ring, the pool
        c0 = host_counts()
        loop(8, at_once=False)
        typical = [round(b - c, 3) for b, c in zip(host_counts(), c0)]
        out = {}
        for rep in range(a.reps):
            for name in ("at_once", "deferred"):
                ends, long_ = loop(a.rounds, at_once=(name == "at_once"))
                periods = [1e3 * (b - c) for b, c in zip(ends[1:], ends)]
                out.setdefault(name, []).append({
                    "median_ms": statistics.median(periods),
                    "mean_ms": statistics.fmean(periods),
                    "max_ms": max(periods),
                    "over_1s": long_,
                    "hbm_peak_GB_so_far": peak_gb()})
                print(name, rep, json.dumps(out[name][-1]), flush=True)
        print(json.dumps({"workload": a.workload,
                          "device": jax.devices()[0].device_kind,
                          "rounds": a.rounds,
                          "host_counts_of_8_rounds": typical, **out}))
        run.loader.close()
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
