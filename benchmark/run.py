#!/usr/bin/env python3
"""The benchmark's one command: one process, one cell, one run.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, builder, reference
or metric lives in a file of its own, found here by the name
``BENCHMARK.json`` gives it:

    workloads/<cell>.json   configs/<config>.json   builders/<family>.py
    reference/<config>.py   metrics/<metric>.py

The run: set-up (data and weights from the seed, the trainer's own
objects assembled by the builder, the first three real rounds as
warm-up), a measured window of rounds driven exactly as the trainer's
``run_batches`` drives them, then, outside every timed number, the
comparison with the plain float32 reference that decides ``correct``.
The last line of standard output is the result object.

Without a TPU the command exits 2 and prints no result. ``--rehearse``
(the benchmark's own tests) runs the tiny presets on whatever backend
is there and reports counts and ``correct`` only, no metric.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WARMUP_ROUNDS = 3          # also the steps the reference follows
TRACE_SECONDS = 3.0        # the traced tail of a --trace 1 window
SEED_MODULUS = 2147483629  # driver seeds pass 2**31; the program's may not


def load(kind, name):
    """The module at ``benchmark/<kind>/<name>.py``."""
    path = os.path.join(HERE, kind, name + ".py")
    modname = "benchmark_%s_%s" % (kind, re.sub(r"\W", "_", name))
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class ListSink:
    """In-memory telemetry sink: the round records of a traced run."""

    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)

    def close(self):
        pass


class Feed:
    """The trainer's loader, re-entered at each epoch end."""

    def __init__(self, loader):
        self.loader = loader
        self.it = iter(loader)

    def next(self):
        batch = next(self.it, None)
        if batch is None:
            self.it = iter(self.loader)
            batch = next(self.it)
        return batch


def applies(entry, cell_name):
    return "workloads" not in entry or cell_name in entry["workloads"]


def device_use(devices):
    """The arrays this process has alive on its devices, and the
    allocator's ``bytes_in_use`` where the backend reports it."""
    import jax
    live = jax.live_arrays()
    return {"arrays": len(live), "array_bytes": sum(x.nbytes for x in live),
            "bytes_in_use": max((d.memory_stats() or {}).get(
                "bytes_in_use", 0) for d in devices)}


def release(devices):
    """Delete every array this process has alive on its devices and let
    go of every compiled program; what is in use after that."""
    import gc

    import jax
    for x in jax.live_arrays():
        x.delete()
    gc.collect()
    jax.clear_caches()
    return device_use(devices)


def main(argv=None, fault=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny presets, any backend, no metric")
    ap.add_argument("--control", action="store_true",
                    help="also compute the lower-precision control")
    a = ap.parse_args(argv)

    manifest = read_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == a.workload), None)
    if entry is None:
        print(f"no cell {a.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    cell = read_json(HERE, "workloads", a.workload + ".json")
    config = read_json(HERE, "configs", entry["config"] + ".json")
    if a.rehearse:
        cell.update({k: v for k, v in cell["rehearse"].items()
                     if k != "data"})

    sys.path.insert(0, ROOT)
    import jax
    import numpy as np
    devices = jax.devices()
    chips = int(entry["chips"])
    if not a.rehearse and (devices[0].platform != "tpu"
                           or len(devices) < chips):
        print(f"needs {chips} TPU chip(s); JAX sees {len(devices)} x "
              f"{devices[0].platform}", file=sys.stderr)
        return 2
    n_dev = len(devices) if a.rehearse else chips
    if not a.rehearse:
        from commefficient_tpu.utils import setup_compile_cache
        print("compile cache:", setup_compile_cache())
        # small programs too: a warm set-up finds every one in the cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from benchmark.lib import fetchsgd_ref as fr
    from benchmark.lib.peaks import peaks_of
    from jax import monitoring
    from commefficient_tpu.telemetry import trace as markers

    # programs built (or fetched from the persistent cache) so far; a
    # trace of a small eager op is not one and is not counted
    built = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: built.append(secs)
        if event.endswith("backend_compile_duration") else None)

    pseed = a.seed % SEED_MODULUS
    workdir = os.path.join(HERE, ".cache", f"{a.workload}-{a.seed}")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    try:
        # ---- set-up ---------------------------------------------------
        ref = load("reference", config["reference"])
        builder = load("builders", config["builder"])
        cell["num_devices"] = n_dev
        run = builder.build(cell, config, ref, pseed, workdir,
                            rehearse=a.rehearse)
        if fault is not None:
            fault(run)
        model, W = run.model, run.clients_per_round
        sink = None
        if a.trace:
            sink = ListSink()
            model.telemetry.add_sink(sink)
        tel = model.telemetry
        feed = Feed(run.loader)
        rounds = []            # one dict a round, from round 0
        kept = {"batches": [], "losses": [], "lrs": []}

        def one_round(keep=False):
            with tel.span("sampler"):
                batch = feed.next()
            losses, w, down, up = run.step(batch, keep_aggregate=keep)
            mean = float(np.sum(losses * w) / max(w.sum(), 1.0))
            rounds.append({"loss": mean, "down": down, "up": up,
                           "t_end": time.perf_counter()})
            if keep:
                if run.batch_note is not None:
                    print("warm-up batch:", run.batch_note(batch))
                kept["batches"].append(run.ref_batch(batch))
                kept["losses"].append(np.asarray(losses, np.float64))
                kept["lrs"].append(run.lr())
            return mean

        for i in range(WARMUP_ROUNDS):
            one_round(keep=True)
            if i == 0:
                kept["table0"] = np.array(run.last_aggregate)
        jax.block_until_ready(model.ps_weights)
        kept["w_after"] = np.array(model.ps_weights)
        run.last_aggregate = None
        setup_s = time.perf_counter() - _T0

        # ---- the window -----------------------------------------------
        attempted = failed = 0
        built_before = len(built)
        tracer = None
        t_start = time.perf_counter()
        deadline = t_start + a.seconds
        trace_at = deadline - min(TRACE_SECONDS, a.seconds / 2)
        win = {"t_start": t_start, "first": len(rounds)}
        while True:
            now = time.perf_counter()
            if a.trace and tracer is None and now >= trace_at:
                win["t_trace"], win["first_traced"] = now, len(rounds)
                # the program's trace_window, minus the Python tracer: at
                # level 1 it doubled the period of a host-bound round
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(os.path.join(workdir, "trace"),
                                         profiler_options=opts)
                markers.set_tracing(True)
                tracer = True
            attempted += 1
            if not math.isfinite(one_round()):
                failed += 1
            if rounds[-1]["t_end"] >= deadline:
                break
        jax.block_until_ready(model.ps_weights)
        win["t_close"] = time.perf_counter()
        win["last"] = len(rounds)
        if tracer is not None:
            markers.set_tracing(False)
            jax.profiler.stop_trace()
        compiles = len(built) - built_before
        compile_s = sum(built[built_before:])
        if compiles:
            print(f"{compiles} compilation(s) inside the window "
                  f"({compile_s:.3f} s): every round counts as failed")
            failed = attempted
        # what the allocator handed out at most, plus the region the
        # runtime reserves for the programs' temporaries: on this chip
        # the two are disjoint and only their sum is the chip's peak
        stats = [d.memory_stats() or {} for d in devices[:n_dev]]
        mem_peak = max(s.get("peak_bytes_in_use", 0)
                       + s.get("peak_bytes_reserved", 0) for s in stats)
        print("memory_stats:", json.dumps(stats[0]))

        # rounds up to the mark, outside the window, where it was short
        while len(rounds) < cell["mark_round"]:
            one_round()
        step = max(1, len(rounds) // 12)
        print("loss by round:", ", ".join(
            f"{i}: {sum(r['loss'] for r in rounds[i:i + step]) / len(rounds[i:i + step]):.4f}"
            for i in range(0, len(rounds), step)))
        print(f"window: {win['last'] - win['first']} rounds of {W} "
              f"clients in {win['t_close'] - t_start:.3f} s; "
              f"{len(rounds)} rounds in all; set-up {setup_s:.2f} s")

        # ---- the program leaves the chip --------------------------------
        # What the comparison reads (``kept``) is the host's already.
        # The rest is taken from the live program now; then its loader's
        # thread (which stages round r+1 on the device) and the model
        # are shut down as the trainer does, every array the process has
        # on the device is deleted, and the program's objects and
        # compiled programs are let go. ``run`` keeps ``ref_spec`` and
        # ``args``, host values, which is all the readers ask it for.
        t_check = time.perf_counter()
        sk = fr.SketchSpec(**run.sketch_spec())
        hyper = run.hyper()
        eng = None if a.rehearse else run.engagement()
        run.loader.close()
        model.finalize()
        held = device_use(devices[:n_dev])
        run.model = run.opt = run.lr_scheduler = run.loader = None
        model = tel = feed = None
        print("released:", json.dumps(
            {"before": held, "after": release(devices[:n_dev])}))

        # ---- correct: against the plain float32 reference --------------
        params0 = jax.tree_util.tree_map(np.asarray, run.make_params())
        leaf_sizes = [int(np.prod(x.shape))
                      for x in jax.tree_util.tree_leaves(params0)]
        observed = {"losses": kept["losses"], "table0": kept["table0"],
                    "delta": kept.pop("w_after")}
        observed["delta"] -= fr.ravel_host(params0)
        follow = dict(ref=ref, spec_model=run.ref_spec, params=params0,
                      batches=kept["batches"], lrs=kept["lrs"],
                      hyper=hyper, sk=sk)
        want = fr.follow(**follow)
        nums = fr.numbers(observed, want, leaf_sizes)
        print("check detail:", json.dumps(fr.detail(observed, want)))
        rows = fr.verdict(nums, ref.LIMITS)
        for name, value, limit, ok in rows:
            print(f"correct: {name} = {value:.6g} (limit {limit:g}) "
                  f"{'ok' if ok else 'FAILS'}")
        correct = all(ok for *_, ok in rows)
        if eng is not None:
            print("engagement:", json.dumps(eng))
            engaged = (eng["sketch_backend"] == "pallas"
                       and eng["client_custom_calls"] >= 1
                       and eng["server_custom_calls"] >= 1)
            if not engaged:
                print("correct: the Pallas kernels are not engaged FAILS")
            correct = correct and engaged
        correct = correct and failed == 0
        if a.control:
            ctl = fr.follow(**follow, precision=config["control_precision"])
            cnums = fr.numbers(ctl, want, leaf_sizes)
            print("control detail:", json.dumps(fr.detail(ctl, want)))
            crow = fr.verdict(cnums, ref.LIMITS)
            for name, value, limit, ok in crow:
                print(f"control[{config['control_precision']}]: {name} = "
                      f"{value:.6g} (limit {limit:g}) "
                      f"{'passes' if ok else 'fails'}")
            print("control_correct:",
                  json.dumps(all(ok for *_, ok in crow)))
        print(f"check took {time.perf_counter() - t_check:.2f} s")

        # ---- the metrics ----------------------------------------------
        kind = devices[0].device_kind
        result = {"correct": bool(correct), "attempted": attempted,
                  "failed": failed, "metrics": {},
                  "device": {"platform": devices[0].platform, "kind": kind,
                             "count": len(devices),
                             "memory_peak_bytes": int(mem_peak)}}
        ctx = {"cell": cell, "config": config, "manifest": manifest,
               "run": run, "ref": ref, "clients_per_round": W,
               "setup_s": setup_s, "rounds": rounds, "window": win,
               "records": sink.records if sink else None,
               "trace_dir": (os.path.join(workdir, "trace")
                             if a.trace else None),
               "device_kind": kind, "chips": n_dev,
               # a rehearsal's readings are thrown away: any row serves
               "peaks": peaks_of("TPU v5 lite" if a.rehearse else kind),
               "memory_peak_bytes": int(mem_peak)}
        for m in manifest["per_layer" if a.trace else "end_to_end"]:
            if not applies(m, a.workload):
                continue
            reader = load("metrics", m["name"])
            value = reader.read(ctx)
            if a.rehearse:
                # a CPU number is never written under a device metric's
                # name: the readers are only exercised
                print(f"rehearsal: reader {m['name']} "
                      f"{'found nothing to read' if value is None else 'ran'}")
            elif value is not None:
                result["metrics"][m["name"]] = {
                    "value": float(value), "unit": m["unit"]}
        if a.trace:
            from benchmark.lib import tracesum
            tr = tracesum.summary(ctx)
            if a.rehearse:
                print("rehearsal: trace summary", json.dumps(tr)[:600])
            else:
                result["device"].update(tr["device"])
                result["breakdown"] = tr["breakdown"]
        print(json.dumps(result))
        return 0
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
