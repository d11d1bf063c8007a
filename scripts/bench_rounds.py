#!/usr/bin/env python3
"""By hand, on the chip: one run of the benchmark's command with every
round kept.

    python3 scripts/bench_rounds.py --workload <cell> --seed <n> \
        --seconds 40 --trace <0|1> [--out chiprun_out/rounds]

Calls ``benchmark/run.py``'s ``main`` unchanged, in this process, with
a host timestamp taken around each ``next(loader)`` and each round
(``FedRun.step``) and, in a traced run, every round record the program
emitted. Writes ``<out>/<cell>-<seed>-t<trace>.json``: per round the
fetch and the step in ms, the rounds longer than a second with their
index and which of the two held them, and the records' counters
(the process's ``host.*`` among them), spans, their CPU and a long
round's ``stall`` (the timeline is dropped). The result line is the benchmark's
own, printed by it. What the benchmark cannot show: a rare round of
seconds (PERF.md section 6) vanishes in ``updates_per_s`` and is
invisible in ``round_ms_p90``.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "rounds"))
    a, rest = ap.parse_known_args(argv)
    named = argparse.ArgumentParser()       # the output file's name only
    for flag in ("--workload", "--seed", "--trace"):
        named.add_argument(flag, default="0")
    b, _ = named.parse_known_args(rest)
    from benchmark import run as bench
    from benchmark.lib import fedrun

    fetch, step, records = [], [], []
    feed_next, run_step = bench.Feed.next, fedrun.FedRun.step
    sink_write = bench.ListSink.write

    def timed(log, fn):
        def wrapped(*args, **kw):
            t0 = time.perf_counter()
            try:
                return fn(*args, **kw)
            finally:
                log.append(1e3 * (time.perf_counter() - t0))
        return wrapped

    def write(self, rec):
        sink_write(self, rec)
        if rec.get("kind") == "round":
            records.append({k: rec.get(k) for k in
                            ("round", "counters", "spans", "cpu",
                             "stall")})

    bench.Feed.next = timed(fetch, feed_next)
    fedrun.FedRun.step = timed(step, run_step)
    bench.ListSink.write = write
    rc = bench.main(rest)
    rounds = [{"fetch_ms": round(f, 3), "step_ms": round(s, 3)}
              for f, s in zip(fetch, step)]
    long_ = [dict(r, round=i) for i, r in enumerate(rounds)
             if r["fetch_ms"] + r["step_ms"] > 1000.0 and i >= 3]
    os.makedirs(a.out, exist_ok=True)
    path = os.path.join(a.out, f"{b.workload}-{b.seed}-t{b.trace}.json")
    with open(path, "w") as f:
        json.dump({"argv": rest, "rc": rc, "n_rounds": len(rounds),
                   "long_rounds": long_, "rounds": rounds,
                   "records": records}, f)
    print(f"rounds kept: {len(rounds)}; longer than 1 s after warm-up: "
          f"{long_}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
