#!/usr/bin/env python3
"""ROADMAP S10, by hand, on the chip: what does one expert layer
(``models/moe.py routed_experts``) cost, forward + backward, when the
clients' held rows are taken a client at a time and when they are taken
in one grouped pass (PR 45's step 0, PERF.md section 6)?

    python3 scripts/moe_probe.py [--cells joyai,nemotron,smallthinker]
        [--slacks 1,1.25,1.5,2] [--reps 5] [--iters 5] [--seed 7]
        [--by_op 6] [--rehearse]

One layer alone at each cell's shapes (``CELLS`` below: clients, tokens
a client, picks a token, held of the router's experts, width, expert
width, form, scoring), bf16 rows, float32 weights, the routing drawn as
the cell's (the scores of a random router, ``route`` + ``dispatch`` a
client, outside the timed program). Timed: ``jax.grad`` of a sum over
``vmap(routed_experts)`` with respect to the rows, the gates and every
weight, so one forward and one backward pass loop. Rows of the table:

``per_client``: the ``vmap`` says nothing: a client at a time, each
with its own weight gradient (W x the experts written out, which the
transformation then sums).
``pooled@M``: the ``vmap`` is named ``SHARED_CLIENTS``: one pass loop
over every client's held assignments, a buffer of M rows (the probe
sets ``moe.pool_rows`` for the row): M = ``--slacks`` x the load the
shapes predict (W x N x k x held / router's), rounded up to
``moe.POOL_ALIGN``, and M = W x N. ``pooled``: the program's own
choice. Each row: ms a call (median of ``--reps`` timings of
``--iters`` calls), the passes it took, the compiled program's
temporaries, the relative difference of its gradient from the first
row's, and with ``--by_op n`` the n device operations with the most
time of their own in one traced call.

On a tree from before PR 45 (no ``moe.pool_rows``) the named ``vmap``
is the per-client loops with the weight gradient summed as they go
(``named_loops``): copy the script into a ``git archive`` copy of that
commit to compare. ``--rehearse``: tiny shapes on whatever backend
there is. Results also go to ``chiprun_out/moe_probe/<cell>.json``.
"""

import argparse
import glob
import json
import os
import re
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: cell -> (W, N, k, held, router's, width, expert width, form, scoring)
CELLS = {
    "joyai": (8, 4096, 8, 8, 256, 2048, 768, "swiglu", "sigmoid"),
    "nemotron": (8, 2048, 22, 8, 512, 1024, 2688, "relu2", "sigmoid"),
    "smallthinker": (2, 8192, 6, 8, 64, 2560, 768, "reglu", "softmax"),
}
TINY = {name: (3, 32, c[2], 8, 64, 16, 12, c[7], c[8])
        for name, c in CELLS.items()}


def own_time_by_operation(fn, args, top):
    """{operation: ms of its own} of one traced call, the ``top``
    largest: the device's ``XLA Ops`` line, an event's time less the
    events inside it (a ``while`` holds its body's), names without
    their numbers. Empty where the trace has no such line."""
    import jax
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            jax.block_until_ready(fn(*args))
        paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            return {}
        data = ProfileData.from_file(paths[0])
        events = []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:0"):
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        events = [(e.start_ns, e.duration_ns, e.name)
                                  for e in line.events]
    own, names, stack = [], [], []       # stack: (end, index) of open events
    for start, dur, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= dur
        stack.append((start + dur, len(own)))
        own.append(dur)
        names.append(re.sub(r"[.\d]+$", "", name.lstrip("%").split(" ")[0]))
    total = {}
    for name, ns in zip(names, own):
        total[name] = total.get(name, 0.0) + ns / 1e6
    return dict(sorted(total.items(), key=lambda kv: -kv[1])[:top])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--slacks", default="1,1.25,1.5,2")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--by_op", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    if a.rehearse:
        a.reps, a.iters = 2, 1

    import jax
    import jax.numpy as jnp
    import numpy as np
    from commefficient_tpu.models import moe
    from commefficient_tpu.parallel.mesh import SHARED_CLIENTS

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}")
    pools = hasattr(moe, "pool_rows")
    out_dir = os.path.join(ROOT, "chiprun_out", "moe_probe")
    os.makedirs(out_dir, exist_ok=True)

    def rel(a, b):
        num = sum(float(jnp.sum(jnp.square(x.astype(jnp.float32)
                                           - y.astype(jnp.float32))))
                  for x, y in zip(a, b))
        den = sum(float(jnp.sum(jnp.square(y.astype(jnp.float32))))
                  for y in b)
        return (num / max(den, 1e-30)) ** 0.5

    for name in a.cells.split(","):
        W, N, k, E, R, C, F, form, scoring = (TINY if a.rehearse
                                              else CELLS)[name]
        key = jax.random.split(jax.random.PRNGKey(a.seed), 8)
        x = jax.random.normal(key[0], (W, N, C), jnp.bfloat16)
        router = jax.random.normal(key[1], (C, R)) / np.sqrt(C)
        bias = None if scoring == "softmax" else jnp.zeros((R,))
        n_w = 2 if form == "relu2" else 3
        w = tuple(0.02 * jax.random.normal(
            key[2 + i], (E, C, F) if i < n_w - 1 else (E, F, C))
            for i in range(n_w))
        cot = jax.random.normal(key[6], (W, N, C))

        @jax.jit
        def routing(x):
            def one(xi):
                top, g = moe.route(xi, router, bias, k, 1.0,
                                   scoring=scoring)
                return moe.dispatch(top, g, 0, E)
            return jax.vmap(one)(x)

        token, gate, load = routing(x)
        held = int(jnp.sum(load))
        expected = W * N * k * E / R
        print(f"\n{name}: W {W} N {N} k {k} held {E} of {R} width {C} "
              f"F {F} {form}; held assignments {held} "
              f"(the shapes predict {expected:.0f}), fullest expert "
              f"{int(jnp.max(jnp.sum(load, 0)))}, fullest client "
              f"{int(jnp.max(jnp.sum(load, 1)))}")

        def program(axis):
            extra = (E / R,) if pools else ()

            def loss(x, gate, w):
                y = jax.vmap(lambda xi, ti, gi, li: moe.routed_experts(
                    xi, ti, gi, li, w, form, *extra),
                    axis_name=axis)(x, token, gate, load)
                return jnp.sum(y * cot)
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        rows = [("per_client", None, None)]
        if pools:
            align = moe.POOL_ALIGN
            sizes = [-(-int(np.ceil(float(s) * expected)) // align) * align
                     for s in a.slacks.split(",")] + [W * N]
            rows += [(f"pooled@{M}", SHARED_CLIENTS, M) for M in sizes]
            rows += [("pooled", SHARED_CLIENTS, None)]
        else:
            rows += [("named_loops", SHARED_CLIENTS, None)]

        first, table = None, []
        own_rule = getattr(moe, "pool_rows", None)
        for label, axis, M in rows:
            if M is not None:
                moe.pool_rows = lambda *_, M=M: min(M, W * N * k)
            elif pools:
                moe.pool_rows = own_rule
            rows_used = moe.pool_rows(W, N * k, E / R) \
                if pools and axis else N
            fn = program(axis)
            try:
                compiled = fn.lower(x, gate, w).compile()
                temp = compiled.memory_analysis().temp_size_in_bytes
                got = jax.block_until_ready(fn(x, gate, w))
            except Exception as e:  # a row that does not fit is a row
                print(f"  {label}: {type(e).__name__}: {str(e)[:200]}")
                continue
            flat = jax.tree_util.tree_leaves(got)
            first = first or flat
            times = []
            for _ in range(a.reps):
                t0 = time.perf_counter()
                for _ in range(a.iters):
                    out = fn(x, gate, w)
                jax.block_until_ready(out)
                times.append(1e3 * (time.perf_counter() - t0) / a.iters)
            row = {"row": label, "ms": statistics.median(times),
                   "ms_min": min(times), "ms_max": max(times),
                   "rows_a_pass": rows_used,
                   "passes": (-(-held // rows_used) if axis and pools else
                              int(jnp.max(-(-jnp.sum(load, 1) // N)))),
                   "temp_MB": temp / 1e6, "grad_rel": rel(flat, first)}
            if a.by_op:
                row["own_ms_by_op"] = own_time_by_operation(
                    fn, (x, gate, w), a.by_op)
            table.append(row)
            print("  " + json.dumps(row))
        if pools:
            moe.pool_rows = own_rule
        with open(os.path.join(out_dir, f"{name}.json"), "w") as f:
            json.dump({"cell": name, "device": dev.device_kind,
                       "held": held, "expected": expected,
                       "pooled_rule": pools, "rows": table}, f, indent=1)


if __name__ == "__main__":
    main()
