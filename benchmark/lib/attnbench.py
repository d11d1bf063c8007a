"""The attention kernel's time a round, and the least time the chip
could take for a round's attention.

Time a round comes from the device trace and from nowhere else: the
``XLA Ops`` events named after the flash kernel's device operations
(``splash_*``: the library's forward, dq and dk/dv kernels, the forward
again where ``--remat`` recomputes it), their durations summed over the
traced rounds' windows, over the rounds and the devices. A trace that
names no such event (a program that builds its attention from
``jax.numpy``: every cell off the kernel path, the parent of the PR that
brought it, any run off the chip) has nothing to read: the metric is
left out of the line, and nothing of the program is imported.

Least work, from the reference's own count and the shapes alone: the
(query, key) pairs the layers' masks let through (``attention_pairs`` of
the configuration's reference file where it has one: the causal half,
the band; the causal half of every ``attention`` layer where it has
none), 12 * head size FLOPs a pair and query head (QK^T and PV, forward
and backward, as ``train_flops_per_round`` counts them; no
recomputation), over the bf16 peak. The kernels' bytes (q, k, v, the
output and their cotangents, once each) take under a twentieth of that
time at these shapes: bound by flops. What the kernel computes beyond
the needed pairs (the masked part of a tile, the recomputed forward, the
scores both backward kernels compute again) is in the traced time and
not in the least, so the share cannot pass 100 %.
"""

from __future__ import annotations

KERNEL = "splash_"


def traced_seconds_a_round(ctx):
    """Seconds a traced round, averaged over the devices, of the
    ``KERNEL`` events; None where the trace holds none."""
    if not ctx.get("trace_dir"):
        return None
    from benchmark.lib import tracesum
    tr = tracesum.of(ctx)
    wins = tr["windows"]
    if not wins:
        return None
    lo, hi = wins[0][1], wins[-1][2]
    per_dev, names = {}, {}
    for e in tracesum.op_events(ctx):
        name, ts = e.get("name", ""), float(e["ts"])
        if KERNEL not in name or ts < lo or ts >= hi:
            continue
        dev = tr["lanes"][(e.get("pid"), e.get("tid"))]
        dur = float(e.get("dur", 0.0)) / 1e6
        per_dev[dev] = per_dev.get(dev, 0.0) + dur
        names[name] = names.get(name, 0.0) + dur
    if not per_dev:
        return None
    n = len(per_dev) * len(wins)
    print("kernels.attn: ms a round by operation:", "; ".join(
        f"{k} {1e3 * v / n:.3f}" for k, v in sorted(names.items())))
    return sum(per_dev.values()) / n


def needed_pairs(ref, spec, cell):
    """(pairs a sequence over the attention layers, query heads, head
    size), by the reference file's own sizes."""
    z, T = ref._sizes(spec), int(cell["sequence_length"])
    if "windows" in z:      # a window a layer, or none: the whole past
        pairs = sum(ref.attention_pairs(T, z["window"] if w else None)
                    for w in z["windows"])
    else:
        pairs = T * (T + 1) // 2 * z["kinds"].count("attention")
    return pairs, z["Hq"], z["D"]


def least_seconds(pairs, heads, head_dim, sequences, peaks):
    """(seconds, bound) for a round's attention."""
    return 12 * head_dim * heads * pairs * sequences \
        / peaks["bf16_flops"], "flops"


def roofline_share(ctx):
    per_round = traced_seconds_a_round(ctx)
    if per_round is None:
        return None
    cell = ctx["cell"]
    pairs, heads, head_dim = needed_pairs(ctx["ref"], ctx["run"].ref_spec,
                                          cell)
    least, bound = least_seconds(
        pairs, heads, head_dim,
        cell["clients_per_round"] * cell["local_batch_size"], ctx["peaks"])
    print(f"kernels.attn: {1e3 * per_round:.3f} ms a round (trace); least "
          f"{1e3 * least:.3f} ms, bound by {bound}")
    return 100.0 * least / per_round
