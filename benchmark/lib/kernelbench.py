"""The sketch kernels' time a call, and the least time the chip could
take for them.

Time a call comes from the device trace and from nowhere else: the
``XLA Ops`` events named after the Pallas kernel (``sketch_pallas*`` /
``estimates_pallas*``, the names Mosaic gives them today; a stable
``name=`` is PERF.md section 7's request), total duration over count.
Where the cell's mode has no sketch, or the trace names no such event,
there is nothing to read and the metric is left out of the line.

Least work, from the shapes alone:
  sketch     reads the (d,) float32 vector once, writes the (r, c) table;
             r * d sign-multiplies and r * d adds.
  estimates  reads the (r, c) table, writes the (d,) float32 estimates;
             r * d sign-multiplies and a median of r per coordinate
             (counted as r * d compares).
Signs and rotations are computed, not read, in the least-work count.
"""

from __future__ import annotations


def least_seconds(which, d, c, r, peaks):
    """(seconds, bound) for one call; bound is 'hbm' or 'flops'."""
    assert which in ("sketch", "estimates"), which
    nbytes = 4 * d + 4 * r * c
    ops = 2 * r * d
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["bf16_flops"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "flops")


def traced_seconds_a_call(ctx, which):
    from benchmark.lib import tracesum
    durs = [float(e.get("dur", 0.0)) / 1e6 for e in tracesum.op_events(ctx)
            if e.get("name", "").startswith(which + "_pallas")]
    return sum(durs) / len(durs) if durs else None


def roofline_share(ctx, which):
    from commefficient_tpu.core.rounds import args2sketch
    sk = args2sketch(ctx["run"].args)
    per_call = None if sk is None else traced_seconds_a_call(ctx, which)
    if per_call is None:
        return None
    least, bound = least_seconds(which, sk.d, sk.c, sk.r, ctx["peaks"])
    print(f"kernels.{which}: {1e3 * per_call:.4f} ms a call (trace); "
          f"least {1e3 * least:.4f} ms, bound by {bound}")
    return 100.0 * least / per_call
