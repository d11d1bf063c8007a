"""What a traced run's readers share: the trace's events, the
per-round buckets of ``tracelib.attribute_rounds``, the device lanes,
and the ``device`` / ``breakdown`` entries of the result line. Loaded
once per run and kept on the context."""

from __future__ import annotations

from benchmark.lib import tracelib

TOP = 10


def of(ctx) -> dict:
    tr = ctx.get("_trace")
    if tr is None:
        events = tracelib.load_trace_events(ctx["trace_dir"])
        # the last traced round is dropped, by position and whatever it
        # holds: the profiler's trace.json.gz stops at 1,000,000 events,
        # so where a round has many (ResNet9: 430,000) the file carries
        # the last round's marker and none of its device operations,
        # which would read as a round of pure idling. Every other round
        # counts, however little the device is seen to do in it
        buckets = tracelib.attribute_rounds(events)
        wins = tracelib.round_windows(events)
        last = wins[-1][0] if wins else None
        print(f"trace: {len(events)} events, {len(buckets)} rounds; device "
              f"busy s by round "
              f"{[round(b['busy_s'], 4) for b in buckets.values()][-12:]}; "
              f"dropped the last (round {last})")
        buckets = {r: b for r, b in buckets.items() if r != last}
        tr = ctx["_trace"] = {
            "events": events,
            "buckets": buckets,
            "windows": [w for w in wins if w[0] in buckets],
            "lanes": tracelib.lane_devices(events),
            "names": tracelib._lane_names(events),
        }
    return tr


def op_events(ctx):
    """Device-lane events that are single operations, not the modules
    or steps that contain them: the ``XLA Ops`` lines where the trace
    names its lines so, every device lane otherwise."""
    tr = of(ctx)
    _, threads = tr["names"]
    ops = {k for k in tr["lanes"] if "XLA Ops" in threads.get(k, "")}
    lanes = ops or set(tr["lanes"])
    for e in tr["events"]:
        if e.get("ph") == "X" and (e.get("pid"), e.get("tid")) in lanes:
            name = e.get("name", "")
            if name != tracelib.ROUND_MARKER \
                    and not name.startswith(tracelib.PHASE_PREFIX):
                yield e


def module_seconds(ctx, needle):
    """Seconds, summed over the traced rounds' windows and averaged over
    the devices, of the ``XLA Modules`` events whose name holds
    ``needle``; None where the trace has no such line or event."""
    tr = of(ctx)
    _, threads = tr["names"]
    wins = tr["windows"]
    if not wins:
        return None
    lo, hi = wins[0][1], wins[-1][2]
    per_dev = {}
    for e in tr["events"]:
        key = (e.get("pid"), e.get("tid"))
        if e.get("ph") != "X" or key not in tr["lanes"] \
                or "XLA Modules" not in threads.get(key, ""):
            continue
        if needle not in e.get("name", ""):
            continue
        ts, dur = float(e["ts"]), float(e.get("dur", 0.0))
        a, b = max(ts, lo), min(ts + dur, hi)
        if b > a:
            per_dev.setdefault(tr["lanes"][key], 0.0)
            per_dev[tr["lanes"][key]] += (b - a) / 1e6
    if not per_dev:
        return None
    return sum(per_dev.values()) / len(per_dev)


def traced_rounds(ctx) -> int:
    return len(of(ctx)["buckets"])


def summary(ctx) -> dict:
    """``device.busy_s`` / ``window_s`` (averaged over the chips used)
    and the ``breakdown`` of a --trace 1 result line."""
    tr = of(ctx)
    if "summary" not in tr:
        tr["summary"] = _summary(ctx, tr)
    return tr["summary"]


def _summary(ctx, tr) -> dict:
    procs, threads = tr["names"]
    print("trace lanes:", sorted({(procs.get(p, ""), threads.get((p, t), ""))
                                  for p, t in tr["lanes"]}))
    buckets = tr["buckets"]
    window = sum(b["window_s"] for b in buckets.values())
    per_dev = {}
    for b in buckets.values():
        for d, v in b["per_device"].items():
            per_dev[d] = per_dev.get(d, 0.0) + v["busy_s"]
    busy = sum(per_dev.values()) / max(len(per_dev), 1)

    ops = list(op_events(ctx))
    totals = {}
    for e in ops:
        totals[e["name"]] = totals.get(e["name"], 0.0) \
            + float(e.get("dur", 0.0)) / 1e6
    device_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]

    # idle gaps of the pooled device timeline, shared out among the host
    # annotations (fed_phase::*) that overlap them; what no phase covers
    # (the trainer loop: sampler, accounting, note_update) is outside_phases
    wins = tr["windows"]
    gaps = {}
    if wins:
        lo, hi = wins[0][1], wins[-1][2]
        busy_iv = tracelib._union(tracelib._clip(
            [(float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0.0)))
             for e in ops], lo, hi))
        phases = sorted(
            (float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0.0)),
             e["name"]) for e in tr["events"]
            if e.get("ph") == "X"
            and e.get("name", "").startswith(tracelib.PHASE_PREFIX))
        cur = lo
        for a, b in busy_iv + [[hi, hi]]:
            if a > cur:
                left = a - cur
                for s0, s1, name in phases:
                    ov = min(a, s1) - max(cur, s0)
                    if ov > 0:
                        gaps[name] = gaps.get(name, 0.0) + ov / 1e6
                        left -= ov
                gaps["outside_phases"] = gaps.get("outside_phases", 0.0) \
                    + max(left, 0.0) / 1e6
            cur = max(cur, b)
    idle_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return {"device": {"busy_s": busy, "window_s": window},
            "breakdown": {"device_ops": [[n, s] for n, s in device_ops],
                          "idle_gaps": [[n, s] for n, s in idle_gaps]}}
