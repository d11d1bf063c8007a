"""What the readers of the program's span timeline share: the window's
untraced round records, means over their spans and counters, the part
of a round period no span covers, and the set-up spans.

The round records of a traced run (``ctx["records"]``) carry, from
schema 8 on, a ``timeline`` of ``[name, t0, t1, parent, thread]``
entries beside the accumulated ``spans``. Host metrics are taken over
the rounds of the window that ran wholly **before** the profiler
started: the profiler slows a host-bound round (ResNet9: 680 against
257 ms). Record ``r`` holds round ``r``'s client and server pass and
the fetch of batch ``r + 1``, so the last record that qualifies is
``first_traced - 2``. A program without the timeline, a span or a
counter (the parent of the PR that added them) gives None, and the
metric is left out of the line.
"""

from __future__ import annotations

TOP_LEVEL = ("sampler", "client_pass", "server_pass")


def untraced_records(ctx):
    """The window's round records every span of which ran before the
    profiler started, in round order; [] in an untraced run."""
    if "_untraced" not in ctx:
        recs, win = ctx.get("records"), ctx["window"]
        out = []
        if recs and "first_traced" in win:
            lo, hi = win["first"], win["first_traced"] - 1
            out = sorted((r for r in recs if r.get("kind") == "round"
                          and lo <= r["round"] < hi),
                         key=lambda r: r["round"])
        ctx["_untraced"] = out
    return ctx["_untraced"]


def span_mean_ms(ctx, names, records=None):
    """Mean per round, in ms, of the summed ``names`` spans; None where
    no record carries any of them."""
    recs = untraced_records(ctx) if records is None else records
    if not any(n in r["spans"] for r in recs for n in names):
        return None
    return 1e3 * sum(r["spans"].get(n, 0.0)
                     for r in recs for n in names) / len(recs)


def epoch_start_records(ctx):
    return [r for r in untraced_records(ctx)
            if r["counters"].get("data.epoch_start")]


def uncovered_ms(ctx):
    """Mean per round, in ms, of the round period that lies under no
    top-level span of the main thread (``sampler``, ``client_pass``,
    ``server_pass``). A round's period runs from its ``client_pass``
    opening to the next round's. None without timelines."""
    recs = untraced_records(ctx)
    starts = {}
    for r in recs:
        for name, t0, _t1, parent, _thread in r.get("timeline") or ():
            if name == "client_pass" and parent is None:
                starts[r["round"]] = t0
                break
    vals, gaps = [], {}
    for r in recs:
        lo, hi = starts.get(r["round"]), starts.get(r["round"] + 1)
        if lo is None or hi is None:
            continue
        tops = sorted((t0, t1, name) for name, t0, t1, parent, _thread
                      in r["timeline"] if t1 is not None
                      and parent is None and name in TOP_LEVEL)
        cur, last, open_s = lo, "start", 0.0
        for t0, t1, name in tops + [(hi, hi, "next client_pass")]:
            if t0 > cur:
                key = f"{last} -> {name}"
                gaps[key] = gaps.get(key, 0.0) + t0 - cur
                open_s += t0 - cur
            cur, last = max(cur, min(t1, hi)), name
        vals.append(open_s)
    if not vals:
        return None
    print(f"uncovered: {1e3 * sum(vals) / len(vals):.3f} ms a round, in "
          "the gaps " + ", ".join(f"{k} ({1e3 * v / len(vals):.3f} ms)"
                                  for k, v in gaps.items()))
    return 1e3 * sum(vals) / len(vals)


def children_ms(ctx, parent_name, records=None):
    """(mean ms a round of the top-level ``parent_name`` spans, {child
    name: mean ms a round of its direct children}) from the timelines;
    (None, {}) without them. What the children do not cover is the
    parent's self time."""
    recs = untraced_records(ctx) if records is None else records
    total, kids, n = 0.0, {}, 0
    for r in recs:
        tl = r.get("timeline")
        if tl is None:
            return None, {}
        n += 1
        mine = {i for i, e in enumerate(tl)
                if e[0] == parent_name and e[3] is None}
        for i, (name, t0, t1, parent, _thread) in enumerate(tl):
            if t1 is None:
                continue
            if i in mine:
                total += t1 - t0
            elif parent in mine:
                kids[name] = kids.get(name, 0.0) + t1 - t0
    if not n:
        return None, {}
    return 1e3 * total / n, {k: 1e3 * v / n for k, v in kids.items()}


def loader_table(ctx):
    """Printed lines for PERF.md's tables: each top-level span against
    its children (``data.submit`` and ``data.pop_alloc`` have no metric
    of their own), and what other threads recorded."""
    recs = untraced_records(ctx)
    for parent in TOP_LEVEL:
        total, kids = children_ms(ctx, parent)
        if total is None:
            return
        covered = sum(kids.values())
        print(f"{parent} {total:.3f} ms a round over {len(recs)} untraced "
              "rounds; children: "
              + ", ".join(f"{k} {v:.3f}" for k, v in kids.items())
              + f"; they cover {covered:.3f} "
              f"({100.0 * covered / max(total, 1e-9):.1f} %), self time "
              f"{total - covered:.3f}")
    other = {}
    for r in recs:
        for name, t0, t1, parent, thread in r["timeline"]:
            if thread != "MainThread" and t1 is not None:
                key = f"{name} [{thread}]"
                other[key] = other.get(key, 0.0) + 1e3 * (t1 - t0)
    print("other threads, ms a round:",
          {k: round(v / max(len(recs), 1), 3) for k, v in other.items()},
          "; timeline entries dropped:",
          sum(r["counters"].get("timeline_dropped", 0) for r in recs))


def clock_check(ctx):
    """One printed line: how far each traced span's ``timeline`` entry,
    moved onto the trace's clock by the program's ``fed_clock`` offset
    (``trace.host_timeline``), lies from its own ``fed_phase::``
    annotation in the same trace."""
    try:
        from commefficient_tpu.telemetry.trace import host_timeline
    except ImportError:
        return
    import bisect

    from benchmark.lib import tracesum
    events = tracesum.of(ctx)["events"]
    moved = host_timeline(events, ctx["records"] or ())
    anns = {}
    for e in events:
        name = e.get("name", "")
        if e.get("ph") == "X" and name.startswith("fed_phase::"):
            anns.setdefault(name[len("fed_phase::"):], []).append(
                (float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0))))
    for v in anns.values():
        v.sort()
    d0, d1 = [], []
    for sp in moved:
        cand = anns.get(sp["name"])
        if not cand:
            continue
        i = bisect.bisect_left(cand, (sp["ts"],))
        near = min(cand[max(i - 1, 0):i + 1],
                   key=lambda a: abs(a[0] - sp["ts"]))
        if abs(near[0] - sp["ts"]) < 1e3:      # its own annotation
            d0.append(abs(near[0] - sp["ts"]))
            d1.append(abs(near[1] - sp["end"]))
    if d0:
        d0.sort()
        d1.sort()
        print(f"clock check: {len(d0)} traced spans against their "
              f"annotations, |start| median {d0[len(d0) // 2]:.1f} us "
              f"max {d0[-1]:.1f} us, |end| median {d1[len(d1) // 2]:.1f} "
              f"us max {d1[-1]:.1f} us ({len(moved)} spans moved)")


# --- set-up ---------------------------------------------------------------


def setup_seconds(ctx, name):
    """Summed seconds of the program's ``setup_span(name)`` entries
    that ended before the window opened; None where the program keeps
    none."""
    try:
        from commefficient_tpu.telemetry import setup_spans
    except ImportError:
        return None
    t_open = ctx["window"]["t_start"]
    vals = [t1 - t0 for n, t0, t1 in setup_spans()
            if n == name and t1 <= t_open]
    return sum(vals) if vals else None


def setup_compile_seconds(ctx):
    """Seconds the compile listener counted from its start to the
    window's first round: what the first record says compiled before
    it, plus the warm-up records' own. None without those counters."""
    recs = ctx.get("records")
    first = ctx["window"]["first"]
    if not recs:
        return None
    warm = sorted((r for r in recs if r.get("kind") == "round"
                   and r["round"] < first), key=lambda r: r["round"])
    if not warm or "compile_secs_before" not in warm[0]["counters"]:
        return None
    c0 = warm[0]["counters"]
    secs = c0["compile_secs_before"] + sum(
        r["counters"].get("compile_secs", 0.0) for r in warm)
    events = c0["compile_events_before"] + sum(
        r["counters"].get("compile_events", 0) for r in warm)
    hits = c0["compile_cache_hits_before"] + sum(
        r["counters"].get("compile_cache_hits", 0) for r in warm)
    print(f"set-up compile: {secs:.3f} s in {events} events, {hits} "
          f"persistent-cache hits, {c0['compile_secs_before']:.3f} s of "
          "it before round 0")
    return secs


def setup_table(ctx):
    """One printed line: where ``setup_s`` goes, for PERF.md."""
    try:
        from commefficient_tpu.telemetry import setup_spans
    except ImportError:
        return
    win, rounds = ctx["window"], ctx["rounds"]
    t0 = win["t_start"] - ctx["setup_s"]      # process start, about
    spans = [(n, a - t0, b - t0) for n, a, b in setup_spans()
             if b <= win["t_start"]]
    if not spans:
        return
    last_build = max(b for _, _, b in spans)
    first = win["first"]
    ends = [rounds[i]["t_end"] - t0 for i in range(first)]
    print("set-up, s from process start: "
          + "; ".join(f"{n} {a:.2f}-{b:.2f}" for n, a, b in spans)
          + "; warm-up rounds end at "
          + ", ".join(f"{e:.2f}" for e in ends)
          + f"; last build ends {last_build:.2f}; set-up {ctx['setup_s']:.2f}")
