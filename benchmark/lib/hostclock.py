"""What the readers of the program's resource clock share (schema 9:
``timeline_cpu`` beside ``timeline``, the ``host.*`` counters, the
``stall`` field, the ``telemetry.close`` span): round periods, the CPU
and the wait of a thread's spans, the process's counters a round; and,
for a traced window, the device's idle gaps laid over every thread's
innermost open span.

All of them read the untraced round records of a traced run
(``timeline.untraced_records``) and give None on a program whose
records lack the field (the parent of the PR that added it).
"""

from __future__ import annotations

import statistics

from benchmark.lib import tracelib
from benchmark.lib.timeline import span_mean_ms, untraced_records

CLOSE = "telemetry.close"   # the recorder's own span: no parent, yet it
                            # lies inside client_pass in time
HOST_COUNTERS = ("host.cpu_user_s", "host.cpu_sys_s", "host.minflt",
                 "host.majflt", "host.nvcsw", "host.nivcsw", "host.gc_s",
                 "host.gc_runs", "host.throttled_s")
STALL_TIMES = 3.0           # a period past this many medians is a stall
QUEUED = ("note_update", "metrics_host")    # the round loop waits for a
                                            # program it has dispatched


def clocked_records(ctx):
    """The untraced records that carry a CPU reading for every timeline
    entry; [] on a program that takes none."""
    return [r for r in untraced_records(ctx)
            if r.get("timeline_cpu") is not None
            and len(r["timeline_cpu"]) == len(r.get("timeline") or ())]


def _client_pass(record):
    """The record's parentless ``client_pass`` entry, or None."""
    return next((e for e in record.get("timeline") or ()
                 if e[0] == "client_pass" and e[3] is None), None)


def loop_thread(records):
    """The round loop's thread: the one that opens ``client_pass``."""
    return next((e[4] for e in map(_client_pass, records) if e), None)


def periods(records):
    """[(record, its ``client_pass`` opening, the next round's)] for the
    records whose successor is among them: a round's period."""
    starts = {r["round"]: e[1] for r in records
              for e in (_client_pass(r),) if e}
    return [(r, starts[r["round"]], starts[r["round"] + 1])
            for r in records
            if r["round"] in starts and r["round"] + 1 in starts]


def span_table(records, keep):
    """{span name: [wall s, CPU s, own wait s]} summed over the closed
    timeline entries whose thread ``keep`` accepts. A span's own wait is
    its wall minus its CPU minus its children's (wall minus CPU): the
    time its thread did not run that no child accounts for."""
    out = {}
    for r in records:
        tl, cpu = r["timeline"], r["timeline_cpu"]
        idle = [None if e[2] is None or c is None else e[2] - e[1] - c
                for e, c in zip(tl, cpu)]
        own = list(idle)
        for i, e in enumerate(tl):
            if idle[i] is not None and e[3] is not None \
                    and own[e[3]] is not None:
                own[e[3]] -= idle[i]
        for i, e in enumerate(tl):
            if idle[i] is None or not keep(e[4]):
                continue
            row = out.setdefault(e[0], [0.0, 0.0, 0.0])
            row[0] += e[2] - e[1]
            row[1] += cpu[i]
            row[2] += own[i]
    return out


def top_sums(records, keep):
    """(wall s, CPU s) summed over the closed parentless entries of the
    threads ``keep`` accepts, the recorder's own span left out (on the
    round loop's thread it lies inside ``client_pass``, whose CPU
    already holds it)."""
    wall = cpu = 0.0
    for r in records:
        for e, c in zip(r["timeline"], r["timeline_cpu"]):
            if e[3] is None and e[2] is not None and c is not None \
                    and e[0] != CLOSE and keep(e[4]):
                wall += e[2] - e[1]
                cpu += c
    return wall, cpu


def print_table(title, table, n):
    rows = sorted(table.items(), key=lambda kv: -kv[1][0])
    print(f"{title}, ms a round over {n} untraced rounds (wall / CPU / "
          "own wait): " + "; ".join(
              f"{k} {1e3 * w / n:.3f} / {1e3 * c / n:.3f} / "
              f"{1e3 * o / n:.3f}" for k, (w, c, o) in rows))


def host_counters(records):
    """{counter: mean a round} of the ``host.*`` deltas; {} without."""
    recs = [r for r in records if "host.cpu_user_s" in r["counters"]]
    return {k: sum(r["counters"].get(k, 0.0) for r in recs) / len(recs)
            for k in HOST_COUNTERS
            if any(k in r["counters"] for r in recs)}


def host_shape(ctx):
    """``host.cpus`` / ``host.threads`` / ``host.os_threads`` of the
    run's first record (a warm-up round's); {} without."""
    for r in ctx.get("records") or ():
        c = r.get("counters") or {}
        if "host.cpus" in c:
            return {k: c[k] for k in ("host.cpus", "host.threads",
                                      "host.os_threads") if k in c}
    return {}


# --- the device's idle gaps over the host's threads -----------------------


def innermost(spans):
    """One thread's properly nested ``(ts, end, name)`` spans as sorted,
    disjoint ``(ts, end, name)`` segments, each named after the
    innermost span open in it."""
    marks = sorted([(ts, 1, i) for i, (ts, _e, _n) in enumerate(spans)]
                   + [(end, 0, i) for i, (_t, end, _n) in enumerate(spans)])
    out, stack, at = [], [], None
    for t, opening, i in marks:
        if stack and t > at:
            out.append((at, t, spans[stack[-1]][2]))
        if opening:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
        at = t
    return out


def _named(segments, names):
    return [(s0, s1) for s0, s1, name in segments if name in names]


def overlay(gaps, segments):
    """{segment name: summed overlap with ``gaps``}; both sorted and
    disjoint. What of the gaps no segment covers is under None."""
    out = {}
    for name in {n for _s0, _s1, n in segments}:
        ov = tracelib._measure(
            tracelib._intersect(gaps, _named(segments, (name,))))
        if ov > 0:
            out[name] = ov
    rest = sum(b - a for a, b in gaps) - sum(out.values())
    if rest > 1e-9:
        out[None] = rest
    return out


def clip(gaps, segments, names):
    """The parts of ``gaps`` inside the segments named in ``names``."""
    return tracelib._intersect(gaps, _named(segments, names))


def _covered(intervals, lo, hi):
    """Seconds of [lo, hi] under the union of ``intervals``."""
    return tracelib._measure(tracelib._union(
        tracelib._clip(intervals, lo, hi)))


# --- the readers ----------------------------------------------------------


def _periodic(ctx):
    """(records with a period, [(record, opening, next opening)], the
    round loop's thread) of the clocked untraced records; None without."""
    per = periods(clocked_records(ctx))
    if not per:
        return None
    recs = [r for r, _a, _b in per]
    return recs, per, loop_thread(recs)


def _uncovered(per, thread):
    """Seconds of the periods that lie under no parentless span of
    ``thread``: the periods minus the union of those spans, cut to
    their record's period."""
    return sum(hi - lo - _covered(
        [(e[1], e[2]) for e in r["timeline"] if e[3] is None
         and e[2] is not None and e[4] == thread and e[0] != CLOSE],
        lo, hi)
        for r, lo, hi in per)


def stall_ms(ctx):
    """``runtime.stall_ms``: mean ms a round of the period beyond
    ``STALL_TIMES`` medians, and the longest round's whole record."""
    found = _periodic(ctx)
    if found is None:
        return None
    _recs, per, _thread = found
    lens = [b - a for _r, a, b in per]
    median = statistics.median(lens)
    over = [max(p - STALL_TIMES * median, 0.0) for p in lens]
    rec, a, b = max(per, key=lambda x: x[2] - x[1])
    print(f"stall: {1e3 * sum(over) / len(per):.3f} ms a round beyond "
          f"{STALL_TIMES:g} x the median period {1e3 * median:.3f} ms, in "
          f"{sum(o > 0 for o in over)} of {len(per)} untraced rounds; "
          f"records with a stall field: "
          f"{[r['round'] for r, _a, _b in per if r.get('stall')]}")
    cpu = rec.get("cpu") or {}
    print(f"longest round {rec['round']}: period {1e3 * (b - a):.3f} ms; "
          "spans wall / CPU ms: " + "; ".join(
              f"{k} {1e3 * v:.3f} / {1e3 * cpu.get(k, 0.0):.3f}"
              for k, v in sorted(rec["spans"].items(),
                                 key=lambda kv: -kv[1]))
          + "; counters: " + ", ".join(
              f"{k} {rec['counters'][k]:g}" for k in HOST_COUNTERS
              if k in rec["counters"]))
    stall = rec.get("stall")
    if stall:
        print(f"its stall, taken {stall['after_s']:.3f} s after it opened: "
              + "; ".join(f"{t}: {' < '.join(f[:3])}"
                          for t, f in stall["threads"].items()))
    return 1e3 * sum(over) / len(per)


def loop_cpu_ms(ctx):
    """``runtime.loop_cpu_ms``: CPU ms a round of the round loop's
    thread under its parentless spans. Prints the three that make a
    period: that CPU, those spans' wait (wall - CPU) and what lies
    under none of them."""
    found = _periodic(ctx)
    if found is None:
        return None
    recs, per, thread = found
    n = len(per)
    wall, cpu = top_sums(recs, lambda t: t == thread)
    period = sum(b - a for _r, a, b in per)
    open_s = _uncovered(per, thread)
    total = cpu + (wall - cpu) + open_s
    print(f"round loop [{thread}], ms a round over {n} untraced rounds: "
          f"CPU {1e3 * cpu / n:.3f} + wait {1e3 * (wall - cpu) / n:.3f} + "
          f"under no span {1e3 * open_s / n:.3f} = {1e3 * total / n:.3f} "
          f"against a mean period of {1e3 * period / n:.3f} "
          f"({100.0 * (total / period - 1.0):+.3f} %)")
    print_table(f"round loop [{thread}]",
                span_table(recs, lambda t: t == thread), n)
    return 1e3 * cpu / n


def loader_cpu_ms(ctx):
    """``data.loader_cpu_ms``: CPU ms a round of every other Python
    thread under its parentless spans, printed beside their wall."""
    found = _periodic(ctx)
    if found is None:
        return None
    recs, per, thread = found
    n = len(per)
    others = sorted({e[4] for r in recs for e in r["timeline"]} - {thread})
    total = 0.0
    for t in others:
        keep = t.__eq__
        wall, cpu = top_sums(recs, keep)
        total += cpu
        print(f"thread [{t}], ms a round over {n} untraced rounds: wall "
              f"{1e3 * wall / n:.3f}, CPU {1e3 * cpu / n:.3f}, wait "
              f"{1e3 * (wall - cpu) / n:.3f}")
        print_table(f"thread [{t}]", span_table(recs, keep), n)
    return 1e3 * total / n


def host_cpu_ms(ctx):
    """``runtime.host_cpu_ms``: user + system CPU ms a round of the
    whole process (the native ring's threads and the runtime's too),
    from the records' ``host.*`` counters; prints the rest of them."""
    recs = clocked_records(ctx)
    means = host_counters(recs)
    if "host.cpu_user_s" not in means:
        return None
    ms = 1e3 * (means["host.cpu_user_s"] + means["host.cpu_sys_s"])
    shape = host_shape(ctx)
    per = periods(recs)
    period = (sum(b - a for _r, a, b in per) / len(per)) if per else None
    line = (f"process, a round over {len(recs)} untraced rounds: CPU "
            f"{ms:.3f} ms (user {1e3 * means['host.cpu_user_s']:.3f}, "
            f"system {1e3 * means['host.cpu_sys_s']:.3f})")
    if period and shape.get("host.cpus"):
        line += (f" = {100.0 * ms / (1e3 * period * shape['host.cpus']):.2f}"
                 f" % of {shape['host.cpus']} cores x the mean period "
                 f"{1e3 * period:.3f} ms")
    print(line + "; " + ", ".join(
        f"{k} {v:g}" for k, v in list(means.items())[2:])
        + "; first record: " + ", ".join(
            f"{k} {v}" for k, v in shape.items()))
    return ms


def telemetry_ms(ctx):
    """``runtime.telemetry_ms``: the recorder's own ``telemetry.close``
    span, mean ms a round; with its CPU where the records carry one."""
    ms = span_mean_ms(ctx, (CLOSE,))
    recs = clocked_records(ctx)
    if ms is not None and recs:
        cpu = 1e3 * sum(r["cpu"].get(CLOSE, 0.0) for r in recs) / len(recs)
        print(f"{CLOSE}: {ms:.3f} ms a round, of it CPU {cpu:.3f}")
    return ms


def idle_queued_ms(ctx):
    """``device.idle_queued_ms``: ms a traced round in which the device
    is idle while the round loop, past its dispatch, is inside one of
    ``QUEUED``: a program is queued and has not finished, so the device
    waits for its input or the runtime and not for the host's code.
    Prints every idle gap's seconds by the innermost span open on the
    round loop's thread and on each other thread. None without the
    program's timeline or its clock marks in the trace."""
    try:
        from commefficient_tpu.telemetry.trace import host_timeline
    except ImportError:
        return None
    from benchmark.lib import tracesum
    tr = tracesum.of(ctx)
    wins = tr["windows"]
    moved = host_timeline(tr["events"], ctx.get("records") or ())
    if not wins or not moved:
        return None
    lo, hi = wins[0][1], wins[-1][2]
    busy = tracelib._union(tracelib._clip(
        [(float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0.0)))
         for e in tracesum.op_events(ctx)], lo, hi))
    gaps = tracelib._subtract([(lo, hi)], busy)
    by_thread = {}
    for s in moved:
        if s["end"] > lo and s["ts"] < hi:
            by_thread.setdefault(s["thread"], []).append(
                (s["ts"], s["end"], s["name"]))
    loop = next((t for t, v in by_thread.items()
                 if any(n == "client_pass" for _a, _b, n in v)), None)
    if loop is None:
        return None
    segments = {t: innermost(v) for t, v in by_thread.items()}
    queued = clip(gaps, segments[loop], QUEUED)

    def fmt(table):
        return ", ".join(f"{k or 'no span'} {v / 1e6:.4f}" for k, v in
                         sorted(table.items(), key=lambda kv: -kv[1]))

    print(f"idle gaps: {sum(b - a for a, b in gaps) / 1e6:.4f} s of a "
          f"{(hi - lo) / 1e6:.4f} s window in {len(wins)} traced rounds; "
          f"by the round loop's [{loop}] innermost span, s: "
          + fmt(overlay(gaps, segments[loop])))
    for t in sorted(set(segments) - {loop}):
        print(f"idle gaps by [{t}]'s innermost span, s: "
              + fmt(overlay(gaps, segments[t]))
              + f"; the queued part of them ({'/'.join(QUEUED)}): "
              + fmt(overlay(queued, segments[t])))
    return sum(b - a for a, b in queued) / 1e3 / len(wins)


# --- set-up ---------------------------------------------------------------


def _warmup(ctx):
    """(start, end) of the warm-up rounds on the host's clock: round
    0's first recorded span to the last warm-up round's end; None
    without records that carry a timeline."""
    first = ctx["window"]["first"]
    recs = ctx.get("records") or ()
    starts = [e[1] for r in recs if r.get("kind") == "round"
              and r["round"] == 0 for e in r.get("timeline") or ()]
    if not starts or not first or len(ctx["rounds"]) < first:
        return None
    return min(starts), ctx["rounds"][first - 1]["t_end"]


def warmup_s(ctx):
    """``entry.warmup_s``: seconds of the warm-up rounds, the first
    dispatch's compilations or cache reads included."""
    warm = _warmup(ctx)
    return None if warm is None else warm[1] - warm[0]


def setup_uncovered_s(ctx):
    """``entry.uncovered_s``: ``setup_s`` minus the union of the
    program's set-up spans (``data_build``, ``model_build``) and the
    warm-up rounds: imports, the builder's own work, the first batch's
    fetch."""
    try:
        from commefficient_tpu.telemetry import setup_spans
    except ImportError:
        return None
    warm = _warmup(ctx)
    if warm is None:
        return None
    t_open = ctx["window"]["t_start"]
    t0 = t_open - ctx["setup_s"]
    covered = _covered([(a, b) for _n, a, b in setup_spans()] + [warm],
                       t0, t_open)
    print(f"set-up {ctx['setup_s']:.2f} s: the spans and the warm-up "
          f"rounds cover {covered:.2f}, under none "
          f"{ctx['setup_s'] - covered:.2f} (warm-up "
          f"{warm[0] - t0:.2f}-{warm[1] - t0:.2f} s from process start)")
    return ctx["setup_s"] - covered
