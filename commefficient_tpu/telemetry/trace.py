"""Device-time attribution: profiler round markers + trace parsing.

The round ledger (core.py) measures *host* phases; this module closes
the gap to the device timeline. Two halves:

**Markers** — while a ``trace_window`` is open, ``FedModel`` brackets
each round in a ``jax.profiler.StepTraceAnnotation`` (name
``fed_round``, ``step_num`` = the ledger round index), and every
``Telemetry.span(name)`` (core.py) that is the outermost one open on
the thread that opened the window (the round loop's: ``sampler``,
``client_pass``, ``server_pass``) opens a ``fed_phase::<name>``
``TraceAnnotation`` through ``phase`` below, so no instant of the
trace lies under two phases; the spans nested in those, and other
threads', are placed by the clock below. The round annotation is
opened at ``begin_round`` and closed at the NEXT round's begin —
mirroring the ledger record lifecycle, so the server step (dispatched after
``_call_train`` returns) lands inside its own round's window. State is
module-level (one live FedModel per process, like
``fed_model._CURRENT_MODEL``); every call is a single flag check when
no trace is active, so the round hot loop pays nothing.

**One clock** — ``set_tracing`` writes a zero-length annotation
``fed_clock::<clock.tick() in ns>`` when a window opens and when it
closes. ``annotation ts - tick`` is the offset from the host spans'
clock to the trace's, so ``host_timeline`` places every ``timeline``
entry of the round records (also those of a thread or a round that
carries no annotation) on the device timeline.

**Parser** — ``jax.profiler.stop_trace`` writes a Chrome trace-event
dump (``plugins/profile/<ts>/<host>.trace.json.gz``): ``ph:"X"``
complete events with µs ``ts``/``dur`` and ``ph:"M"`` metadata naming
each pid/tid lane. Device lanes are the ``/device:*`` processes (TPU,
GPU) or the ``tf_XLA*`` client threads (CPU backend).
``attribute_rounds`` buckets every device event into its round's
window: {compute, collective, h2d/d2h transfer, host-gap}, by interval
union so nested/overlapping op events never double-count. Buckets sum
to the round window by construction — the acceptance bar for the
schema-v3 ``device_time`` ledger field.

Schema v4 keeps each device lane's interval set instead of collapsing
to one union: every round additionally carries
``per_device[<device_id>]`` buckets ({busy, compute, collective,
transfer} for that device alone) and a *skew decomposition* of the
collective bucket. Matching collective events are aligned across
device lanes (k-th in-window occurrence of each collective op name);
a device's collective time then splits into **wait** (straggler skew:
from this device entering the collective until the LAST device
enters) and **wire** (the post-alignment transfer, ``collective -
wait`` — exact by construction). Round-level skew stats (max/p95
enter-delta, the straggler device id) land in ``device_time.skew``
and feed the ``collective_skew`` alarm rule (telemetry/alarms.py).
The cross-device aggregate buckets are computed from the pooled
interval set exactly as in v3 — bit-for-bit unchanged.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import threading

from commefficient_tpu.telemetry import clock

ROUND_MARKER = "fed_round"
PHASE_PREFIX = "fed_phase"
CLOCK_PREFIX = "fed_clock"

#: lines of a device process whose events are not operations: the
#: TPU's ``Steps`` line repeats the step annotation (events named
#: ``fed_round``) over each step's device activity
NOT_OPERATIONS = ("Steps",)

#: substrings (lowercase) classifying a device-lane event
COLLECTIVE_TOKENS = (
    "all-reduce", "allreduce", "all-gather", "allgather",
    "reduce-scatter", "reducescatter", "all-to-all", "alltoall",
    "collective-permute", "collectivepermute", "collective-broadcast",
)
TRANSFER_TOKENS = (
    "infeed", "outfeed", "copy", "memcpy", "transfer",
    "h2d", "d2h", "send", "recv",
)

# one live FedModel per process (fed_model._CURRENT_MODEL) -> one
# module-level marker state; "ann" is the currently-open round
# StepTraceAnnotation, closed at the next begin or at window exit
_STATE = {"tracing": False, "ann": None, "round": None,
          # the thread that opened the window, and how many of its
          # spans are open as ``_Phase``
          "thread": None, "depth": 0}


def tracing() -> bool:
    return _STATE["tracing"]


def set_tracing(on: bool):
    """Flipped by ``profiler.trace_window`` enter/exit, between
    ``start_trace`` and ``stop_trace``. Turning tracing off
    force-closes any open round marker first, so its end timestamp
    lands inside the trace. Either way one ``fed_clock`` annotation
    ties ``clock.tick`` to the trace's clock."""
    if not on:
        end_round_marker()
    _STATE["tracing"] = bool(on)
    _STATE["thread"] = threading.get_ident() if on else None
    _clock_mark()


def _clock_mark():
    import jax
    name = f"{CLOCK_PREFIX}::{int(clock.tick() * 1e9)}"
    with jax.profiler.TraceAnnotation(name):
        pass


def begin_round_marker(round_index: int):
    """Open round ``round_index``'s StepTraceAnnotation (closing the
    previous round's). No-op unless a trace window is active."""
    if not _STATE["tracing"]:
        return
    end_round_marker()
    import jax
    ann = jax.profiler.StepTraceAnnotation(ROUND_MARKER,
                                           step_num=int(round_index))
    ann.__enter__()
    _STATE["ann"] = ann
    _STATE["round"] = int(round_index)


def end_round_marker():
    ann, _STATE["ann"] = _STATE["ann"], None
    _STATE["round"] = None
    if ann is not None:
        ann.__exit__(None, None, None)


class _NullPhase:
    """Shared, allocation-free no-op context manager."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


#: the one no-op span of the package (``core.NULL_SPAN`` is this object)
NULL_PHASE = _NullPhase()


class _Phase:
    """``fed_phase::<name>`` for the outermost span open on the round
    loop's thread; a span opened inside another adds no annotation.
    One level, one thread: whoever credits a stretch of the device's
    time to the phase over it (the benchmark's ``idle_gaps``) counts
    it once, and every other span is in the records' ``timeline``."""
    __slots__ = ("_name", "_ann")

    def __init__(self, name):
        self._name = name

    def __enter__(self):
        _STATE["depth"] += 1
        self._ann = None
        if _STATE["depth"] == 1:
            import jax
            self._ann = jax.profiler.TraceAnnotation(
                f"{PHASE_PREFIX}::{self._name}")
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        _STATE["depth"] -= 1
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


def phase(name: str):
    """Context manager: the ``fed_phase::<name>`` annotation (of the
    outermost span open) when tracing and on the thread that opened
    the window, the shared no-op otherwise. What ``Telemetry.span``
    opens; nothing else calls it."""
    if not _STATE["tracing"] or threading.get_ident() != _STATE["thread"]:
        return NULL_PHASE
    return _Phase(name)


# --- trace file discovery + loading ------------------------------------


def find_trace_file(logdir: str):
    """Newest ``*.trace.json.gz`` under ``logdir`` (searched at any
    depth: jax writes ``plugins/profile/<timestamp>/<host>.trace.
    json.gz``). None when the profiler wrote nothing."""
    pats = (os.path.join(logdir, "**", "*.trace.json.gz"),
            os.path.join(logdir, "**", "*.trace.json"))
    hits = []
    for pat in pats:
        hits.extend(glob.glob(pat, recursive=True))
    if not hits:
        return None
    return max(hits, key=os.path.getmtime)


def load_trace_events(path_or_logdir: str):
    """Chrome trace-event list from a ``.trace.json(.gz)`` file, or
    from the newest one under a directory."""
    path = path_or_logdir
    if os.path.isdir(path):
        path = find_trace_file(path)
        if path is None:
            raise FileNotFoundError(
                f"no .trace.json(.gz) under {path_or_logdir}")
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc) if isinstance(doc, dict) \
        else doc
    return [e for e in events if isinstance(e, dict)]


# --- lane classification -----------------------------------------------


def _lane_names(events):
    """(pid -> process_name, (pid, tid) -> thread_name) from the
    ``ph:"M"`` metadata events."""
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        name = (e.get("args") or {}).get("name", "")
        if e.get("name") == "process_name":
            procs[e.get("pid")] = name
        elif e.get("name") == "thread_name":
            threads[(e.get("pid"), e.get("tid"))] = name
    return procs, threads


def lane_devices(events):
    """(pid, tid) -> device id for every device-side execution lane.

    TPU/GPU xplanes expose one ``/device:<KIND>:<N>`` process per
    device — every thread under it belongs to that device, so the id
    is the process-name suffix (``TPU:0``), except the ``Steps`` line
    (``NOT_OPERATIONS``): counted as operations its step-long events
    make a device look busy all the time. The CPU backend runs each
    virtual device on a ``tf_XLA*`` runtime thread; each such thread
    is its own lane, labelled ``cpu:<n>`` by the trailing integer of
    the thread name (stable across a run, unlike raw tids)."""
    procs, threads = _lane_names(events)
    out = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        key = (e.get("pid"), e.get("tid"))
        if key in out:
            continue
        pname = procs.get(key[0], "")
        tname = threads.get(key, "")
        if pname.startswith("/device:"):
            if tname not in NOT_OPERATIONS:
                out[key] = pname[len("/device:"):]
        elif tname.startswith("tf_XLA"):
            m = re.search(r"(\d+)$", tname)
            out[key] = "cpu:%s" % (m.group(1) if m else key[1])
    return out


def device_lanes(events):
    """(pid, tid) pairs whose events are device-side execution:
    ``/device:*`` processes (TPU/GPU xplanes) or ``tf_XLA*`` runtime
    threads (the CPU backend's per-device execution threads)."""
    return set(lane_devices(events))


# --- interval math -----------------------------------------------------


def _union(intervals):
    """Merged, sorted interval list — nested/overlapping device events
    (module > fusion > op) collapse to their covering span."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _measure(merged):
    return sum(b - a for a, b in merged)


def _clip(intervals, lo, hi):
    out = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b))
    return out


def _subtract(a, b):
    """``a \\ b`` for merged, sorted interval lists — a lane's compute
    slice is its busy union minus its collective/transfer cover."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            s, e = b[k]
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _intersect(a, b):
    """``a ∩ b`` for merged, sorted interval lists — the overlapped
    bucket is collective ∩ (some lane's compute)."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


# --- per-round attribution ---------------------------------------------


def round_windows(events):
    """[(round_index, ts_us, end_us), ...] from the ``fed_round``
    StepTraceAnnotations, in timeline order. Each window is the
    annotation's own extent (begin_round -> next begin_round /
    trace-window exit), taken from host threads only: a TPU device
    process repeats the marker on its ``Steps`` line with the
    device's busy extent in place of the round's."""
    procs, _ = _lane_names(events)
    wins = []
    for e in events:
        if e.get("ph") != "X" or e.get("name") != ROUND_MARKER:
            continue
        if procs.get(e.get("pid"), "").startswith("/device:"):
            continue
        args = e.get("args") or {}
        step = args.get("step_num", args.get("round"))
        if step is None:
            continue
        ts, dur = float(e.get("ts", 0.0)), float(e.get("dur", 0.0))
        wins.append((int(step), ts, ts + dur))
    wins.sort(key=lambda w: w[1])
    return wins


def _classify(name: str) -> str:
    low = name.lower()
    if any(t in low for t in COLLECTIVE_TOKENS):
        return "collective"
    if any(t in low for t in TRANSFER_TOKENS):
        return "transfer"
    return "compute"


def _collective_groups(coll_by_dev, lo, hi):
    """Align matching collective events across devices inside one
    round window.

    ``coll_by_dev``: device -> [(op_name, ts, end), ...]. Each
    device's in-window occurrences of an op name are sorted by start;
    the k-th occurrence on every device forms one *group* (the same
    HLO collective executes once per participant, so equal names +
    occurrence rank is the alignment key). Returns
    ``[{device: (enter, exit)}, ...]`` with enters/exits clipped to
    the window."""
    per = {}
    for dev, insts in coll_by_dev.items():
        for name, ts, end in insts:
            a, b = max(ts, lo), min(end, hi)
            if b > a:
                per.setdefault(name, {}).setdefault(dev, []).append((a, b))
    groups = []
    for name in sorted(per):
        by_dev = per[name]
        for occ in by_dev.values():
            occ.sort()
        depth = max(len(occ) for occ in by_dev.values())
        for k in range(depth):
            groups.append({d: occ[k]
                           for d, occ in sorted(by_dev.items())
                           if k < len(occ)})
    return groups


def _p95(values):
    if not values:
        return 0.0
    vals = sorted(values)
    # nearest-rank: matches the ledger's other percentile fields
    idx = max(0, int(round(0.95 * len(vals) + 0.5)) - 1)
    return vals[min(idx, len(vals) - 1)]


def _skew_stats(groups):
    """Per-device wait intervals + round skew stats from the aligned
    collective groups of one window.

    For a group entered last at ``last_enter``, a device's *wait* is
    ``[enter, min(last_enter, exit)]`` — the straggler-skew slice of
    its collective time; the remainder is *wire*. Single-participant
    groups contribute no wait (all wire). The straggler device is the
    one that caused the most waiting: argmax over devices of the
    summed enter-delta of the groups it entered last."""
    wait_iv = {}
    deltas, caused = [], {}
    for g in groups:
        if len(g) < 2:
            continue
        enters = {d: iv[0] for d, iv in g.items()}
        last_enter = max(enters.values())
        delta = last_enter - min(enters.values())
        deltas.append(delta)
        # deterministic straggler on ties: largest enter, then id
        straggler = max(sorted(g), key=lambda d: (enters[d], d))
        caused[straggler] = caused.get(straggler, 0.0) + delta
        for d, (a, b) in g.items():
            w = min(last_enter, b)
            if w > a:
                wait_iv.setdefault(d, []).append((a, w))
    stats = {
        "n_collectives": len(deltas),
        "max_enter_delta_s": round(max(deltas) / 1e6, 9) if deltas else 0.0,
        "p95_enter_delta_s": round(_p95(deltas) / 1e6, 9),
        "straggler_device": (max(sorted(caused), key=lambda d: caused[d])
                             if caused else None),
    }
    return wait_iv, stats


def attribute_rounds(events) -> dict:
    """Per-round device-time buckets from one trace's events:

        {round_index: {"window_s", "busy_s", "compute_s",
                       "collective_s", "transfer_s", "host_gap_s",
                       "overlapped_s",
                       "per_device": {device_id: {...}},
                       "skew": {...}}}

    ``busy`` is the union of all device-lane events clipped to the
    round window (parallel lanes don't double-count wall time);
    collective/transfer are the unions of the matching-named events;
    ``compute = busy - collective - transfer`` and ``host_gap =
    window - busy``, so the four buckets sum to the window exactly.
    The aggregate buckets pool every lane's intervals — identical to
    the schema-v3 computation bit-for-bit.

    ``overlapped_s`` is the slice of ``collective_s`` that ran
    concurrently with some lane's compute (pooled collective union ∩
    union of per-lane compute) — an overlay on the partition, not a
    fifth bucket: the four buckets above still sum to the window
    exactly, and ``collective_s - overlapped_s`` is the serial
    collective share the --overlap_depth pipeline is built to
    collapse.

    ``per_device[<id>]`` repeats the bucket math on that device's own
    interval set and splits its collective bucket into ``wait_s``
    (straggler skew, from the cross-device alignment of matching
    collectives) and ``wire_s = collective_s - wait_s`` — an exact
    partition by construction. ``skew`` carries the round-level stats
    (max/p95 enter-delta, straggler device id, matched-group count).
    """
    wins = round_windows(events)
    if not wins:
        return {}
    lanes = lane_devices(events)
    dev, coll, xfer = [], [], []
    by_dev = {}          # device -> {"dev": [...], "coll": [...], "xfer": [...]}
    coll_insts = {}      # device -> [(op_name, ts, end), ...]
    for e in events:
        key = (e.get("pid"), e.get("tid"))
        if e.get("ph") != "X" or key not in lanes:
            continue
        name = e.get("name", "")
        if name == ROUND_MARKER or name.startswith(PHASE_PREFIX):
            continue
        ts, dur = float(e.get("ts", 0.0)), float(e.get("dur", 0.0))
        iv = (ts, ts + dur)
        dev.append(iv)
        d = lanes[key]
        slot = by_dev.setdefault(d, {"dev": [], "coll": [], "xfer": []})
        slot["dev"].append(iv)
        kind = _classify(name)
        if kind == "collective":
            coll.append(iv)
            slot["coll"].append(iv)
            coll_insts.setdefault(d, []).append((name, iv[0], iv[1]))
        elif kind == "transfer":
            xfer.append(iv)
            slot["xfer"].append(iv)
    dev, coll, xfer = _union(dev), _union(coll), _union(xfer)
    for slot in by_dev.values():
        for k in slot:
            slot[k] = _union(slot[k])

    out = {}
    for ridx, lo, hi in wins:
        busy = _union(_clip(dev, lo, hi))
        c = _union(_clip(coll, lo, hi))
        t = _union(_clip(xfer, lo, hi))
        busy_us = _measure(busy)
        coll_us = _measure(c)
        # transfer time that isn't already counted as collective
        # (disjoint buckets: the four sum to the window)
        xfer_us = _measure(_union(t + c)) - coll_us
        win_us = hi - lo
        # overlapped: wall time where the pooled collective union runs
        # concurrently with some lane's COMPUTE (its busy minus its
        # own collective/transfer cover) — the slice of collective_s
        # the --overlap_depth pipeline hid behind compute. An overlay
        # on the partition, not a fifth bucket: compute + collective +
        # transfer + host_gap still sum to the window exactly, and
        # 0 <= overlapped_s <= collective_s; collective_s -
        # overlapped_s is the SERIAL collective share.
        comp_iv = []
        for slot in by_dev.values():
            d_busy = _union(_clip(slot["dev"], lo, hi))
            d_other = _union(_clip(slot["coll"], lo, hi)
                             + _clip(slot["xfer"], lo, hi))
            comp_iv.extend(_subtract(d_busy, d_other))
        ovl_us = _measure(_intersect(c, _union(comp_iv)))
        buckets = {
            "window_s": round(win_us / 1e6, 6),
            "busy_s": round(busy_us / 1e6, 6),
            "compute_s": round((busy_us - coll_us - xfer_us) / 1e6, 6),
            "collective_s": round(coll_us / 1e6, 6),
            "transfer_s": round(xfer_us / 1e6, 6),
            "host_gap_s": round((win_us - busy_us) / 1e6, 6),
            "overlapped_s": round(min(ovl_us, coll_us) / 1e6, 6),
        }
        groups = _collective_groups(coll_insts, lo, hi)
        wait_iv, skew = _skew_stats(groups)
        per_device = {}
        for d in sorted(by_dev):
            slot = by_dev[d]
            d_busy_us = _measure(_union(_clip(slot["dev"], lo, hi)))
            d_c = _union(_clip(slot["coll"], lo, hi))
            d_t = _union(_clip(slot["xfer"], lo, hi))
            d_coll_us = _measure(d_c)
            d_xfer_us = _measure(_union(list(d_t) + list(d_c))) - d_coll_us
            d_wait_us = _measure(_union(_clip(wait_iv.get(d, ()), lo, hi)))
            coll_s = round(d_coll_us / 1e6, 6)
            wait_s = round(min(d_wait_us, d_coll_us) / 1e6, 6)
            per_device[d] = {
                "busy_s": round(d_busy_us / 1e6, 6),
                "compute_s": round(
                    (d_busy_us - d_coll_us - d_xfer_us) / 1e6, 6),
                "collective_s": coll_s,
                "transfer_s": round(d_xfer_us / 1e6, 6),
                "wait_s": wait_s,
                # difference of two 6-dp values: wait + wire ==
                # collective holds exactly, not just to tolerance
                "wire_s": round(coll_s - wait_s, 6),
            }
        buckets["per_device"] = per_device
        buckets["skew"] = skew
        out[ridx] = buckets
    return out


def attribute_logdir(logdir: str) -> dict:
    """``attribute_rounds`` over the newest trace under ``logdir``;
    empty dict when no trace file exists."""
    path = find_trace_file(logdir)
    if path is None:
        return {}
    return attribute_rounds(load_trace_events(path))


# --- host spans on the trace's clock -----------------------------------


def clock_offset_us(events):
    """Microseconds to add to ``clock.tick() * 1e6`` to get a trace
    ``ts``, from the ``fed_clock::<tick ns>`` annotations (the mean of
    those the trace holds); None when it holds none."""
    offs = []
    for e in events:
        name = e.get("name", "")
        if e.get("ph") == "X" and name.startswith(CLOCK_PREFIX + "::"):
            tick_ns = int(name[len(CLOCK_PREFIX) + 2:])
            offs.append(float(e["ts"]) - tick_ns / 1e3)
    return sum(offs) / len(offs) if offs else None


def host_timeline(events, records):
    """The round records' ``timeline`` entries in trace time:
    ``[{"round", "name", "ts", "end", "parent", "thread"}, ...]``
    with ``ts``/``end`` in the trace's microseconds, sorted by start;
    ``parent`` indexes the same round's timeline. Entries still open
    when their record was written (no end) are left out. Empty when
    the trace carries no ``fed_clock`` annotation."""
    off = clock_offset_us(events)
    if off is None:
        return []
    out = []
    for rec in records:
        for name, t0, t1, parent, thread in rec.get("timeline") or ():
            if t1 is None:
                continue
            out.append({"round": rec.get("round"), "name": name,
                        "ts": t0 * 1e6 + off, "end": t1 * 1e6 + off,
                        "parent": parent, "thread": thread})
    out.sort(key=lambda s: s["ts"])
    return out
