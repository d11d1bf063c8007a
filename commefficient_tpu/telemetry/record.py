"""Ledger record schema (version 9).

A run ledger is a JSONL file: one self-describing record per line.
Every record carries ``schema`` (this module's version) and ``kind``:

``meta``   — one per run, first line: the static description of the
             round program (mode, grad_size, geometry, the
             ``core.rounds.round_plan`` dict) so a ledger is
             interpretable without the launching command line.
``round``  — one per TRAINING round: wall-time spans (seconds) for
             sampler / gather / h2d / round dispatch / metrics
             materialisation / server step / write-back, counters
             (clientstore prefetch hit-vs-miss, compile events),
             uplink/downlink bytes (identical to FedModel's
             accounting counters), and host-RSS / HBM peak
             watermarks.
``epoch``  — the trainer's per-epoch TableLogger row.
``bench``  — a headline metric the timing scripts of before PR 47
             appended to a run ledger; nothing writes one now, and
             older ledgers still validate.
``summary``— end-of-run aggregate (ConsoleSink's closing record).

Span attribution note: the ``sampler`` span measures fetching the
NEXT round's batch and is attributed to the round that is open while
the fetch happens (the first fetch of a run precedes any round and is
not recorded).

Schema v2 (backward-readable — readers accept both versions) adds two
keys to round records:

``probes`` — None when probing is off, else the round's algorithm
             diagnostics dict (core/rounds.py + core/server.py probe
             outputs: update/residual/momentum norms, NaN/Inf counts,
             mass coverage, sketch-recovery error, host-derived
             residual growth ratio). Keys vary by mode and cadence.
``alarms`` — list of alarm dicts appended by telemetry/alarms.py
             ({"rule", "value", "threshold", "action"}); empty when
             nothing fired. A round that triggered ``--on_divergence
             abort`` is the flagged final record of the run.

Schema v3 adds one key to round records:

``device_time`` — None unless the round ran inside a profiler trace
             window (``--profile``), else the parsed device-timeline
             buckets (telemetry/trace.py attribute_rounds): window_s /
             busy_s / compute_s / collective_s / transfer_s /
             host_gap_s. compute + collective + transfer + host_gap
             == window by construction. Any other numeric key
             validates: ledgers from before PR 47 carry a
             ``roofline_utilization`` bucket nothing writes now.

Schema v4 is a fleet extension — no new required keys, two content
changes:

``device_time.per_device`` / ``device_time.skew`` — the aggregate
             buckets gain nested per-device buckets ({busy, compute,
             collective, transfer, wait, wire} per device lane; wait
             + wire == collective exactly) and round-level collective
             skew stats (max/p95 enter-delta seconds, straggler
             device id, matched-collective count). These are the only
             dict-valued entries allowed inside ``device_time``.
             Additive numeric buckets stay schema-4: ``overlapped_s``
             (collective wall time hidden behind some lane's compute,
             telemetry/trace.py — ``collective_s - overlapped_s`` is
             the serial collective share) appears on traces parsed
             after --overlap_depth landed; readers treat any extra
             numeric bucket generically.
``process`` — optional on every record: the jax process index that
             observed it. Stamped by the per-process ledger shards
             (``<ledger>.p<k>.jsonl``, telemetry/core.py) so merged
             multi-host ledgers (scripts/ledger_merge.py) stay
             attributable.

Schema v5 adds three keys to round records (the DP ledger trail,
privacy/):

``dp_epsilon`` — None outside ``--dp sketch`` runs, else the
             accountant's cumulative ε(δ) AFTER this round was
             charged — the record stream is the spend trajectory, and
             the ``privacy_budget_exhausted`` alarm reads the same
             value.
``dp_delta``   — the δ the ε above is stated at (``--dp_delta``);
             None outside DP runs.
``dp_sigma``   — the effective noise multiplier this round was
             charged at (the dispatched variant's ``dp_noise_mult``
             over the round's staleness weight scale); None outside
             DP runs.

Schema v6 adds one key to round records (the live operations plane,
telemetry/slo.py + telemetry/flightrec.py) and two summary-record
conventions:

``slo``    — None unless an SLO engine evaluated the round, else the
             per-objective {target, seen, fast_rate, slow_rate,
             burn} snapshot (``SLOEngine.stamp``) taken after the
             round's observation — the ledger twin of the
             ``slo_burn_*`` probe keys the same engine merges into
             ``probes``.
``alarm_fired`` — summary records (and only summary records) may
             carry the run's alarm totals by rule
             ({rule: count}); ``Telemetry.close`` emits one when any
             alarm fired, so ``telemetry_report.py`` shows alarm
             totals without scanning every round record.
``postmortem`` — not a ledger record: flight-recorder bundles
             (kind ``postmortem``, telemetry/flightrec.py) are
             standalone JSON files under ``runs/postmortems/`` whose
             ``rounds`` list holds schema-validated round records;
             the run registry's ``postmortem``/``postmortem_reason``
             manifest keys are the lineage stamp.

Schema v7 added one optional round-record key, ``causal``, that
nothing writes any more; a ledger that carries it still validates, the
key unread.

Schema v8 adds NO required keys — two optional round-record keys (the
one span model, telemetry/core.py):

``timeline`` — the round's host spans, one ``[name, t0, t1, parent,
             thread]`` entry each, in the order they opened:
             ``clock.tick`` seconds (``t1`` None for a span still open
             when the record was written), ``parent`` the index in
             this list of the span open on the same thread when this
             one opened (None at the top), ``thread`` the thread's
             name. At most ``TIMELINE_CAP`` entries a round; what did
             not fit is counted in ``counters.timeline_dropped`` (the
             spans' seconds still accumulate in ``spans``).
             ``trace.host_timeline`` moves the entries onto a profiler
             trace's clock by its ``fed_clock`` annotations.
``hbm_reserved_peak_bytes`` — ``peak_bytes_reserved`` of device 0
             beside ``hbm_peak_bytes`` (``peak_bytes_in_use``): on the
             TPU the round programs' temporaries are reserved, not
             allocated, so the chip's peak is the sum. None
             off-accelerator.

Schema v9 adds NO required keys — three optional round-record keys
and a family of counters (the resource clock, telemetry/core.py):

``cpu``    — CPU seconds by span name, accumulated exactly as
             ``spans`` accumulates wall seconds: the calling thread's
             own clock (``clock.thread_cpu``), so a span's wall minus
             its CPU is the time its thread did not run (a wait for
             the device, a lock, the interpreter lock, a page fault's
             disk, a core).
``timeline_cpu`` — one number an entry of ``timeline``, same index:
             that span's CPU seconds, None while it is open.
             ``timeline`` entries keep their five fields.
``stall``  — None unless the round stayed open past
             ``core.stall_limit`` (max(1 s, 8 x the median of the last
             32 periods)): ``{"after_s": seconds after the round
             opened at which the stacks were taken, "threads": {thread
             name: [innermost frame first, "file:line function", at
             most 8]}}``, at most 4 KB, taken once by the recorder's
             own thread; ``counters["stall.captured"]`` marks it.
``counters["host.*"]`` — the process's counters from the previous
             record's finishing to this one's, one ``getrusage`` call:
             ``host.cpu_user_s`` / ``host.cpu_sys_s`` (all threads),
             ``host.minflt`` / ``host.majflt``, ``host.nvcsw`` /
             ``host.nivcsw``, ``host.gc_s`` / ``host.gc_runs`` (the
             collector, through ``gc.callbacks``), ``host.throttled_s``
             (the cgroup's ``cpu.stat``; absent where there is none).
             The run's first record also carries ``host.cpus`` (the
             cores the process may run on), ``host.threads`` (Python
             threads + the native rings' workers) and
             ``host.os_threads``. ``host_rss_peak_bytes`` is that
             call's ``ru_maxrss``.
"""

from __future__ import annotations

from commefficient_tpu.telemetry import clock

LEDGER_SCHEMA_VERSION = 9

# versions validate_record accepts: v1 (pre-probe), v2 (pre-trace),
# v3 (pre-fleet), v4 (pre-DP), v5 (pre-SLO), v6 (pre-causal), v7
# (pre-timeline) and v8 (pre-CPU) ledgers stay readable by the report
# tooling
READABLE_SCHEMA_VERSIONS = (1, 2, 3, 4, 5, 6, 7, 8, 9)

# most timeline entries one round record keeps
TIMELINE_CAP = 256

# device_time keys whose values are nested dicts (v4); every other
# bucket value must be numeric
DEVICE_TIME_DICT_KEYS = ("per_device", "skew")

KINDS = ("meta", "round", "epoch", "bench", "summary")

# keys every round record must carry (values may be None where noted)
ROUND_REQUIRED_KEYS = (
    "schema", "kind", "ts", "round", "spans", "counters",
    "uplink_bytes", "downlink_bytes",      # None until accounted
    "host_rss_peak_bytes",                 # None off-Linux
    "hbm_peak_bytes",                      # None off-accelerator
)

# v2 additions (not required of v1 records)
ROUND_V2_KEYS = (
    "probes",                              # None with probing off
    "alarms",                              # [] when nothing fired
)

# v3 additions (not required of v1/v2 records)
ROUND_V3_KEYS = (
    "device_time",                         # None outside --profile
)

# v5 additions (not required of v1-v4 records)
ROUND_V5_KEYS = (
    "dp_epsilon",                          # None outside --dp runs
    "dp_delta",                            # None outside --dp runs
    "dp_sigma",                            # None outside --dp runs
)

# v6 additions (not required of v1-v5 records)
ROUND_V6_KEYS = (
    "slo",                                 # None without an SLO engine
)


def _base(kind: str) -> dict:
    return {"schema": LEDGER_SCHEMA_VERSION, "kind": kind,
            "ts": clock.wall()}


def make_meta_record(**fields) -> dict:
    rec = _base("meta")
    rec.update(fields)
    return rec


def make_round_record(round_index: int) -> dict:
    rec = _base("round")
    rec.update({
        "round": int(round_index),
        "spans": {},
        "counters": {},
        "uplink_bytes": None,
        "downlink_bytes": None,
        "host_rss_peak_bytes": None,
        "hbm_peak_bytes": None,
        "probes": None,
        "alarms": [],
        "device_time": None,
        "dp_epsilon": None,
        "dp_delta": None,
        "dp_sigma": None,
        "slo": None,
        "timeline": [],
        "hbm_reserved_peak_bytes": None,
        "cpu": {},
        "timeline_cpu": [],
        "stall": None,
    })
    return rec


def make_epoch_record(row: dict, epoch: int) -> dict:
    rec = _base("epoch")
    rec["epoch"] = int(epoch)
    rec["row"] = {k: v for k, v in row.items()}
    return rec


def make_summary_record(**fields) -> dict:
    rec = _base("summary")
    rec.update(fields)
    return rec


def _validate_timeline(timeline) -> list:
    """Problems with an optional v8 ``timeline`` (validated only when
    present)."""
    if not isinstance(timeline, list):
        return ["timeline is not a list"]
    if len(timeline) > TIMELINE_CAP:
        return [f"timeline holds more than {TIMELINE_CAP} entries"]
    problems = []
    for i, entry in enumerate(timeline):
        if not isinstance(entry, (list, tuple)) or len(entry) != 5:
            problems.append("timeline entry is not [name, t0, t1, "
                            "parent, thread]")
            continue
        name, t0, t1, parent, thread = entry
        if not isinstance(name, str) or not isinstance(thread, str):
            problems.append("timeline name/thread is not a string")
        if not isinstance(t0, (int, float)) or not (
                t1 is None or isinstance(t1, (int, float))):
            problems.append("timeline t0/t1 is non-numeric")
        if parent is not None and not (isinstance(parent, int)
                                       and 0 <= parent < i):
            problems.append("timeline parent is not an earlier index")
    return problems


def _validate_cpu(rec) -> list:
    """Problems with the optional v9 keys (validated only when
    present)."""
    problems = []
    cpu = rec.get("cpu", {})
    if not isinstance(cpu, dict) or any(
            not isinstance(v, (int, float)) for v in cpu.values()):
        problems.append("cpu is not a {span: seconds} dict")
    tcpu = rec.get("timeline_cpu")
    if tcpu is not None:
        if not isinstance(tcpu, list) or any(
                not (v is None or isinstance(v, (int, float)))
                for v in tcpu):
            problems.append("timeline_cpu is not a list of seconds")
        elif len(tcpu) != len(rec.get("timeline") or ()):
            problems.append("timeline_cpu is not the timeline's length")
    stall = rec.get("stall")
    if stall is not None and not (
            isinstance(stall, dict)
            and isinstance(stall.get("after_s"), (int, float))
            and isinstance(stall.get("threads"), dict)
            and all(isinstance(f, list) and all(
                isinstance(x, str) for x in f)
                for f in stall["threads"].values())):
        problems.append("stall is not {after_s, threads: {name: "
                        "[frames]}}")
    return problems


def validate_record(rec) -> list:
    """Schema check: a list of problem strings, empty when valid."""
    problems = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not dict"]
    schema = rec.get("schema")
    if schema not in READABLE_SCHEMA_VERSIONS:
        problems.append(f"schema {schema!r} not in "
                        f"{READABLE_SCHEMA_VERSIONS}")
    kind = rec.get("kind")
    if kind not in KINDS:
        problems.append(f"unknown kind {kind!r}")
    if not isinstance(rec.get("ts"), (int, float)):
        problems.append("ts missing or non-numeric")
    if kind == "round":
        required = ROUND_REQUIRED_KEYS
        if isinstance(schema, int) and schema >= 2:
            required = required + ROUND_V2_KEYS
        if isinstance(schema, int) and schema >= 3:
            required = required + ROUND_V3_KEYS
        if isinstance(schema, int) and schema >= 5:
            required = required + ROUND_V5_KEYS
        if isinstance(schema, int) and schema >= 6:
            required = required + ROUND_V6_KEYS
        for key in required:
            if key not in rec:
                problems.append(f"round record missing {key!r}")
        if not isinstance(rec.get("spans"), dict):
            problems.append("spans is not a dict")
        elif any(not isinstance(v, (int, float))
                 for v in rec["spans"].values()):
            problems.append("non-numeric span value")
        if not isinstance(rec.get("counters"), dict):
            problems.append("counters is not a dict")
        for key in ("uplink_bytes", "downlink_bytes") + ROUND_V5_KEYS:
            v = rec.get(key)
            if v is not None and not isinstance(v, (int, float)):
                problems.append(f"{key} is non-numeric")
        slo = rec.get("slo")
        if slo is not None and not isinstance(slo, dict):
            problems.append("slo is not a dict")
        if "timeline" in rec:              # optional (v8): validate
            problems.extend(_validate_timeline(rec["timeline"]))
        problems.extend(_validate_cpu(rec))       # optional (v9)
        v = rec.get("hbm_reserved_peak_bytes")    # optional (v8)
        if v is not None and not isinstance(v, (int, float)):
            problems.append("hbm_reserved_peak_bytes is non-numeric")
        dt = rec.get("device_time")
        if dt is not None:
            if not isinstance(dt, dict):
                problems.append("device_time is not a dict")
            else:
                for k, v in dt.items():
                    if k in DEVICE_TIME_DICT_KEYS:
                        if not isinstance(v, dict):
                            problems.append(
                                f"device_time.{k} is not a dict")
                    elif not isinstance(v, (int, float)):
                        problems.append("non-numeric device_time bucket")
    proc = rec.get("process")
    if proc is not None and not isinstance(proc, int):
        problems.append("process is non-integer")
    if kind == "bench":
        for key in ("metric", "value", "unit"):
            if key not in rec:
                problems.append(f"bench record missing {key!r}")
    if kind == "epoch" and not isinstance(rec.get("row"), dict):
        problems.append("epoch record missing row dict")
    if kind == "summary":
        fired = rec.get("alarm_fired")
        if fired is not None and (
                not isinstance(fired, dict)
                or any(not isinstance(v, (int, float))
                       for v in fired.values())):
            problems.append("alarm_fired is not a {rule: count} dict")
    return problems
