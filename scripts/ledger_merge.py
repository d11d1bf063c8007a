"""Merge per-process ledger shards into one fleet ledger.

    python scripts/ledger_merge.py runs/a.jsonl
        discovers runs/a.jsonl.p1.jsonl, runs/a.jsonl.p2.jsonl, ...
        (the per-process shards telemetry/core.py writes on multi-host
        meshes) and writes runs/a.jsonl.merged.jsonl

Process 0 owns the canonical ledger — its round records carry the
replicated accounting arrays and the trace-derived device_time. Every
other process's shard carries what only THAT host observed: its
host-phase spans, RSS watermarks, locally-observed bytes, and (when
it traced) its own device_time. The merge joins shards on round id:

* each canonical round record gains ``shards`` —
  ``{"p<k>": {spans, counters, host_rss_peak_bytes, uplink_bytes,
  downlink_bytes, host_gap_s}}`` — plus ``host_gap_by_process``, the
  per-host host-gap seconds (the honest multi-host scoreboard: one
  host stalling shows up as ITS gap, not averaged away);
* shard rounds missing from the canonical ledger are appended in
  round order with ``shard_only: true`` (a host that kept going after
  process 0 died is data, not garbage);
* shard meta/bench/epoch records are dropped (the canonical copies
  are authoritative); the count is reported.

Per-JOB shards from a fedservice daemon run —
``runs/a.jsonl.job<j>.jsonl``, one solo-equivalent ledger per tenant
(telemetry/sinks.py ``job_ledger_path``) — are discovered alongside
the ``.p<k>`` process shards. Unlike process shards, job rounds are
INDEPENDENT round streams (round 3 of job 0 and round 3 of job 1 are
different rounds), so they cannot join on round id: every job record
is instead appended after the canonical stream stamped with
``"job": j``, rounds in order within each job. A multi-host daemon
produces the full matrix — ``runs/a.jsonl.job<j>.jsonl.p<k>.jsonl``
sub-shards per job — which are discovered per job shard and joined
on round id WITHIN the job (same rules as the top-level process
merge) before the job stream is appended.

``scripts/telemetry_report.py`` renders merged ledgers with a
per-shard summary block. Pure host-side JSON work: no jax import.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from commefficient_tpu.telemetry.record import validate_record  # noqa: E402

MERGED_SUFFIX = ".merged.jsonl"

#: round-record keys a shard contributes to the merged view (what the
#: observing process measured locally; device_time collapses to its
#: host-gap bucket)
SHARD_VIEW_KEYS = ("spans", "counters", "host_rss_peak_bytes",
                   "uplink_bytes", "downlink_bytes")


def discover_shards(path: str) -> list:
    """[(process_index, shard_path), ...] for a canonical ledger
    path, sorted by process index (telemetry/sinks.py
    shard_ledger_path layout)."""
    hits = []
    for shard in glob.glob(glob.escape(path) + ".p*.jsonl"):
        m = re.match(re.escape(path) + r"\.p(\d+)\.jsonl$", shard)
        if m:
            hits.append((int(m.group(1)), shard))
    return sorted(hits)


def discover_job_shards(path: str) -> list:
    """[(job_index, shard_path), ...] for a fedservice base ledger
    path, sorted by job index (telemetry/sinks.py job_ledger_path
    layout)."""
    hits = []
    for shard in glob.glob(glob.escape(path) + ".job*.jsonl"):
        m = re.match(re.escape(path) + r"\.job(\d+)\.jsonl$", shard)
        if m:
            hits.append((int(m.group(1)), shard))
    return sorted(hits)


def merge_job_shards(merged, job_records: dict) -> tuple:
    """Append per-job shard records to a merged stream, each stamped
    ``"job": j``. ``job_records``: {job_index: [records, ...]}.
    Returns (records, stats)."""
    out = list(merged)
    appended = 0
    for j, records in sorted(job_records.items()):
        for rec in records:
            rec = dict(rec)
            rec["job"] = int(j)
            out.append(rec)
            appended += 1
    stats = {"job_records": appended,
             "jobs": sorted(int(j) for j in job_records)}
    return out, stats


def load_records(path: str) -> tuple:
    """(records, problems) from one JSONL ledger; bad lines are
    skipped, not fatal."""
    records, problems = [], []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as exc:
                problems.append(f"{path}:{lineno}: not JSON ({exc})")
                continue
            issues = validate_record(rec)
            if issues:
                problems.append(
                    f"{path}:{lineno}: " + "; ".join(issues))
                continue
            records.append(rec)
    return records, problems


def _host_gap_s(rec):
    dt = rec.get("device_time")
    if isinstance(dt, dict):
        hg = dt.get("host_gap_s")
        if isinstance(hg, (int, float)):
            return hg
    return None


def _shard_view(rec) -> dict:
    view = {}
    for key in SHARD_VIEW_KEYS:
        if rec.get(key) is not None:
            view[key] = rec[key]
    hg = _host_gap_s(rec)
    if hg is not None:
        view["host_gap_s"] = hg
    return view


def merge_ledgers(canonical_records, shard_records: dict) -> tuple:
    """Join shard round records onto the canonical ones by round id.

    ``shard_records``: {process_index: [records, ...]}. Returns
    (merged_records, stats) where stats counts joined / shard-only
    rounds and dropped non-round shard records."""
    shard_rounds = {}       # round id -> {"p<k>": round record}
    dropped = 0
    for k, records in sorted(shard_records.items()):
        for rec in records:
            if rec.get("kind") == "round":
                shard_rounds.setdefault(
                    rec["round"], {})[f"p{int(k)}"] = rec
            else:
                dropped += 1
    merged, joined = [], 0
    seen_rounds = set()
    for rec in canonical_records:
        if rec.get("kind") != "round":
            merged.append(rec)
            continue
        ridx = rec["round"]
        seen_rounds.add(ridx)
        shards = shard_rounds.get(ridx)
        if not shards:
            merged.append(rec)
            continue
        joined += 1
        rec = dict(rec)
        rec["shards"] = {pk: _shard_view(sh)
                         for pk, sh in sorted(shards.items())}
        gaps = {}
        hg0 = _host_gap_s(rec)
        if hg0 is not None:
            gaps["p0"] = hg0
        for pk, sh in sorted(shards.items()):
            hg = _host_gap_s(sh)
            if hg is not None:
                gaps[pk] = hg
        if gaps:
            rec["host_gap_by_process"] = gaps
        merged.append(rec)
    # rounds only a shard saw (e.g. process 0 died first): keep them,
    # flagged, in round order after the canonical stream
    orphans = []
    for ridx in sorted(set(shard_rounds) - seen_rounds):
        for pk, sh in sorted(shard_rounds[ridx].items()):
            orphan = dict(sh)
            orphan["shard_only"] = True
            orphans.append(orphan)
    merged.extend(orphans)
    stats = {"joined_rounds": joined, "shard_only_rounds": len(orphans),
             "dropped_shard_records": dropped,
             "shards": sorted(int(k) for k in shard_records)}
    return merged, stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="merge per-process ledger shards on round id")
    ap.add_argument("ledger",
                    help="canonical (process-0) ledger path; shards "
                         "are discovered as <ledger>.p<k>.jsonl")
    ap.add_argument("-o", "--out", default=None,
                    help=f"output path (default <ledger>{MERGED_SUFFIX})")
    args = ap.parse_args(argv)

    canonical, problems = load_records(args.ledger)
    shards = discover_shards(args.ledger)
    shard_records = {}
    for k, spath in shards:
        recs, probs = load_records(spath)
        shard_records[k] = recs
        problems.extend(probs)
    job_shards = discover_job_shards(args.ledger)
    job_records = {}
    job_subs = 0
    for j, jpath in job_shards:
        recs, probs = load_records(jpath)
        problems.extend(probs)
        # multi-host daemon: each job shard may have its own process
        # sub-shards (<base>.job<j>.jsonl.p<k>.jsonl) — join them on
        # round id WITHIN the job before appending the job stream
        subs = discover_shards(jpath)
        if subs:
            sub_records = {}
            for k, spath in subs:
                srecs, sprobs = load_records(spath)
                sub_records[k] = srecs
                problems.extend(sprobs)
            recs, substats = merge_ledgers(recs, sub_records)
            job_subs += len(subs)
            print(f"job {j}: joined {len(subs)} process "
                  f"sub-shard(s), {substats['joined_rounds']} "
                  f"round(s) joined, "
                  f"{substats['shard_only_rounds']} shard-only")
        job_records[j] = recs
    for p in problems:
        print(f"WARNING {p}", file=sys.stderr)
    if not shards and not job_shards:
        print(f"{args.ledger}: no shards found (expected "
              f"{args.ledger}.p<k>.jsonl or .job<j>.jsonl) — "
              "nothing to merge")
        return 1

    merged, stats = merge_ledgers(canonical, shard_records)
    merged, job_stats = merge_job_shards(merged, job_records)
    out = args.out or (args.ledger + MERGED_SUFFIX)
    with open(out, "w") as f:
        for rec in merged:
            json.dump(rec, f, separators=(",", ":"))
            f.write("\n")
    print(f"{args.ledger} + shards p{stats['shards']} "
          f"+ jobs {job_stats['jobs']}: "
          f"{stats['joined_rounds']} round(s) joined, "
          f"{stats['shard_only_rounds']} shard-only, "
          f"{job_stats['job_records']} job record(s) appended, "
          f"{stats['dropped_shard_records']} non-round shard "
          f"record(s) dropped -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
