"""Render or diff run ledgers (commefficient_tpu/telemetry JSONL).

    python scripts/telemetry_report.py runs/a.jsonl
        one-run summary: round program, per-span totals/means, comm
        byte totals, counters, memory watermarks, epoch table, bench
        records

    python scripts/telemetry_report.py runs/a.jsonl runs/b.jsonl
        diff two ledgers: per-span mean deltas, comm/byte deltas,
        bench metric ratios — the "did my change help" view

    python scripts/telemetry_report.py --runs_dir runs
        registry mode: list recent manifest-registered runs
        (telemetry/registry.py), summarize the latest run's ledger,
        diff it against the previous COMPARABLE run (same config hash
        AND same (device_count, process_count) topology — an 8-device
        run never diffs against a single-chip one), and render any
        scaling curves (manifests that carry a ``scaling`` dict) found
        in the registry — no hand-typed paths

    python scripts/telemetry_report.py --audit
        findings diff: the committed audit_baseline.json vs a fresh
        two-tier lint run — waived/new/fixed counts per rule, the
        "did this branch move the static-analysis needle" view

Schema-v3 ledgers additionally render the trace-derived device-time
breakdown (compute / collective / transfer / host-gap per round) next
to the host-span percentiles. Schema-v4
ledgers add per-device lanes (busy/collective/wait/wire per device),
round collective-skew stats, and — for merged multi-host ledgers
(scripts/ledger_merge.py) — per-process shard summaries with each
host's gap. ``--json`` prints the summary (or diff) as one JSON
object instead of text. Invalid records are reported but don't abort
the render.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from commefficient_tpu.telemetry.record import validate_record  # noqa: E402


def load_ledger(path):
    """Parse a JSONL ledger -> (records, problems). Problems carry
    the 1-based line number; bad lines are skipped, not fatal."""
    records, problems = [], []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as exc:
                problems.append(f"line {lineno}: not JSON ({exc})")
                continue
            issues = validate_record(rec)
            if issues:
                problems.append(
                    f"line {lineno}: " + "; ".join(issues))
                continue
            records.append(rec)
    return records, problems


def job_summaries(records, ledger_path=None) -> dict:
    """Per-job summaries for a fedservice daemon run:
    ``{job_index: summary}``. Job records come from ``"job": j``
    stamps in a merged ledger (scripts/ledger_merge.py), else from
    the ``<ledger>.job<j>.jsonl`` shards living next to
    ``ledger_path`` (telemetry/sinks.py job_ledger_path layout)."""
    import glob
    import re

    by_job = {}
    for r in records:
        j = r.get("job")
        if isinstance(j, int):
            by_job.setdefault(j, []).append(r)
    if not by_job and ledger_path:
        pat = re.compile(re.escape(ledger_path)
                         + r"\.job(\d+)\.jsonl$")
        for shard in glob.glob(glob.escape(ledger_path)
                               + ".job*.jsonl"):
            m = pat.match(shard)
            if m:
                recs, _ = load_ledger(shard)
                by_job[int(m.group(1))] = recs
    return {j: summarize(recs)
            for j, recs in sorted(by_job.items())}


def _pct(sorted_vals, q):
    """Nearest-rank percentile of an already-sorted list."""
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def summarize(records) -> dict:
    """Aggregate a ledger's records into one summary dict. Reads both
    schema v1 (no probes/alarms) and v2 ledgers."""
    rounds = [r for r in records if r["kind"] == "round"]
    span_vals, counters = {}, {}
    probe_vals = {}          # probe key -> [(round, value), ...]
    alarm_rounds = []        # [{"round": r, "alarms": [...]}, ...]
    device_vals = {}         # v3 device-time bucket -> [seconds, ...]
    lane_vals = {}           # v4: device id -> bucket -> [seconds]
    skew_vals = {}           # v4: skew stat -> [seconds, ...]
    stragglers = {}          # v4: device id -> straggler-round count
    shard_vals = {}          # merged ledgers: "p<k>" -> aggregates
    variant_first = {}       # autopilot variant key -> first round a
                             # compile was stamped under it
    frontier_pts = []        # (uplink_bytes, recovery_error, round)
    privacy_eps = []         # v5: (round, cumulative dp_epsilon)
    dp_sigma_err = {}        # v5: dp_sigma -> [recovery_error, ...]
    dp_delta = None          # v5: the accountant's delta (constant)
    uplink = downlink = 0.0
    rss_peak = hbm_peak = None
    for r in rounds:
        for name, secs in r["spans"].items():
            span_vals.setdefault(name, []).append(float(secs))
        # v3-only: trace-derived device-time buckets
        dt = r.get("device_time") or {}
        for name, val in dt.items():
            # the time buckets only: every one is named ``*_s``
            if name.endswith("_s") and isinstance(val, (int, float)):
                device_vals.setdefault(name, []).append(float(val))
        # v4-only: per-device lanes + collective-skew stats
        pd = dt.get("per_device")
        if isinstance(pd, dict):
            for dev, buckets in pd.items():
                slot = lane_vals.setdefault(dev, {})
                for bname, bval in (buckets or {}).items():
                    if isinstance(bval, (int, float)):
                        slot.setdefault(bname, []).append(float(bval))
        skew = dt.get("skew")
        if isinstance(skew, dict):
            for sname in ("max_enter_delta_s", "p95_enter_delta_s"):
                sval = skew.get(sname)
                if isinstance(sval, (int, float)):
                    skew_vals.setdefault(sname, []).append(float(sval))
            dev = skew.get("straggler_device")
            if dev:
                stragglers[dev] = stragglers.get(dev, 0) + 1
        # merged multi-host ledgers: per-process shard data joined
        # onto the canonical round record (scripts/ledger_merge.py)
        shards = r.get("shards")
        if isinstance(shards, dict):
            for pk, sh in sorted(shards.items()):
                if not isinstance(sh, dict):
                    continue
                entry = shard_vals.setdefault(
                    pk, {"rounds": 0, "span_total_s": 0.0,
                         "host_gap_s": [], "rss_peak": None})
                entry["rounds"] += 1
                entry["span_total_s"] += sum(
                    float(v) for v in (sh.get("spans") or {}).values()
                    if isinstance(v, (int, float)))
                hg = sh.get("host_gap_s")
                if isinstance(hg, (int, float)):
                    entry["host_gap_s"].append(float(hg))
                rss = sh.get("host_rss_peak_bytes")
                if isinstance(rss, (int, float)) and \
                        (entry["rss_peak"] is None
                         or rss > entry["rss_peak"]):
                    entry["rss_peak"] = rss
        for name, n in r["counters"].items():
            counters[name] = counters.get(name, 0) + n
            # autopilot re-jit cache: each compile is stamped with
            # its variant key — the round it first appears is the
            # round that variant entered the program (the ledger-side
            # view of the controller's knob trajectory)
            if name.startswith("vcompile_programs:"):
                key = name.split(":", 1)[1]
                variant_first.setdefault(key, r["round"])
        uplink += r.get("uplink_bytes") or 0.0
        downlink += r.get("downlink_bytes") or 0.0
        rerr = (r.get("probes") or {}).get("recovery_error")
        rup = r.get("uplink_bytes")
        if isinstance(rerr, (int, float)) and \
                isinstance(rup, (int, float)):
            frontier_pts.append((float(rup), float(rerr),
                                 r["round"]))
        # v5: the privacy accountant's per-round ε stamp, plus the
        # noise-vs-recovery-error pairing (what each σ level cost in
        # sketch recovery — the DP analogue of the bytes frontier)
        eps = r.get("dp_epsilon")
        if isinstance(eps, (int, float)):
            privacy_eps.append((r["round"], float(eps)))
            if isinstance(r.get("dp_delta"), (int, float)):
                dp_delta = float(r["dp_delta"])
        sig = r.get("dp_sigma")
        if isinstance(sig, (int, float)) and \
                isinstance(rerr, (int, float)):
            dp_sigma_err.setdefault(float(sig), []).append(float(rerr))
        # v2-only keys: absent on v1 records, hence .get
        for key, val in (r.get("probes") or {}).items():
            if isinstance(val, (int, float)):
                probe_vals.setdefault(key, []).append(
                    (r["round"], float(val)))
        if r.get("alarms"):
            alarm_rounds.append({"round": r["round"],
                                 "alarms": r["alarms"]})
        for key, best in (("host_rss_peak_bytes", rss_peak),
                          ("hbm_peak_bytes", hbm_peak)):
            v = r.get(key)
            if v is not None and (best is None or v > best):
                if key == "host_rss_peak_bytes":
                    rss_peak = v
                else:
                    hbm_peak = v
    n = max(len(rounds), 1)
    spans = {}
    for name, vals in sorted(span_vals.items()):
        sv = sorted(vals)
        spans[name] = {"total_s": round(sum(vals), 4),
                       "mean_ms": round(1e3 * sum(vals) / n, 3),
                       "p50_ms": round(1e3 * _pct(sv, 50), 3),
                       "p95_ms": round(1e3 * _pct(sv, 95), 3),
                       "max_ms": round(1e3 * sv[-1], 3)}
    probes = {}
    for key, pairs in sorted(probe_vals.items()):
        vals = [v for _, v in pairs]
        probes[key] = {"n": len(vals),
                       "first": vals[0], "last": vals[-1],
                       "mean": sum(vals) / len(vals),
                       "max": max(vals)}
    device_time = {}
    for name, vals in sorted(device_vals.items()):
        sv = sorted(vals)
        device_time[name] = {
            "n": len(sv),
            "total_s": round(sum(sv), 4),
            "mean_ms": round(1e3 * sum(sv) / len(sv), 3),
            "p50_ms": round(1e3 * _pct(sv, 50), 3),
            "p95_ms": round(1e3 * _pct(sv, 95), 3)}
    # overlap fraction (--overlap_depth pipelining): how much of the
    # round's collective wall time ran hidden under some lane's
    # compute — 0.0 for serial rounds, the pipeline's win otherwise
    overlap_fraction = None
    if "overlapped_s" in device_vals and "collective_s" in device_vals:
        coll_total = sum(device_vals["collective_s"])
        if coll_total > 0:
            overlap_fraction = round(
                sum(device_vals["overlapped_s"]) / coll_total, 4)
    per_device = {}
    for dev, buckets in sorted(lane_vals.items()):
        per_device[dev] = {
            bname: round(1e3 * sum(vals) / len(vals), 3)
            for bname, vals in sorted(buckets.items())}
    collective_skew = None
    if skew_vals:
        collective_skew = {"stragglers": dict(sorted(
            stragglers.items()))}
        for sname, vals in sorted(skew_vals.items()):
            collective_skew[sname] = {
                "mean_ms": round(1e3 * sum(vals) / len(vals), 6),
                "max_ms": round(1e3 * max(vals), 6),
                "n": len(vals)}
    shards = {}
    for pk, entry in sorted(shard_vals.items()):
        hg = entry["host_gap_s"]
        shards[pk] = {
            "rounds": entry["rounds"],
            "span_total_s": round(entry["span_total_s"], 4),
            "host_gap_mean_ms": (round(1e3 * sum(hg) / len(hg), 3)
                                 if hg else None),
            "host_rss_peak_bytes": entry["rss_peak"]}
    # per-variant compile cost (autopilot re-jit cache): the
    # vcompile_* counter triplet keyed by variant cache key —
    # raw XLA compile events, wall seconds, and whole executables
    variant_compiles = {}
    for name, n in counters.items():
        if not name.startswith("vcompile_"):
            continue
        kind, key = name.split(":", 1)
        slot = variant_compiles.setdefault(
            key, {"events": 0, "secs": 0.0, "programs": 0,
                  "first_round": variant_first.get(key)})
        if kind == "vcompile_events":
            slot["events"] = int(n)
        elif kind == "vcompile_secs":
            slot["secs"] = round(float(n), 3)
        elif kind == "vcompile_programs":
            slot["programs"] = int(n)
    # bytes-vs-recovery-error frontier: one point per uplink level
    # the controller settled on — what each byte budget bought in
    # recovery error (cheapest in-band point is the autopilot target)
    frontier = []
    by_bytes = {}
    for up, err, ridx in frontier_pts:
        by_bytes.setdefault(up, []).append((err, ridx))
    for up in sorted(by_bytes, reverse=True):
        errs = [e for e, _ in by_bytes[up]]
        frontier.append({
            "uplink_bytes": up, "rounds": len(errs),
            "first_round": min(r for _, r in by_bytes[up]),
            "err_mean": sum(errs) / len(errs),
            "err_max": max(errs)})
    # privacy trajectory (v5 DP runs): the accountant's cumulative
    # ε stamps plus one noise-vs-recovery-error point per σ level
    privacy = None
    if privacy_eps:
        privacy_eps.sort(key=lambda p: p[0])
        privacy = {
            "rounds": len(privacy_eps),
            "eps_first": privacy_eps[0][1],
            "eps_last": privacy_eps[-1][1],
            "delta": dp_delta,
            "noise_vs_recovery": [
                {"dp_sigma": s, "rounds": len(v),
                 "recovery_err_mean": sum(v) / len(v),
                 "recovery_err_max": max(v)}
                for s, v in sorted(dp_sigma_err.items())],
        }
    # v6: run alarm totals — the close()-time summary record's
    # alarm_fired backfill is authoritative (it counts fires even on
    # rounds this reader never saw, e.g. a truncated ledger); fall
    # back to counting the flagged rounds for older ledgers
    alarm_totals = {}
    for rec in records:
        if rec.get("kind") == "summary" and \
                isinstance(rec.get("alarm_fired"), dict):
            for rule, cnt in rec["alarm_fired"].items():
                alarm_totals[str(rule)] = \
                    alarm_totals.get(str(rule), 0) + int(cnt)
    if not alarm_totals:
        for a in alarm_rounds:
            for al in a["alarms"]:
                rule = str(al.get("rule", "?"))
                alarm_totals[rule] = alarm_totals.get(rule, 0) + 1
    # v6: the last round's SLO stamp is the run's closing burn state
    slo_stamp = next((r["slo"] for r in reversed(rounds)
                      if isinstance(r.get("slo"), dict)), None)
    return {
        "meta": next((r for r in records if r["kind"] == "meta"),
                     None),
        "rounds": len(rounds),
        "uplink_bytes": uplink,
        "downlink_bytes": downlink,
        "spans": spans,
        "device_time": device_time,
        "overlap_fraction": overlap_fraction,
        "per_device": per_device,
        "collective_skew": collective_skew,
        "shards": shards,
        "probes": probes,
        "alarm_rounds": alarm_rounds,
        "alarm_totals": dict(sorted(alarm_totals.items())),
        "slo": slo_stamp,
        "variant_compiles": dict(sorted(variant_compiles.items())),
        "frontier": frontier,
        "privacy": privacy,
        "counters": dict(sorted(counters.items())),
        "host_rss_peak_bytes": rss_peak,
        "hbm_peak_bytes": hbm_peak,
        "epochs": [r["row"] for r in records if r["kind"] == "epoch"],
        "benches": [{k: v for k, v in r.items()
                     if k not in ("schema", "kind", "ts")}
                    for r in records if r["kind"] == "bench"],
        "summary_records": [r for r in records
                            if r["kind"] == "summary"],
    }


def _mib(b):
    return f"{b / 2**20:.3f} MiB"


def render_summary(s, label="") -> str:
    lines = []
    head = f"== ledger summary{' ' + label if label else ''} =="
    lines.append(head)
    meta = s["meta"]
    if meta:
        plan = meta.get("plan") or {}
        bits = [f"mode={plan.get('mode')}",
                f"grad_size={plan.get('grad_size')}",
                f"workers={plan.get('num_workers')}"]
        if "num_clients" in meta:
            bits.append(f"clients={meta['num_clients']}")
        if plan.get("fused_grad"):
            bits.append("fused_grad")
        lines.append("  run: " + ", ".join(bits))
    lines.append(f"  rounds: {s['rounds']}")
    lines.append(f"  comm: up {_mib(s['uplink_bytes'])}, "
                 f"down {_mib(s['downlink_bytes'])}")
    for name, v in s["spans"].items():
        lines.append(f"  span {name}: total {v['total_s']} s, "
                     f"mean {v['mean_ms']} ms/round"
                     f" (p50 {v['p50_ms']}, p95 {v['p95_ms']}, "
                     f"max {v['max_ms']})")
    # device-time breakdown (schema v3, --profile runs) next to the
    # host-span percentiles above
    for name, v in s.get("device_time", {}).items():
        lines.append(f"  device {name}: mean {v['mean_ms']} "
                     f"ms/round (p50 {v['p50_ms']}, "
                     f"p95 {v['p95_ms']}, {v['n']} rounds)")
    if s.get("overlap_fraction") is not None:
        lines.append(
            f"  overlap: {100 * s['overlap_fraction']:.1f}% of "
            "collective time hidden under compute "
            "(serial share = collective - overlapped)")
    for dev, buckets in s.get("per_device", {}).items():
        bits = ", ".join(f"{b.replace('_s', '')} {v} ms"
                         for b, v in buckets.items())
        lines.append(f"  lane {dev}: {bits} (means/round)")
    csk = s.get("collective_skew")
    if csk:
        mx = csk.get("max_enter_delta_s") or {}
        p95 = csk.get("p95_enter_delta_s") or {}
        lines.append(
            f"  collective skew: enter-delta mean "
            f"{mx.get('mean_ms')} ms, max {mx.get('max_ms')} ms "
            f"(p95-stat mean {p95.get('mean_ms')} ms, "
            f"{mx.get('n')} rounds)")
        if csk.get("stragglers"):
            lines.append(
                f"  stragglers (rounds led): {csk['stragglers']}")
    for pk, sh in s.get("shards", {}).items():
        gap = (f", host-gap mean {sh['host_gap_mean_ms']} ms"
               if sh.get("host_gap_mean_ms") is not None else "")
        rss = (f", RSS peak {_mib(sh['host_rss_peak_bytes'])}"
               if sh.get("host_rss_peak_bytes") is not None else "")
        lines.append(f"  shard {pk}: {sh['rounds']} rounds, spans "
                     f"total {sh['span_total_s']} s{gap}{rss}")
    # fedservice daemon runs: one solo-equivalent block per tenant
    for jk, js in (s.get("jobs") or {}).items():
        alarms = sum(len(a.get("alarms") or ())
                     for a in js.get("alarm_rounds") or ())
        lines.append(
            f"  job {jk}: {js['rounds']} rounds, uplink "
            f"{_mib(js['uplink_bytes'])}, downlink "
            f"{_mib(js['downlink_bytes'])}, {alarms} alarm(s)")
    for name, p in s.get("probes", {}).items():
        lines.append(f"  probe {name}: first {p['first']:.6g} -> "
                     f"last {p['last']:.6g}, mean {p['mean']:.6g}, "
                     f"max {p['max']:.6g} ({p['n']} rounds)")
    for a in s.get("alarm_rounds", []):
        names = ", ".join(al.get("rule", "?") for al in a["alarms"])
        lines.append(f"  ALARM round {a['round']}: {names}")
    if s.get("alarm_totals"):
        lines.append("  alarm totals: " + ", ".join(
            f"{rule} x{n}"
            for rule, n in s["alarm_totals"].items()))
    slo = s.get("slo")
    if slo:
        for obj, st in sorted(slo.items()):
            if not isinstance(st, dict):
                continue
            lines.append(
                f"  slo {obj}: burn {st.get('burn', 0):.3g} "
                f"(target {st.get('target')}, fast rate "
                f"{st.get('fast_rate', 0):.3g}, slow rate "
                f"{st.get('slow_rate', 0):.3g}, "
                f"{st.get('seen', 0)} observed)")
    vc = s.get("variant_compiles") or {}
    if vc:
        # knob trajectory, ledger view: variants in first-dispatch
        # order (the manifest's autopilot record holds the full
        # per-round decision log for bit-exact replay)
        order = sorted(vc, key=lambda k: (
            vc[k].get("first_round")
            if vc[k].get("first_round") is not None else 1 << 30))
        lines.append("  knob trajectory: " + " -> ".join(
            f"{k}@r{vc[k]['first_round']}"
            if vc[k].get("first_round") is not None else k
            for k in order))
        for k in order:
            v = vc[k]
            lines.append(
                f"  variant {k}: {v['programs']} program(s) "
                f"compiled in {v['secs']} s "
                f"({v['events']} XLA events)")
    for p in s.get("frontier") or []:
        lines.append(
            f"  frontier {_mib(p['uplink_bytes'])}/round: "
            f"recovery err mean {p['err_mean']:.4g}, "
            f"max {p['err_max']:.4g} "
            f"({p['rounds']} round(s), from r{p['first_round']})")
    pv = s.get("privacy")
    if pv:
        delta = (f" at delta {pv['delta']:.3g}"
                 if pv.get("delta") is not None else "")
        lines.append(
            f"  privacy: eps {pv['eps_first']:.6g} -> "
            f"{pv['eps_last']:.6g}{delta} "
            f"({pv['rounds']} charged round(s))")
        for pt in pv.get("noise_vs_recovery") or []:
            lines.append(
                f"  privacy sigma {pt['dp_sigma']:.6g}: "
                f"recovery err mean {pt['recovery_err_mean']:.4g}, "
                f"max {pt['recovery_err_max']:.4g} "
                f"({pt['rounds']} round(s))")
    if s["counters"]:
        lines.append(f"  counters: {s['counters']}")
    if s["host_rss_peak_bytes"] is not None:
        lines.append(
            f"  host RSS peak: {_mib(s['host_rss_peak_bytes'])}")
    if s["hbm_peak_bytes"] is not None:
        lines.append(f"  HBM peak: {_mib(s['hbm_peak_bytes'])}")
    for row in s["epochs"]:
        lines.append("  epoch " + json.dumps(row))
    for b in s["benches"]:
        lines.append("  bench " + json.dumps(b))
    return "\n".join(lines)


def diff_summaries(a: dict, b: dict) -> dict:
    """B relative to A: per-span mean deltas, byte deltas, matching
    bench metrics as ratios (>1 = B slower/bigger)."""
    out = {"rounds": {"a": a["rounds"], "b": b["rounds"]}}
    span_diff = {}
    for name in sorted(set(a["spans"]) | set(b["spans"])):
        ma = a["spans"].get(name, {}).get("mean_ms")
        mb = b["spans"].get(name, {}).get("mean_ms")
        entry = {"a_mean_ms": ma, "b_mean_ms": mb}
        if ma and mb:
            entry["ratio"] = round(mb / ma, 3)
        span_diff[name] = entry
    out["spans"] = span_diff
    dev_diff = {}
    for name in sorted(set(a.get("device_time", {}))
                       & set(b.get("device_time", {}))):
        da, db = a["device_time"][name], b["device_time"][name]
        entry = {"a": da["mean_ms"], "b": db["mean_ms"]}
        if da["mean_ms"]:
            entry["ratio"] = round(db["mean_ms"] / da["mean_ms"], 4)
        dev_diff[name] = entry
    if dev_diff:
        out["device_time"] = dev_diff
    fa, fb = a.get("overlap_fraction"), b.get("overlap_fraction")
    if fa is not None or fb is not None:
        out["overlap_fraction"] = {"a": fa, "b": fb}
    for key in ("uplink_bytes", "downlink_bytes"):
        entry = {"a": a[key], "b": b[key],
                 "delta": b[key] - a[key]}
        if a[key]:
            entry["ratio"] = round(b[key] / a[key], 6)
        out[key] = entry
    bench_a = {r.get("metric"): r for r in a["benches"]}
    bench_diff = {}
    for r in b["benches"]:
        ra = bench_a.get(r.get("metric"))
        if ra is None:
            continue
        va, vb = ra.get("value"), r.get("value")
        entry = {"a": va, "b": vb}
        if isinstance(va, (int, float)) and \
                isinstance(vb, (int, float)) and va:
            entry["ratio"] = round(vb / va, 4)
        bench_diff[r["metric"]] = entry
    if bench_diff:
        out["benches"] = bench_diff
    probe_diff = {}
    for name in sorted(set(a.get("probes", {}))
                       & set(b.get("probes", {}))):
        pa, pb = a["probes"][name], b["probes"][name]
        entry = {"a_mean": pa["mean"], "b_mean": pb["mean"]}
        if pa["mean"]:
            entry["ratio"] = round(pb["mean"] / pa["mean"], 4)
        probe_diff[name] = entry
    if probe_diff:
        out["probes"] = probe_diff
    vc_diff = {}
    va = a.get("variant_compiles") or {}
    vb = b.get("variant_compiles") or {}
    for key in sorted(set(va) | set(vb)):
        ea, eb = va.get(key), vb.get(key)
        vc_diff[key] = {
            "a_secs": ea["secs"] if ea else None,
            "b_secs": eb["secs"] if eb else None,
            "a_programs": ea["programs"] if ea else None,
            "b_programs": eb["programs"] if eb else None}
    if vc_diff:
        out["variant_compiles"] = vc_diff
    pa, pb = a.get("privacy"), b.get("privacy")
    if pa or pb:
        entry = {"a_eps_last": pa["eps_last"] if pa else None,
                 "b_eps_last": pb["eps_last"] if pb else None}
        if pa and pb and pa["eps_last"]:
            entry["ratio"] = round(pb["eps_last"] / pa["eps_last"], 4)
        out["privacy"] = entry
    aa = [x["round"] for x in a.get("alarm_rounds", [])]
    ab = [x["round"] for x in b.get("alarm_rounds", [])]
    if aa or ab:
        out["alarm_rounds"] = {"a": aa, "b": ab}
    return out


def render_diff(d, label_a, label_b) -> str:
    lines = [f"== ledger diff: {label_a} -> {label_b} ==",
             f"  rounds: {d['rounds']['a']} -> {d['rounds']['b']}"]
    for name, e in d["spans"].items():
        r = f" ({e['ratio']}x)" if "ratio" in e else ""
        lines.append(f"  span {name}: {e['a_mean_ms']} -> "
                     f"{e['b_mean_ms']} ms/round{r}")
    for name, e in d.get("device_time", {}).items():
        r = f" ({e['ratio']}x)" if "ratio" in e else ""
        lines.append(f"  device {name}: {e['a']} -> {e['b']} "
                     f"ms/round{r}")
    if "overlap_fraction" in d:
        e = d["overlap_fraction"]
        fmt = lambda v: f"{100 * v:.1f}%" if v is not None else "-"
        lines.append(f"  overlap fraction: {fmt(e['a'])} -> "
                     f"{fmt(e['b'])} of collective hidden")
    for key in ("uplink_bytes", "downlink_bytes"):
        e = d[key]
        r = f" ({e['ratio']}x)" if "ratio" in e else ""
        lines.append(f"  {key.split('_')[0]}: {_mib(e['a'])} -> "
                     f"{_mib(e['b'])}{r}")
    for name, e in d.get("benches", {}).items():
        r = f" ({e['ratio']}x)" if "ratio" in e else ""
        lines.append(f"  bench {name}: {e['a']} -> {e['b']}{r}")
    for name, e in d.get("probes", {}).items():
        r = f" ({e['ratio']}x)" if "ratio" in e else ""
        lines.append(f"  probe {name}: mean {e['a_mean']:.6g} -> "
                     f"{e['b_mean']:.6g}{r}")
    for key, e in d.get("variant_compiles", {}).items():
        fmt = lambda s, p: (f"{s} s / {p} prog"
                            if s is not None else "-")
        lines.append(
            f"  variant {key} compile: "
            f"{fmt(e['a_secs'], e['a_programs'])} -> "
            f"{fmt(e['b_secs'], e['b_programs'])}")
    if "privacy" in d:
        e = d["privacy"]
        fmt = lambda v: f"{v:.6g}" if v is not None else "-"
        r = f" ({e['ratio']}x)" if "ratio" in e else ""
        lines.append(f"  privacy eps spent: {fmt(e['a_eps_last'])} "
                     f"-> {fmt(e['b_eps_last'])}{r}")
    if "alarm_rounds" in d:
        e = d["alarm_rounds"]
        lines.append(f"  ALARM rounds: {e['a']} -> {e['b']}")
    return "\n".join(lines)


def scaling_curves(manifests) -> list:
    """Scaling-curve points from the registry: manifests carrying a
    ``scaling`` dict (a sweep's per-topology result) grouped by config
    hash, newest manifest per topology point, sorted by device count.
    Only groups with >= 2 distinct points form a curve."""
    from commefficient_tpu.telemetry import registry

    groups = {}
    for path, rec in manifests:             # oldest first
        if not isinstance(rec.get("scaling"), dict):
            continue
        by_topo = groups.setdefault(rec.get("config_hash", ""), {})
        by_topo[registry.run_topology(rec)] = (path, rec)
    curves = []
    for chash, by_topo in sorted(groups.items()):
        if len(by_topo) < 2:
            continue
        points = []
        for (dc, pc), (path, rec) in sorted(
                by_topo.items(),
                key=lambda kv: (kv[0][0] or 0, kv[0][1] or 0)):
            sc = rec["scaling"]
            points.append({
                "device_count": dc, "process_count": pc,
                "clients_per_s": sc.get("clients_per_s"),
                "parallel_efficiency": sc.get("parallel_efficiency"),
                "collective_fraction": sc.get("collective_fraction"),
                "overlapped_fraction": sc.get("overlapped_fraction"),
                "max_skew_s": sc.get("max_skew_s"),
                "manifest": path})
        curves.append({"config_hash": chash, "points": points})
    return curves


def render_scaling_curves(curves) -> str:
    lines = []
    for curve in curves:
        lines.append(f"== scaling curve (config "
                     f"{curve['config_hash'][:8] or '????????'}, "
                     f"{len(curve['points'])} points) ==")
        for p in curve["points"]:
            dc = p["device_count"]
            pc = p["process_count"]
            bits = [f"{p['clients_per_s']:.6g} clients/s"
                    if isinstance(p["clients_per_s"], (int, float))
                    else "clients/s ?"]
            if isinstance(p["parallel_efficiency"], (int, float)):
                bits.append(f"eff {p['parallel_efficiency']:.3f}")
            if isinstance(p["collective_fraction"], (int, float)):
                bits.append(
                    f"collective {100 * p['collective_fraction']:.1f}%")
            if p.get("overlapped_fraction"):
                bits.append(
                    f"overlapped "
                    f"{100 * p['overlapped_fraction']:.1f}%")
            if isinstance(p["max_skew_s"], (int, float)):
                bits.append(f"skew max {p['max_skew_s']:.6g} s")
            lines.append(f"  d{dc}p{pc}: " + ", ".join(bits))
    return "\n".join(lines)


def lineages(manifests) -> list:
    """Resume lineages from the registry: manifests carrying a
    ``resumed_from`` stamp (trainers write it from checkpoint
    metadata) grouped with the earlier same-config manifests they
    continue, oldest first. One lineage = one logical training run,
    possibly spanning several manifests and several topologies (the
    ``topology_segments`` chain records each leg)."""
    from commefficient_tpu.telemetry import registry

    by_hash = {}
    for path, rec in manifests:             # oldest first
        by_hash.setdefault(rec.get("config_hash", ""), []) \
            .append((path, rec))
    out = []
    for chash, group in sorted(by_hash.items()):
        if not any(isinstance(rec.get("resumed_from"), dict)
                   for _, rec in group):
            continue
        entries = []
        for path, rec in group:
            dc, pc = registry.run_topology(rec)
            entries.append({
                "manifest": path,
                "resumed_from": rec.get("resumed_from")
                if isinstance(rec.get("resumed_from"), dict) else None,
                "device_count": dc, "process_count": pc,
                "mesh_shape": registry.run_mesh_shape(rec),
                "segments": registry.run_segments(rec),
            })
        changed = any(registry.run_topology_changed(rec)
                      for _, rec in group)
        out.append({"config_hash": chash, "entries": entries,
                    "topology_changed": changed})
    return out


def _segment_label(seg: dict) -> str:
    dc = seg.get("device_count")
    pc = seg.get("process_count")
    label = f"d{dc}p{pc}" if dc is not None else "d?p?"
    ms = seg.get("mesh_shape")
    if isinstance(ms, dict) and ms:
        label += " " + "x".join(str(v) for v in ms.values())
    r = seg.get("round_index")
    if r is not None:
        label += f"@r{r}"
    return label


def render_lineages(lins) -> str:
    lines = []
    for lin in lins:
        lines.append(f"== resume lineage (config "
                     f"{lin['config_hash'][:8] or '????????'}, "
                     f"{len(lin['entries'])} runs) ==")
        for e in lin["entries"]:
            name = os.path.basename(e["manifest"])
            rf = e["resumed_from"]
            tail = ""
            if rf:
                src = os.path.basename(str(rf.get("checkpoint", "")))
                tail = (f" <- resumed from {src} "
                        f"(round {rf.get('round_index', '?')})")
            segs = e["segments"]
            chain = " -> ".join(_segment_label(s) for s in segs) \
                if segs else _segment_label(e)
            lines.append(f"  {name}: {chain}{tail}")
        if lin["topology_changed"]:
            lines.append("  NOTE: topology changed mid-lineage — "
                         "read each segment's own ledger, not the "
                         "merged one")
    return "\n".join(lines)


def runs_dir_report(runs_dir: str, as_json: bool) -> int:
    """Registry mode: list the recent manifest-registered runs, render
    the latest run's ledger, diff it against the previous COMPARABLE
    one (same config hash + topology; registry.run_key), and render
    any scaling curves the registry holds."""
    from commefficient_tpu.telemetry import registry

    manifests = registry.list_manifests(runs_dir)
    if not manifests:
        print(f"no run manifests under {runs_dir} "
              f"(runs write them when --ledger is set)")
        return 1
    if not as_json:
        print(f"== runs under {runs_dir} ({len(manifests)}) ==")
        for path, rec in manifests[-10:]:
            bench = rec.get("bench") or {}
            headline = next(
                (f"{m}: {v.get('value')} {v.get('unit', '')}"
                 for m, v in bench.items()
                 if isinstance(v, dict)), "")
            dc, pc = registry.run_topology(rec)
            topo = (f"d{dc}p{pc}" if dc is not None and pc is not None
                    else "d?p?")
            print(f"  {os.path.basename(path)}: "
                  f"git {rec.get('git_sha', '')[:8]}, "
                  f"config {rec.get('config_hash', '')[:8]}, "
                  f"backend {rec.get('backend', '?')}, {topo}"
                  + (f", {headline}" if headline else ""))
    curves = scaling_curves(manifests)
    lins = lineages(manifests)
    if lins and not as_json:
        print(render_lineages(lins))
    hits = registry.latest_ledgers(runs_dir, n=1)
    if not hits:
        print("no manifest points at an existing ledger file")
        return 1
    _, latest_manifest, latest = hits[0]
    records, problems = load_ledger(latest)
    for p in problems:
        print(f"WARNING {latest}: {p}", file=sys.stderr)
    summ = summarize(records)
    # previous COMPARABLE run only: same config hash AND topology —
    # pairing the newest two manifests regardless of device count
    # made an 8-device run "regress" against a single-chip baseline
    key = registry.run_key(latest_manifest)
    prev_hits = registry.latest_ledgers(runs_dir, n=2, key=key)
    prev = prev_hits[1][2] if len(prev_hits) > 1 else None
    if prev is None:
        if as_json:
            print(json.dumps({"latest": summ,
                              "scaling_curves": curves,
                              "lineages": lins}))
        else:
            print(render_summary(summ, label=latest))
            if not len(prev_hits) > 1:
                print("(no previous run with this config+topology "
                      "to diff against)")
            if curves:
                print(render_scaling_curves(curves))
        return 0
    records_p, problems_p = load_ledger(prev)
    for p in problems_p:
        print(f"WARNING {prev}: {p}", file=sys.stderr)
    d = diff_summaries(summarize(records_p), summ)
    if as_json:
        print(json.dumps({"latest": summ, "diff_vs_previous": d,
                          "scaling_curves": curves,
                          "lineages": lins}))
    else:
        print(render_summary(summ, label=latest))
        print(render_diff(d, prev, latest))
        if curves:
            print(render_scaling_curves(curves))
    return 0


def postmortem_report(path: str, as_json: bool) -> int:
    """Render a flight-recorder bundle: the incident header (reason,
    rule, labels, lineage), the recent compile/alarm event queue, and
    the ring's rounds summarized exactly like a ledger."""
    from commefficient_tpu.telemetry.flightrec import load_postmortem
    bundle, problems = load_postmortem(path)
    for p in problems:
        print(f"WARNING {path}: {p}", file=sys.stderr)
    rounds = [r for r in (bundle.get("rounds") or [])
              if isinstance(r, dict)]
    meta = bundle.get("meta")
    summ = summarize(([meta] if meta else []) + rounds)
    if as_json:
        print(json.dumps({"bundle": {
            k: bundle.get(k)
            for k in ("reason", "rule", "ts", "labels", "context",
                      "config_hash", "ring_rounds", "events",
                      "manifest", "environment")},
            "summary": summ, "problems": problems}))
        return 0
    lines = [f"== postmortem {path} =="]
    rule = f" rule={bundle.get('rule')}" if bundle.get("rule") else ""
    lines.append(f"  incident: {bundle.get('reason')}{rule} "
                 f"at ts {bundle.get('ts')}")
    if bundle.get("labels"):
        lines.append("  labels: " + ", ".join(
            f"{k}={v}" for k, v in sorted(bundle["labels"].items())))
    lines.append(f"  config: {bundle.get('config_hash', '')[:12]}"
                 + (f", manifest {bundle['manifest']}"
                    if bundle.get("manifest") else ""))
    ctx = bundle.get("context")
    if ctx:
        lines.append("  context: " + json.dumps(ctx, sort_keys=True))
    lines.append(f"  ring: {len(rounds)} of last "
                 f"{bundle.get('ring_rounds')} round(s) retained")
    for ev in bundle.get("events") or []:
        kind = ev.get("kind")
        if kind == "alarm":
            lines.append(
                f"  event alarm {ev.get('rule')} round "
                f"{ev.get('round')}: value {ev.get('value')} over "
                f"threshold {ev.get('threshold')}")
        elif kind == "compile":
            lines.append(
                f"  event compile round {ev.get('round')}: "
                f"{ev.get('events')} event(s), {ev.get('secs')} s")
    print("\n".join(lines))
    print(render_summary(summ, label="(flight-recorder ring)"))
    return 0


def _finding_rule(finding: str) -> str:
    """Rule name out of a rendered finding: ``path:NN: rule: msg``."""
    parts = finding.split(": ", 2)
    return parts[1] if len(parts) >= 3 else "?"


def audit_report(baseline_path: str, as_json: bool,
                 program=None, violations=None) -> int:
    """Findings diff: the committed audit baseline vs a fresh run of
    both lint tiers (legacy rules + flowlint checkers). Per rule:
    how many waived findings stand, which are NEW since the baseline
    (including any unwaived hit — those never enter a baseline), and
    which the baseline still carries but the tree has FIXED."""
    from commefficient_tpu.analysis.baseline import load_baseline
    from commefficient_tpu.analysis.lint import (run_all,
                                                 stale_waivers)
    baseline = load_baseline(baseline_path)
    pinned = set(baseline.get("lint", {}).get("waived", []))
    if violations is None:
        violations = run_all(program=program)
    stale = stale_waivers(violations=violations)
    fresh_waived = {str(v) for v in violations if v.waived}
    fresh_unwaived = sorted(str(v) for v in violations
                            if not v.waived)
    new = sorted(fresh_waived - pinned) + fresh_unwaived
    fixed = sorted(pinned - fresh_waived)

    per_rule: dict = {}
    for bucket, findings in (("waived", sorted(fresh_waived)),
                             ("new", new), ("fixed", fixed)):
        for f in findings:
            entry = per_rule.setdefault(
                _finding_rule(f), {"waived": 0, "new": 0, "fixed": 0})
            entry[bucket] += 1
    if as_json:
        print(json.dumps({
            "baseline": baseline_path, "per_rule": per_rule,
            "new": new, "fixed": fixed,
            "waived": sorted(fresh_waived),
            "unwaived": fresh_unwaived, "stale_waivers": stale}))
        return 1 if (new or fixed or stale) else 0
    lines = [f"== audit findings vs {baseline_path} =="]
    for rule in sorted(per_rule):
        c = per_rule[rule]
        lines.append(f"  {rule:24} waived {c['waived']:3}  "
                     f"new {c['new']:3}  fixed {c['fixed']:3}")
    for f in new:
        lines.append(f"  NEW   {f}")
    for f in fixed:
        lines.append(f"  FIXED {f} — refresh the baseline")
    for s in stale:
        lines.append(f"  STALE {s}")
    if not (new or fixed or stale):
        lines.append("  in sync: tree findings match the baseline")
    print("\n".join(lines))
    return 1 if (new or fixed or stale) else 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="render or diff telemetry run ledgers")
    ap.add_argument("ledger", nargs="?", default=None,
                    help="run ledger (JSONL)")
    ap.add_argument("other", nargs="?", default=None,
                    help="second ledger: diff mode (other vs first)")
    ap.add_argument("--runs_dir", default=None,
                    help="registry mode: list recent runs (via their "
                         "manifests), summarize the latest ledger and "
                         "diff it against the previous run")
    ap.add_argument("--postmortem", default=None,
                    help="render a flight-recorder postmortem bundle "
                         "(telemetry/flightrec.py JSON)")
    ap.add_argument("--audit", nargs="?", const="audit_baseline.json",
                    default=None, metavar="BASELINE",
                    help="findings diff: committed audit baseline vs "
                         "a fresh two-tier lint run (new/fixed/"
                         "waived counts per rule)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    args = ap.parse_args(argv)

    if args.audit is not None:
        return audit_report(args.audit, args.json)
    if args.postmortem is not None:
        return postmortem_report(args.postmortem, args.json)
    if args.runs_dir is not None:
        return runs_dir_report(args.runs_dir, args.json)
    if args.ledger is None:
        ap.error("a ledger path (or --runs_dir) is required")

    records, problems = load_ledger(args.ledger)
    for p in problems:
        print(f"WARNING {args.ledger}: {p}", file=sys.stderr)
    # fedservice runs: job records summarize per-tenant, not into the
    # service's own (fairness) stream
    jobs = job_summaries(records, args.ledger)
    records = [r for r in records
               if not isinstance(r.get("job"), int)]
    summ = summarize(records)

    if args.other is None:
        if jobs:
            summ["jobs"] = {str(j): s for j, s in jobs.items()}
        if args.json:
            print(json.dumps(summ))
        else:
            print(render_summary(summ, label=args.ledger))
        return 0

    records_b, problems_b = load_ledger(args.other)
    for p in problems_b:
        print(f"WARNING {args.other}: {p}", file=sys.stderr)
    d = diff_summaries(summ, summarize(records_b))
    if args.json:
        print(json.dumps(d))
    else:
        print(render_diff(d, args.ledger, args.other))
    return 0


if __name__ == "__main__":
    sys.exit(main())
