#!/usr/bin/env python
"""fedwatch: live-tail console for a running fedservice daemon.

Polls a live-plane exporter (``--live_port``'s ``/metrics``) and
renders one refreshing per-job table — rounds done, round-latency
p95, wire bytes, backlog, staleness, ε spend, SLO burn rate, alarm
fires — so an operator watches the pod instead of tailing J ledger
shards.
Falls back to tailing the ledger shards directly (``--ledger``) when
the daemon has no exporter armed.

    python scripts/fedwatch.py --url http://127.0.0.1:9100
    python scripts/fedwatch.py --ledger runs/svc.jsonl --once

Stdlib only, read-only, and deliberately decoupled from the package
internals: the metrics contract is the Prometheus text exposition the
exporter serves, parsed by the same minimal parser the tests use.
"""

import argparse
import json
import sys
import time
import urllib.error
import urllib.request


def parse_prometheus(text):
    """Minimal Prometheus text-exposition (0.0.4) parser:
    ``[(name, labels_dict, value)]``. Handles escaped label values;
    ignores comments/blank lines. Enough for the exporter's own
    output — not a general scraper."""
    out = []
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        if "{" in line:
            name, rest = line.split("{", 1)
            lab_str, _, val = rest.rpartition("}")
            labels = {}
            i, n = 0, len(lab_str)
            while i < n:
                eq = lab_str.index("=", i)
                key = lab_str[i:eq].strip().lstrip(",").strip()
                assert lab_str[eq + 1] == '"', lab_str
                j = eq + 2
                buf = []
                while lab_str[j] != '"':
                    if lab_str[j] == "\\":
                        nxt = lab_str[j + 1]
                        buf.append({"n": "\n"}.get(nxt, nxt))
                        j += 2
                    else:
                        buf.append(lab_str[j])
                        j += 1
                labels[key] = "".join(buf)
                i = j + 1
            out.append((name.strip(), labels, float(val)))
        else:
            name, _, val = line.rpartition(" ")
            out.append((name.strip(), {}, float(val)))
    return out


def scrape(url):
    with urllib.request.urlopen(url.rstrip("/") + "/metrics",
                                timeout=10) as resp:
        return parse_prometheus(resp.read().decode())


def _fmt(v, unit=""):
    if v is None:
        return "-"
    if isinstance(v, str):        # pre-rendered cells (crit column)
        return v
    if unit == "mib":
        return f"{v / 2**20:.2f}M"
    if abs(v) >= 1000 or v == int(v):
        return f"{v:g}"
    return f"{v:.3g}"


def job_table(samples):
    """Fold scraped samples into one row per ``job`` label."""
    jobs = {}

    def slot(labels):
        return jobs.setdefault(labels.get("job", "?"), {})

    for name, labels, val in samples:
        row = slot(labels)
        if name == "commeff_rounds_total":
            row["rounds"] = val
        elif name == "commeff_round_seconds" \
                and labels.get("quantile") == "0.95":
            row["p95_s"] = val
        elif name == "commeff_clients_per_s":
            row["clients_s"] = val
        elif name == "commeff_uplink_bytes_total":
            row["up"] = val
        elif name == "commeff_downlink_bytes_total":
            row["down"] = val
        elif name == "commeff_job_backlog_total":
            row["backlog"] = val
        elif name == "commeff_async_staleness_max":
            row["stale"] = val
        elif name == "commeff_dp_epsilon":
            row["eps"] = val
        elif name == "commeff_slo_burn":
            row["burn"] = max(row.get("burn", 0.0), val)
        elif name == "commeff_alarms_total":
            row["alarms"] = row.get("alarms", 0.0) + val
    return jobs


COLS = (("job", "job", ""), ("rounds", "rounds", ""),
        ("p95_s", "p95 s", ""), ("clients_s", "cl/s", ""),
        ("up", "up", "mib"), ("down", "down", "mib"),
        ("backlog", "backlog", ""), ("stale", "stale", ""),
        ("eps", "eps", ""), ("burn", "burn", ""),
        ("alarms", "alarms", ""))


def render_table(jobs) -> str:
    rows = [[title for _, title, _ in COLS]]
    for job in sorted(jobs, key=lambda j: (j != "service", j)):
        row = jobs[job]
        rows.append([job] + [_fmt(row.get(key), unit)
                             for key, _, unit in COLS[1:]])
    widths = [max(len(r[i]) for r in rows)
              for i in range(len(COLS))]
    lines = ["  ".join(c.rjust(w) for c, w in zip(r, widths))
             for r in rows]
    lines.insert(1, "-" * len(lines[0]))
    return "\n".join(lines)


def ledger_table(path):
    """Exporter-less fallback: derive the same table from the ledger
    shards on disk (base + .job<j> shards)."""
    import glob
    import os

    jobs = {}
    paths = [(p, p.split(".job")[-1].split(".")[0]
              if ".job" in os.path.basename(p) else "service")
             for p in [path] + sorted(
                 glob.glob(glob.escape(path) + ".job*.jsonl"))]
    for p, job in paths:
        if not os.path.isfile(p):
            continue
        row = jobs.setdefault(job, {})
        lats = []
        for line in open(p):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("kind") == "summary" and \
                    rec.get("alarm_fired"):
                row["alarms"] = sum(rec["alarm_fired"].values())
            if rec.get("kind") != "round":
                continue
            row["rounds"] = row.get("rounds", 0) + 1
            spans = rec.get("spans") or {}
            if spans:
                lats.append(sum(spans.values()))
            row["up"] = row.get("up", 0.0) + (
                rec.get("uplink_bytes") or 0.0)
            row["down"] = row.get("down", 0.0) + (
                rec.get("downlink_bytes") or 0.0)
            probes = rec.get("probes") or {}
            if probes.get("job_backlog_total") is not None:
                row["backlog"] = probes["job_backlog_total"]
            if probes.get("async_staleness_max") is not None:
                row["stale"] = probes["async_staleness_max"]
            if probes.get("slo_burn_max") is not None:
                row["burn"] = probes["slo_burn_max"]
            if rec.get("dp_epsilon") is not None:
                row["eps"] = rec["dp_epsilon"]
        if lats:
            lats.sort()
            row["p95_s"] = lats[min(len(lats) - 1,
                                    int(round(0.95 * (len(lats) - 1))))]
    return jobs


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="live-tail console for a fedservice daemon")
    ap.add_argument("--url", default="",
                    help="exporter base URL, e.g. "
                         "http://127.0.0.1:9100")
    ap.add_argument("--ledger", default="",
                    help="fallback: tail the ledger shards at this "
                         "base path instead of scraping")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="seconds between refreshes")
    ap.add_argument("--iterations", type=int, default=0,
                    help="stop after N refreshes (0 = forever)")
    ap.add_argument("--once", action="store_true",
                    help="render one table and exit")
    args = ap.parse_args(argv)
    if not args.url and not args.ledger:
        ap.error("--url or --ledger required")

    n = 0
    while True:
        try:
            jobs = (job_table(scrape(args.url)) if args.url
                    else ledger_table(args.ledger))
            src = args.url or args.ledger
            out = (f"fedwatch {time.strftime('%H:%M:%S')} {src}\n"
                   + render_table(jobs))
        except (urllib.error.URLError, OSError) as e:
            out = f"fedwatch: scrape failed: {e}"
        if args.once or args.iterations:
            print(out)
        else:
            # ANSI home+clear keeps the table in place like top(1)
            sys.stdout.write("\x1b[H\x1b[2J" + out + "\n")
            sys.stdout.flush()
        n += 1
        if args.once or (args.iterations and n >= args.iterations):
            return 0
        time.sleep(args.interval)


if __name__ == "__main__":
    sys.exit(main())
