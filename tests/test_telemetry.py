"""Telemetry subsystem: no-op fast path, record lifecycle/ordering,
JSONL schema, the report script's round-trip + diff, and the
raw-clock grep guard (all host-side timing flows through
``telemetry.clock`` so the ledger is the one source of truth)."""

import importlib.util
import json
import os

import numpy as np
import pytest

from commefficient_tpu.telemetry import (NULL_TELEMETRY, Telemetry,
                                         validate_record)
from commefficient_tpu.telemetry.core import NULL_SPAN
from commefficient_tpu.telemetry.sinks import ConsoleSink, JSONLSink

# --- clock + probe-span guards (now linter rules) ---------------------
# The original grep guards were promoted to first-class rules in the
# analysis/lint.py AST engine (PR 4); these thin wrappers keep the
# guards in tier-1 while leaving one source of truth for each rule.


def _run_rule(name):
    from commefficient_tpu.analysis.lint import (RULES_BY_NAME,
                                                 run_lint, unwaived)
    return unwaived(run_lint(rules=[RULES_BY_NAME[name]]))


def test_no_raw_clocks_outside_telemetry():
    """``time.time()`` / ``perf_counter`` may appear ONLY under
    telemetry/ (clock.py is the one place raw clocks live); everything
    else must go through ``telemetry.clock`` so spans, Timer and the
    ledger agree on what a second is."""
    offenders = _run_rule("raw-clock")
    assert not offenders, (
        "raw clock calls outside commefficient_tpu/telemetry/ "
        "(use telemetry.clock.wall/tick):\n"
        + "\n".join(map(str, offenders)))


def test_probe_host_transfers_only_inside_metrics_host_span():
    """Probe values are materialised (``_host`` / ``jax.device_get``)
    ONLY inside a ``span(\"metrics_host\")`` block: the sync point is
    the probes' entire runtime cost, so it must be ledger-attributed —
    an unspanned transfer would both hide that cost and add a second
    blocking device round-trip per round."""
    offenders = _run_rule("probe-transfer-span")
    assert not offenders, (
        "probe values crossed to the host outside a "
        'span("metrics_host") block:\n'
        + "\n".join(map(str, offenders)))


# --- disabled fast path -----------------------------------------------


def test_disabled_telemetry_is_noop():
    tel = Telemetry()
    assert not tel.enabled
    assert tel.begin_round(0) is None
    # the no-op span is ONE shared object — no per-call allocation
    assert tel.span("h2d") is NULL_SPAN
    assert tel.span("server") is tel.span("gather")
    with tel.span("x"):
        pass
    tel.count("prefetch_hit")
    tel.set_round_bytes(0, 1.0, 2.0)
    tel.epoch({"epoch": 1}, 1)
    tel.close()
    assert NULL_TELEMETRY.span("anything") is NULL_SPAN


def test_disabled_round_retains_nothing():
    tel = Telemetry()
    for r in range(100):
        tel.begin_round(r)
        tel.count("c")
    assert not tel._records and tel._current is None


# --- record lifecycle + JSONL sink ------------------------------------


def test_jsonl_ledger_schema_and_order(tmp_path):
    path = str(tmp_path / "run.jsonl")
    tel = Telemetry([JSONLSink(path)])
    tel.emit_meta(num_clients=4, plan={"mode": "sketch"})
    for r in range(3):
        tel.begin_round(r)
        with tel.span("h2d"):
            pass
        with tel.span("h2d"):  # accumulates, same key
            pass
        tel.count("prefetch_hit")
        tel.set_round_bytes(r, downlink=10.0 * r, uplink=4.0)
    tel.epoch({"epoch": 1, "train_loss": 0.5}, 1)
    tel.close()

    with open(path) as f:
        records = [json.loads(line) for line in f]
    for rec in records:
        assert validate_record(rec) == [], rec
    kinds = [r["kind"] for r in records]
    assert kinds[0] == "meta"
    rounds = [r for r in records if r["kind"] == "round"]
    assert [r["round"] for r in rounds] == [0, 1, 2]
    for r in rounds:
        assert r["spans"]["h2d"] >= 0.0
        assert r["counters"]["prefetch_hit"] == 1
        assert "compile_events" in r["counters"]
        assert r["uplink_bytes"] == 4.0
    assert any(r["kind"] == "epoch" for r in records)


def test_ledger_lines_carry_the_resource_clock(tmp_path):
    path = str(tmp_path / "run.jsonl")
    tel = Telemetry([JSONLSink(path)])
    for r in range(3):
        tel.begin_round(r)
        with tel.span("client_pass"):
            tel.close_round()           # where FedModel calls it
            with tel.span("metrics_host"):
                pass
        tel.set_round_bytes(r, downlink=1.0, uplink=1.0)
    tel.close()
    with open(path) as f:
        rounds = [json.loads(line) for line in f]
    assert [r["round"] for r in rounds] == [0, 1, 2]
    for rec in rounds:
        assert rec["schema"] == 9 and validate_record(rec) == []
        assert all(len(e) == 5 for e in rec["timeline"])
        assert len(rec["timeline_cpu"]) == len(rec["timeline"])
        assert set(rec["cpu"]) == set(rec["spans"])
        assert all(0.0 <= rec["cpu"][k] <= rec["spans"][k] + 1e-3
                   for k in rec["spans"])
        assert rec["stall"] is None
        assert isinstance(rec["host_rss_peak_bytes"], int)
        c = rec["counters"]
        assert {"host.cpu_user_s", "host.cpu_sys_s", "host.minflt",
                "host.nvcsw", "host.gc_runs"} <= set(c)
        assert ("host.cpus" in c) == (rec["round"] == 0)
        assert ("telemetry.close" in rec["spans"]) == (rec["round"] > 0)


def test_deferred_bytes_preserve_round_order(tmp_path):
    """Pipelined shape: rounds close before their bytes arrive (the
    flush replay attaches them later). Emission must wait and stay in
    round order."""
    path = str(tmp_path / "run.jsonl")
    sink = JSONLSink(path)
    tel = Telemetry([sink])
    tel.begin_round(0)
    tel.begin_round(1)   # swaps 0 out
    tel.begin_round(2)   # finishes 0, which has no bytes yet; swaps 1 out
    tel.close_round()    # finishes 1, as FedModel does after its dispatch
    with open(path) as f:
        assert f.read() == ""  # nothing emitted yet
    # bytes arrive out of order: 1 before 0
    tel.set_round_bytes(1, 0.0, 1.0)
    with open(path) as f:
        assert f.read() == ""  # 0 still blocks the front
    tel.set_round_bytes(0, 0.0, 1.0)
    with open(path) as f:
        emitted = [json.loads(x) for x in f]
    assert [r["round"] for r in emitted] == [0, 1]
    tel.set_round_bytes(2, 0.0, 1.0)
    tel.close()
    with open(path) as f:
        emitted = [json.loads(x) for x in f]
    assert [r["round"] for r in emitted] == [0, 1, 2]


def test_close_flushes_byteless_rounds(tmp_path):
    """An aborted run (divergence) never attaches bytes to the last
    rounds; close() must still emit them (bytes stay null) rather
    than dropping the tail."""
    path = str(tmp_path / "run.jsonl")
    tel = Telemetry([JSONLSink(path)])
    tel.begin_round(0)
    tel.close()
    with open(path) as f:
        recs = [json.loads(x) for x in f]
    assert len(recs) == 1 and recs[0]["round"] == 0
    assert recs[0]["uplink_bytes"] is None
    assert validate_record(recs[0]) == []


def test_schema_v4_device_time_round_trip(tmp_path):
    """A fresh round record is schema v4 with ``device_time: None``;
    a populated bucket dict — numeric aggregates plus the v4
    ``per_device``/``skew`` sub-dicts — validates and survives the
    JSONL sink; malformed device_time is caught; v1/v2 (no
    device_time key) and v3 (numeric-only buckets) ledgers stay
    readable."""
    from commefficient_tpu.telemetry.record import (
        READABLE_SCHEMA_VERSIONS, make_round_record)

    assert READABLE_SCHEMA_VERSIONS == (1, 2, 3, 4, 5, 6, 7, 8, 9)
    rec = make_round_record(0)
    assert rec["schema"] == 9 and rec["device_time"] is None
    assert rec["slo"] is None  # v6: the SLO stamp, None unless armed
    assert "causal" not in rec  # v7's key: nothing writes it any more
    assert validate_record(rec) == []

    rec["device_time"] = {"window_s": 0.01, "busy_s": 0.004,
                          "compute_s": 0.003, "collective_s": 0.0005,
                          "transfer_s": 0.0005, "host_gap_s": 0.006,
                          "roofline_utilization": 0.2,
                          "per_device": {"TPU:0": {
                              "busy_s": 0.004, "wait_s": 0.0001,
                              "wire_s": 0.0004}},
                          "skew": {"n_collectives": 2,
                                   "max_enter_delta_s": 0.0001,
                                   "p95_enter_delta_s": 0.0001,
                                   "straggler_device": "TPU:0"}}
    assert validate_record(rec) == []
    # dict values are allowed ONLY under the v4 sub-dict keys
    bad_dict = dict(rec, device_time={"window_s": {"oops": 1.0}})
    assert any("device_time" in p for p in validate_record(bad_dict))
    # shard records may stamp their process index; it must be an int
    stamped = dict(rec, process=1)
    assert validate_record(stamped) == []
    assert any("process" in p
               for p in validate_record(dict(rec, process="p1")))
    path = str(tmp_path / "v4.jsonl")
    sink = JSONLSink(path)
    sink.write(rec)
    sink.close()
    with open(path) as f:
        back = json.loads(f.read())
    assert validate_record(back) == []
    assert back["device_time"] == rec["device_time"]

    bad = dict(rec, device_time=[1, 2])
    assert any("device_time" in p for p in validate_record(bad))
    bad = dict(rec, device_time={"busy_s": "fast"})
    assert any("device_time" in p for p in validate_record(bad))

    # pre-v3 records never carried the key — still valid
    v2 = {k: v for k, v in make_round_record(1).items()
          if k != "device_time"}
    v2["schema"] = 2
    assert validate_record(v2) == []
    v1 = {k: v for k, v in v2.items()
          if k not in ("probes", "alarms")}
    v1["schema"] = 1
    assert validate_record(v1) == []
    # v3 ledgers (numeric-only buckets, no per_device/skew) read back
    v3 = dict(make_round_record(1), schema=3)
    v3["device_time"] = {"window_s": 0.01, "busy_s": 0.004,
                         "compute_s": 0.003, "collective_s": 0.0005,
                         "transfer_s": 0.0005, "host_gap_s": 0.006}
    assert validate_record(v3) == []
    # ...but a v3+/v4 record MUST carry the key
    v4_missing = {k: v for k, v in make_round_record(2).items()
                  if k != "device_time"}
    assert any("device_time" in p
               for p in validate_record(v4_missing))


def test_console_sink_aggregates(capsys):
    tel = Telemetry([ConsoleSink()])
    for r in range(2):
        tel.begin_round(r)
        with tel.span("server"):
            pass
        tel.set_round_bytes(r, downlink=2 ** 20, uplink=2 ** 20)
    tel.close()
    out = capsys.readouterr().out
    assert "telemetry summary (2 rounds)" in out
    assert "span server" in out
    assert "up 2.0 MiB" in out


def test_json_default_handles_numpy(tmp_path):
    path = str(tmp_path / "np.jsonl")
    sink = JSONLSink(path)
    sink.write({"schema": 1, "kind": "bench", "ts": 0.0,
                "metric": "m", "unit": "u",
                "value": np.float32(1.5), "n": np.int64(3),
                "arr": np.arange(2)})
    sink.close()
    with open(path) as f:
        rec = json.load(f)
    assert rec["value"] == 1.5 and rec["n"] == 3 and rec["arr"] == [0, 1]


# --- report script round-trip -----------------------------------------


def _load_report_module():
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "telemetry_report.py")
    spec = importlib.util.spec_from_file_location("telemetry_report",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_ledger(path, n_rounds, ms_per_round, bytes_per_round):
    tel = Telemetry([JSONLSink(str(path))])
    tel.emit_meta(num_clients=4,
                  plan={"mode": "sketch", "grad_size": 10,
                        "num_workers": 2})
    for r in range(n_rounds):
        rec = tel.begin_round(r)
        rec["spans"]["server"] = ms_per_round / 1e3
        tel.set_round_bytes(r, bytes_per_round, bytes_per_round)
    tel.close()


def test_report_summarize_round_trips(tmp_path):
    report = _load_report_module()
    path = tmp_path / "a.jsonl"
    _write_ledger(path, n_rounds=3, ms_per_round=10.0,
                  bytes_per_round=100.0)
    records, problems = report.load_ledger(str(path))
    assert problems == []
    s = report.summarize(records)
    assert s["rounds"] == 3
    assert s["uplink_bytes"] == 300.0
    assert s["spans"]["server"]["mean_ms"] == 10.0
    text = report.render_summary(s)
    assert "rounds: 3" in text and "span server" in text


@pytest.mark.parametrize("schema", [8, 9])
def test_report_reads_a_ledger_with_or_without_the_resource_clock(
        tmp_path, schema):
    report = _load_report_module()
    path = tmp_path / "a.jsonl"
    _write_ledger(path, n_rounds=3, ms_per_round=10.0,
                  bytes_per_round=100.0)
    if schema == 8:         # as PR 37's writer left it
        lines = []
        for line in path.read_text().splitlines():
            rec = json.loads(line)
            rec["schema"] = 8
            for key in ("cpu", "timeline_cpu", "stall"):
                rec.pop(key, None)
            rec["counters"] = {k: v for k, v in
                               rec.get("counters", {}).items()
                               if not k.startswith("host.")}
            lines.append(json.dumps(rec))
        path.write_text("\n".join(lines) + "\n")
    records, problems = report.load_ledger(str(path))
    assert problems == []
    assert {r["schema"] for r in records} == {schema}
    s = report.summarize(records)
    assert s["rounds"] == 3 and s["host_rss_peak_bytes"] > 0
    assert "rounds: 3" in report.render_summary(s)


def test_report_diff(tmp_path):
    report = _load_report_module()
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_ledger(a, n_rounds=2, ms_per_round=10.0,
                  bytes_per_round=100.0)
    _write_ledger(b, n_rounds=2, ms_per_round=20.0,
                  bytes_per_round=50.0)
    sa = report.summarize(report.load_ledger(str(a))[0])
    sb = report.summarize(report.load_ledger(str(b))[0])
    d = report.diff_summaries(sa, sb)
    assert d["spans"]["server"]["ratio"] == 2.0
    assert d["uplink_bytes"]["ratio"] == 0.5
    text = report.render_diff(d, "a", "b")
    assert "span server" in text


def test_report_privacy_section(tmp_path):
    """DP runs: the report carries the ε trajectory and the
    noise-vs-recovery-error pairing; diff shows ε spent a -> b."""
    report = _load_report_module()

    def write(path, sigma, eps_per_round, err):
        tel = Telemetry([JSONLSink(str(path))])
        for r in range(3):
            tel.begin_round(r)
            tel.merge_round_probes(r, {"recovery_error": err})
            tel.set_round_privacy(r, eps_per_round * (r + 1), 1e-5,
                                  sigma)
            tel.set_round_bytes(r, 10.0, 10.0)
        tel.close()

    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    write(a, sigma=0.5, eps_per_round=0.1, err=0.2)
    write(b, sigma=1.0, eps_per_round=0.05, err=0.4)
    sa = report.summarize(report.load_ledger(str(a))[0])
    pv = sa["privacy"]
    assert pv["rounds"] == 3
    assert pv["eps_first"] == pytest.approx(0.1)
    assert pv["eps_last"] == pytest.approx(0.3)
    assert pv["delta"] == pytest.approx(1e-5)
    assert pv["noise_vs_recovery"] == [
        {"dp_sigma": 0.5, "rounds": 3,
         "recovery_err_mean": pytest.approx(0.2),
         "recovery_err_max": pytest.approx(0.2)}]
    text = report.render_summary(sa)
    assert "privacy: eps 0.1 -> 0.3" in text
    assert "privacy sigma 0.5" in text
    sb = report.summarize(report.load_ledger(str(b))[0])
    d = report.diff_summaries(sa, sb)
    assert d["privacy"]["a_eps_last"] == pytest.approx(0.3)
    assert d["privacy"]["b_eps_last"] == pytest.approx(0.15)
    assert "privacy eps spent" in report.render_diff(d, "a", "b")
    # dp-less ledgers: no privacy section, no diff entry
    c = tmp_path / "c.jsonl"
    _write_ledger(c, n_rounds=2, ms_per_round=1.0,
                  bytes_per_round=1.0)
    sc = report.summarize(report.load_ledger(str(c))[0])
    assert sc["privacy"] is None
    assert "privacy" not in report.diff_summaries(sc, sc)


def test_report_flags_invalid_lines(tmp_path):
    report = _load_report_module()
    path = tmp_path / "bad.jsonl"
    path.write_text('not json\n{"schema": 99, "kind": "round"}\n'
                    + json.dumps({"schema": 1, "kind": "meta",
                                  "ts": 0.0}) + "\n")
    records, problems = report.load_ledger(str(path))
    assert len(records) == 1
    assert len(problems) == 2


# --- prefetch worker-death surfacing ----------------------------------


def test_prefetch_worker_death_surfaces():
    """An exception that escapes the worker LOOP (not a per-job
    gather error) must raise on the main thread at the next take(),
    not stall the round out to the take timeout."""
    import pytest

    from commefficient_tpu.clientstore.prefetch import StorePrefetcher

    class EvilStore:
        def gather(self, ids, out=None):
            raise MemoryError("host arena exhausted")

    pf = StorePrefetcher(EvilStore())
    try:
        # malformed job: unpack fails OUTSIDE the per-job try
        pf._jobs.put("not-a-tuple")
        pf._pending += 1
        pf._thread.join(timeout=5.0)
        assert not pf._thread.is_alive()
        with pytest.raises(RuntimeError, match="prefetch worker died"):
            pf.take(np.array([0], np.int64), timeout=5.0)
        with pytest.raises(RuntimeError, match="prefetch worker died"):
            pf.submit(np.array([1], np.int64))
    finally:
        pf.close(timeout=1.0)


def test_prefetch_per_job_error_still_raises_via_take():
    """Per-job store errors keep the existing surfacing path: the
    exception rides the done-queue and re-raises in take()."""
    import pytest

    from commefficient_tpu.clientstore.prefetch import StorePrefetcher

    class EvilStore:
        def gather(self, ids, out=None):
            raise MemoryError("host arena exhausted")

        def row_version(self, cid):
            return 0

    pf = StorePrefetcher(EvilStore())
    try:
        pf.submit(np.array([0, 1], np.int64))
        with pytest.raises(MemoryError, match="arena exhausted"):
            pf.take(np.array([0, 1], np.int64), timeout=5.0)
    finally:
        pf.close(timeout=1.0)
