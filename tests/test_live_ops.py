"""Live operations plane: metrics exporter, SLO burn rates, flight
recorder.

The plane's contract, tested here end to end: the exporter serves
exactly what the ledger records (one registry, per-job labels, text
exposition that a minimal Prometheus parser round-trips); SLO burn is
the classic multi-window error-budget rate and matches a NumPy mirror
bit-for-bit; the ``slo_burn`` alarm shares the ``--on_divergence``
escalation; the flight recorder's postmortem bundle is atomic (a
SIGKILLed process leaves either a complete bundle or none), bounded,
and rate-limited to one bundle per firing rule; and with every knob
unset the whole plane is never constructed — the telemetry no-op
fast path stays untouched.
"""

import importlib.util
import json
import os
import signal
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

from commefficient_tpu.config import Config
from commefficient_tpu.telemetry.alarms import (AlarmEngine,
                                                DivergenceAbort)
from commefficient_tpu.telemetry.core import Telemetry
from commefficient_tpu.telemetry.flightrec import (FlightRecorder,
                                                   install_crash_hook,
                                                   load_postmortem)
from commefficient_tpu.telemetry.live import (PREFIX, LiveMetricsSink,
                                              LiveRegistry, LiveServer,
                                              attach_live_plane,
                                              shutdown_plane)
from commefficient_tpu.telemetry.record import make_round_record
from commefficient_tpu.telemetry.sinks import (job_index_of_ledger,
                                               recover_ledger_shards)
from commefficient_tpu.telemetry.slo import (SLOEngine, SLOSpec,
                                             build_slo_engine)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh_plane():
    yield
    shutdown_plane()


# --- registry + exposition format --------------------------------------


def test_registry_render_round_trips_through_parser():
    """What the registry renders, the operator console's minimal
    parser reads back — names, label escaping, quantiles, _sum/_count
    — so the two ends of the scrape share one wire contract."""
    fedwatch = _load_script("fedwatch")
    reg = LiveRegistry()
    labels = {"job": 'we"ird\\job', "run": "r1"}
    reg.counter_add("c_total", 2, labels)
    reg.counter_add("c_total", 3, labels)
    reg.gauge_set("g", -1.5, labels)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.observe("s_seconds", v, labels)
    samples = fedwatch.parse_prometheus(reg.render())
    by_name = {}
    for name, lab, val in samples:
        by_name.setdefault(name, []).append((lab, val))
    assert by_name["c_total"] == [(labels, 5.0)]
    assert by_name["g"] == [(labels, -1.5)]
    qs = {lab["quantile"]: val for lab, val in by_name["s_seconds"]}
    # nearest-rank quantiles over the sorted window [1,2,3,4]:
    # p50 -> index round(0.5*3) = 2 -> 3.0
    assert qs == {"0.5": 3.0, "0.95": 4.0, "1": 4.0}
    assert by_name["s_seconds_sum"] == [(labels, 10.0)]
    assert by_name["s_seconds_count"] == [(labels, 4.0)]


def _round_rec(r, **kw):
    rec = make_round_record(r)
    rec.update(kw)
    return rec


def test_live_sink_derives_series_from_records():
    """The sink derives every exported series from the record stream
    alone — the same records the ledger gets — so a scrape can never
    disagree with the post-hoc ledger."""
    reg = LiveRegistry()
    sink = LiveMetricsSink(reg, labels={"job": "0"})
    sink.write({"kind": "meta", "plan": {"num_workers": 8}})
    sink.write(_round_rec(
        0, spans={"client": 0.75, "server": 0.25},
        uplink_bytes=1000.0, downlink_bytes=2000.0, dp_epsilon=0.25,
        probes={"job_backlog_total": 3.0, "slo_burn_round_latency": 0.5,
                "slo_burn_max": 0.5},
        alarms=[{"rule": "slo_burn", "value": 10.0}]))
    sink.write({"kind": "summary", "alarm_fired": {"slo_burn": 2}})
    snap = reg.snapshot()

    def series(kind, name):
        return {snap["labels"][k]["objective"]
                if "objective" in snap["labels"][k]
                else snap["labels"][k].get("rule", "0"): v
                for k, v in snap[kind][PREFIX + name].items()}

    assert series("counters", "rounds_total") == {"0": 1.0}
    assert series("counters", "uplink_bytes_total") == {"0": 1000.0}
    assert series("counters", "downlink_bytes_total") == {"0": 2000.0}
    assert series("counters", "alarms_total") == {"slo_burn": 1.0}
    assert series("gauges", "clients_per_s") == {"0": 8.0}
    assert series("gauges", "job_backlog_total") == {"0": 3.0}
    assert series("gauges", "dp_epsilon") == {"0": 0.25}
    assert series("gauges", "slo_burn") == {"round_latency": 0.5,
                                            "max": 0.5}
    assert series("gauges", "alarms_run_total") == {"slo_burn": 2.0}
    window, total, count = next(iter(
        snap["summaries"][PREFIX + "round_seconds"].values()))
    assert (window, total, count) == ([1.0], 1.0, 1)


def test_exporter_serves_metrics_and_healthz():
    reg = LiveRegistry()
    reg.counter_add(PREFIX + "rounds_total", 7, {"job": "a"})
    server = LiveServer(reg, port=0)  # ephemeral
    try:
        with urllib.request.urlopen(server.url + "/metrics") as resp:
            assert "version=0.0.4" in resp.headers["Content-Type"]
            body = resp.read().decode()
        assert f'{PREFIX}rounds_total{{job="a"}} 7' in body
        with urllib.request.urlopen(server.url + "/healthz") as resp:
            assert resp.read() == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(server.url + "/nope")
    finally:
        server.close()


def test_plane_off_is_never_constructed():
    """Both knobs unset: no sink, no recorder, no server thread, and
    the telemetry fan-out keeps its disabled fast path."""
    from commefficient_tpu.telemetry import live

    tel = Telemetry()
    sink, rec = attach_live_plane(tel, Config())
    assert sink is None and rec is None
    assert not tel.enabled
    assert live._PLANE["server"] is None
    assert live._PLANE["registry"] is None


def test_job_index_of_ledger():
    assert job_index_of_ledger("runs/svc.jsonl.job3.jsonl") == 3
    assert job_index_of_ledger(
        "runs/svc.jsonl.job3.jsonl.p1.jsonl") == 3
    assert job_index_of_ledger("runs/svc.jsonl") is None
    assert job_index_of_ledger("") is None


# --- SLO burn-rate math ------------------------------------------------


def test_burn_rate_matches_numpy_mirror():
    """The engine's burn per round equals the NumPy-mirrored
    min(fast, slow) window violation rate over the error budget."""
    spec = SLOSpec(round_p95_s=1.0, error_budget=0.05,
                   window=16, fast_window=4)
    eng = SLOEngine(spec)
    lat = np.random.RandomState(0).uniform(0.5, 1.5, size=64)
    viol = (lat > spec.round_p95_s).astype(float)
    for i, v in enumerate(lat):
        probes = eng.observe(i, round_s=float(v))
        if i + 1 < spec.fast_window:  # warmup: never alarm cold
            assert probes["slo_burn_round_latency"] == 0.0
            continue
        fast = viol[max(0, i + 1 - spec.fast_window):i + 1].mean()
        slow = viol[max(0, i + 1 - spec.window):i + 1].mean()
        want = min(fast, slow) / spec.error_budget
        assert probes["slo_burn_round_latency"] == pytest.approx(want)
        assert probes["slo_burn_max"] == pytest.approx(want)


def test_multiwindow_needs_current_and_sustained():
    """One bad round never pages (slow window dilutes it); a
    sustained burn does; recovery drops the burn immediately (fast
    window clears) even while the slow window is still hot."""
    spec = SLOSpec(round_p95_s=1.0, error_budget=0.05,
                   window=32, fast_window=4)
    eng = SLOEngine(spec)
    for i in range(32):
        eng.observe(i, round_s=0.5)
    p = eng.observe(32, round_s=5.0)  # one blip after a clean run
    assert p["slo_burn_round_latency"] == pytest.approx(
        (1 / 32) / 0.05)
    assert not eng.burning
    for i in range(33, 49):  # sustained: 16 bad rounds
        p = eng.observe(i, round_s=5.0)
    assert p["slo_burn_round_latency"] >= 10.0
    assert eng.burning
    for i in range(49, 53):  # recovery: fast window all clean
        p = eng.observe(i, round_s=0.5)
    assert p["slo_burn_round_latency"] == 0.0
    assert not eng.burning


def test_privacy_burn_linear_schedule():
    """ε spend at or under the linear schedule ε*(n+1)/horizon never
    violates; spending ahead of it burns."""
    spec = SLOSpec(eps_horizon=10, eps_budget=1.0,
                   window=4, fast_window=2)
    eng = SLOEngine(spec)
    for n in range(6):  # strictly under the schedule
        p = eng.observe(n, dp_epsilon=0.05 * (n + 1))
        assert p["slo_burn_privacy_burn"] == 0.0
    for n in range(6, 10):  # overspent from round 6 of 10 on
        p = eng.observe(n, dp_epsilon=1.1)
    assert p["slo_burn_privacy_burn"] == pytest.approx(1.0 / 0.05)
    stamp = eng.stamp()["privacy_burn"]
    assert stamp["seen"] == 10 and stamp["fast_rate"] == 1.0


def test_objectives_advance_independently():
    """An objective with no signal this round does not advance — its
    windows measure its own stream, not wall rounds."""
    spec = SLOSpec(round_p95_s=1.0, staleness_max=2.0,
                   window=8, fast_window=2)
    eng = SLOEngine(spec)
    for i in range(4):
        eng.observe(i, round_s=5.0)  # latency only
    p = eng.observe(4, staleness_max=1.0)  # first staleness sample
    assert eng.stamp()["round_latency"]["seen"] == 4
    assert eng.stamp()["staleness"]["seen"] == 1
    assert p["slo_burn_staleness"] == 0.0  # still in ITS warmup
    assert p["slo_burn_max"] == p["slo_burn_round_latency"] > 1.0


def test_build_slo_engine_gating():
    assert build_slo_engine(Config()) is None  # all targets 0
    eng = build_slo_engine(Config(slo_round_p95=0.5))
    assert eng is not None and not eng.burning
    # privacy objective arms only with a real DP budget
    eng = build_slo_engine(Config(dp="sketch", dp_epsilon=2.0,
                                  dp_noise_mult=1.0,
                                  slo_eps_rounds=10))
    assert eng is not None
    assert "privacy_burn" in eng._objectives
    with pytest.raises(AssertionError):  # ε horizon without DP
        Config(slo_eps_rounds=10)


# --- the slo_burn alarm rule -------------------------------------------


def test_slo_alarm_fires_with_objective_breakdown():
    cfg = Config(alarm_slo_burn=2.0, slo_round_p95=0.1,
                 slo_window=4, slo_fast_window=2)
    engine = AlarmEngine(cfg)
    assert engine.check_slo(0, {}) == []
    assert engine.check_slo(
        0, {"slo_burn_max": 1.9,
            "slo_burn_round_latency": 1.9}) == []
    fired = engine.check_slo(
        3, {"slo_burn_max": 12.0, "slo_burn_round_latency": 12.0,
            "slo_burn_staleness": 0.5})
    assert [a["rule"] for a in fired] == ["slo_burn"]
    assert fired[0]["value"] == 12.0 and fired[0]["threshold"] == 2.0
    # the alarm names WHICH objective burns, not just that one does
    assert fired[0]["slo_burn_round_latency"] == 12.0
    assert fired[0]["slo_burn_staleness"] == 0.5


def test_slo_alarm_abort_escalation():
    cfg = Config(alarm_slo_burn=1.0, slo_round_p95=0.1,
                 on_divergence="abort")
    engine = AlarmEngine(cfg)
    with pytest.raises(DivergenceAbort, match="slo_burn"):
        engine.check_slo(5, {"slo_burn_max": 3.0})


def test_alarm_counts_backfilled_on_summary(tmp_path):
    """Every flagged alarm lands in the close()-time summary record's
    per-rule ``alarm_fired`` totals; clean runs emit no summary."""
    from commefficient_tpu.telemetry.sinks import JSONLSink

    path = str(tmp_path / "led.jsonl")
    tel = Telemetry([JSONLSink(path)])
    tel.begin_round(0)
    tel.flag_alarm(0, {"rule": "slo_burn", "value": 2.0})
    tel.flag_alarm(0, {"rule": "slo_burn", "value": 3.0})
    tel.flag_alarm(0, {"rule": "nan_inf", "value": 1.0})
    tel.close()
    recs = [json.loads(x) for x in open(path)]
    summ = [r for r in recs if r["kind"] == "summary"]
    assert len(summ) == 1
    assert summ[0]["alarm_fired"] == {"nan_inf": 1, "slo_burn": 2}

    clean = str(tmp_path / "clean.jsonl")
    tel = Telemetry([JSONLSink(clean)])
    tel.begin_round(0)
    tel.close()
    kinds = [json.loads(x)["kind"] for x in open(clean)]
    assert "summary" not in kinds  # bit-identity for healthy runs


# --- flight recorder ---------------------------------------------------


def test_flightrec_ring_bound_and_one_bundle_per_rule(tmp_path):
    out = str(tmp_path / "pm")
    fr = FlightRecorder(Config(), 4, labels={"job": "j"}, out_dir=out)
    fr.write({"kind": "meta", "plan": {"num_workers": 2}})
    for r in range(9):
        fr.write(_round_rec(r))
    trip = _round_rec(9, alarms=[{"rule": "slo_burn", "value": 9.0,
                                  "threshold": 1.0}])
    fr.write(trip)  # alarm in-stream -> dump, trigger inside the ring
    first = fr.last_bundle
    assert first and os.path.isfile(first)
    assert not [n for n in os.listdir(out) if n.endswith(".tmp")]
    bundle, problems = load_postmortem(first)
    assert problems == []
    assert [r["round"] for r in bundle["rounds"]] == [6, 7, 8, 9]
    assert bundle["rounds"][-1]["alarms"][0]["rule"] == "slo_burn"
    assert bundle["labels"] == {"job": "j"}
    assert bundle["meta"]["plan"] == {"num_workers": 2}
    assert [e["rule"] for e in bundle["events"]
            if e["kind"] == "alarm"] == ["slo_burn"]

    # same rule keeps firing: same incident, no new bundle
    fr.write(_round_rec(10, alarms=[{"rule": "slo_burn",
                                     "value": 10.0}]))
    assert fr.last_bundle == first
    assert len(os.listdir(out)) == 1
    # a DIFFERENT rule (and a shutdown) are new incidents
    fr.write(_round_rec(11, alarms=[{"rule": "nan_inf",
                                     "value": 1.0}]))
    fr.dump("graceful_shutdown", context={"signal": "SIGTERM"})
    assert len(os.listdir(out)) == 3


def test_flightrec_crash_hook_dumps_before_traceback(tmp_path,
                                                     capsys):
    fr = FlightRecorder(Config(), 2, out_dir=str(tmp_path / "pm"))
    fr.write(_round_rec(0))
    prev = sys.excepthook
    try:
        hook = install_crash_hook(fr)
        hook(ValueError, ValueError("boom"), None)
    finally:
        sys.excepthook = prev
    bundle, problems = load_postmortem(fr.last_bundle)
    assert problems == []
    assert bundle["reason"] == "crash"
    assert "ValueError: boom" in bundle["context"]["exception"]
    assert capsys.readouterr().err  # the traceback still printed


def test_postmortem_survives_sigkill(tmp_path):
    """Trip an alarm (bundle dumps atomically), then SIGKILL the
    process: the parent finds a complete, valid bundle — never a torn
    one — because the write is tmp + fsync + rename."""
    out = str(tmp_path / "pm")
    code = (
        "import os, signal\n"
        "from commefficient_tpu.config import Config\n"
        "from commefficient_tpu.telemetry.flightrec import "
        "FlightRecorder\n"
        "from commefficient_tpu.telemetry.record import "
        "make_round_record\n"
        f"fr = FlightRecorder(Config(), 4, labels={{'job': '0'}},\n"
        f"                    out_dir={out!r})\n"
        "for r in range(6):\n"
        "    rec = make_round_record(r)\n"
        "    if r == 5:\n"
        "        rec['alarms'] = [{'rule': 'slo_burn', 'value': 9.0,\n"
        "                          'threshold': 1.0}]\n"
        "    fr.write(rec)\n"
        "assert fr.last_bundle\n"
        "os.kill(os.getpid(), signal.SIGKILL)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=_REPO)
    assert res.returncode == -signal.SIGKILL, res.stderr[-2000:]
    names = sorted(os.listdir(out))
    assert len(names) == 1 and names[0].endswith(".json"), names
    bundle, problems = load_postmortem(os.path.join(out, names[0]))
    assert problems == []
    assert bundle["reason"] == "alarm" and bundle["rule"] == "slo_burn"
    assert [r["round"] for r in bundle["rounds"]] == [2, 3, 4, 5]


# --- lock confinement under real threads (flowlint regression) ---------


def test_flightrec_concurrent_writer_and_dump(tmp_path):
    """The crash-hook/alarm threads dump while the round loop
    appends: the ring snapshot under the lock means no 'deque mutated
    during iteration', and the claim-before-I/O means two racing
    dumps of the SAME incident write exactly one bundle."""
    import threading

    out = str(tmp_path / "pm")
    fr = FlightRecorder(Config(), 4, labels={"job": "j"},
                        out_dir=out)
    fr.write({"kind": "meta", "plan": {}})
    stop = threading.Event()
    errors = []

    def writer():
        r = 0
        while not stop.is_set():
            try:
                fr.write(_round_rec(r))
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return
            r += 1

    def dumper(reason):
        try:
            fr.dump(reason, rule="crash_race")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    w = threading.Thread(target=writer)
    w.start()
    dumpers = [threading.Thread(target=dumper, args=("crash",))
               for _ in range(4)]
    for t in dumpers:
        t.start()
    for t in dumpers:
        t.join()
    stop.set()
    w.join()
    assert errors == []
    bundles = [n for n in os.listdir(out) if n.endswith(".json")]
    assert len(bundles) == 1, bundles  # one incident, one bundle
    _, problems = load_postmortem(os.path.join(out, bundles[0]))
    assert problems == []


def test_live_registry_concurrent_writers():
    """HTTP scrape threads render while round loops publish: every
    label-map write now happens under the registry lock, so N
    hammering threads lose no increments and render() never sees a
    mid-write dict."""
    import threading

    reg = LiveRegistry()
    errors = []

    def pound(j):
        try:
            for i in range(200):
                reg.counter_add("ffl_rounds_total", 1.0,
                                labels={"job": str(j)})
                reg.gauge_set("ffl_loss", float(i),
                              labels={"job": str(j)})
                reg.render()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=pound, args=(j,))
               for j in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    snap = reg.snapshot()
    counts = snap["counters"]["ffl_rounds_total"]
    assert sorted(counts.values()) == [200.0] * 4


def test_jsonl_sink_concurrent_claim_single_winner(tmp_path):
    """Two threads racing to open the same ledger path: the claim is
    taken under ``_live_lock`` BEFORE the file opens, so exactly one
    construction succeeds and the losers get the live-writer error —
    never two writers interleaving on one shard."""
    import threading

    from commefficient_tpu.telemetry.sinks import JSONLSink

    path = str(tmp_path / "led.jsonl")
    results = []

    def construct():
        try:
            results.append(JSONLSink(path))
        except RuntimeError as e:
            results.append(e)

    threads = [threading.Thread(target=construct) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sinks = [r for r in results if isinstance(r, JSONLSink)]
    errs = [r for r in results if isinstance(r, RuntimeError)]
    assert len(sinks) == 1 and len(errs) == 3, results
    sinks[0].close()
    # the claim dies with close(): reopening is legal again
    JSONLSink(path).close()


def test_report_renders_postmortem(tmp_path, capsys):
    out = str(tmp_path / "pm")
    fr = FlightRecorder(Config(), 3, labels={"job": "7"}, out_dir=out)
    fr.write({"kind": "meta", "plan": {"num_workers": 2}})
    for r in range(3):
        rec = _round_rec(r)
        rec["spans"]["server"] = 0.01
        fr.write(rec)
    path = fr.dump("alarm", rule="slo_burn",
                   context={"alarms": [{"rule": "slo_burn"}]})
    report = _load_script("telemetry_report")
    assert report.main(["--postmortem", path]) == 0
    text = capsys.readouterr().out
    assert "incident: alarm rule=slo_burn" in text
    assert "job=7" in text and "3 of last 3 round(s)" in text
    assert report.main(["--postmortem", path, "--json"]) == 0
    blob = json.loads(capsys.readouterr().out)
    assert blob["problems"] == []
    assert blob["bundle"]["rule"] == "slo_burn"
    assert blob["summary"]["rounds"] == 3


def test_latency_alarm_bundle_carries_the_rounds_timeline(tmp_path,
                                                         capsys):
    """What a ``step_time_regression`` post-mortem has to explain a
    slow round with: the ring's round records, each with its
    ``timeline`` of spans, and no second account of the same time."""
    fr = FlightRecorder(Config(), 4, out_dir=str(tmp_path / "pm"))
    tel = Telemetry([fr])
    for r in range(6):
        tel.begin_round(r)
        with tel.span("client_pass"):
            tel.close_round()           # where FedModel calls it
            with tel.span("metrics_host"):
                pass
        with tel.span("server_pass"):
            pass
        if r == 5:
            tel.flag_alarm(r, {"rule": "step_time_regression",
                               "round": r, "value": 10.0,
                               "threshold": 2.0, "rolling_median": 1.0})
        tel.set_round_bytes(r, 8.0, 4.0)
    tel.close()
    bundle, problems = load_postmortem(fr.last_bundle)
    assert problems == []
    assert bundle["rule"] == "step_time_regression"
    assert set(bundle["context"]) == {"alarms", "round"}
    assert [r["round"] for r in bundle["rounds"]] == [2, 3, 4, 5]
    for rec in bundle["rounds"]:
        assert [(e[0], e[3]) for e in rec["timeline"]] == [
            ("client_pass", None), ("telemetry.close", None),
            ("metrics_host", 0), ("server_pass", None)]
        assert all(e[1] <= e[2] for e in rec["timeline"])
        assert len(rec["timeline_cpu"]) == 4 and set(rec["cpu"]) == {
            e[0] for e in rec["timeline"]}
        assert rec["counters"]["host.cpu_user_s"] >= 0.0
        assert "causal" not in rec
    report = _load_script("telemetry_report")
    assert report.main(["--postmortem", fr.last_bundle]) == 0
    text = capsys.readouterr().out
    assert "incident: alarm rule=step_time_regression" in text
    assert "4 of last 4 round(s)" in text
    assert "critical" not in text and "causal" not in text


# --- shard recovery at daemon restart ----------------------------------


def test_recover_ledger_shards_sweeps_job_and_process_shards(
        tmp_path):
    base = str(tmp_path / "svc.jsonl")
    good = json.dumps({"kind": "round", "round": 0}) + "\n"
    shards = [base, base + ".job0.jsonl", base + ".p1.jsonl",
              base + ".job0.jsonl.p2.jsonl"]
    for p in shards:
        with open(p, "w") as f:
            f.write(good + '{"kind": "round", "rou')  # torn tail
    dropped = recover_ledger_shards(base)
    assert sorted(dropped) == sorted(shards)
    assert all(n > 0 for n in dropped.values())
    for p in shards:
        assert open(p).read() == good
    assert recover_ledger_shards(base) == {}  # idempotent
    assert recover_ledger_shards(
        str(tmp_path / "missing.jsonl")) == {}


# --- fedwatch console --------------------------------------------------


def test_fedwatch_folds_scrape_into_job_table():
    fedwatch = _load_script("fedwatch")
    reg = LiveRegistry()
    sink = LiveMetricsSink(reg, labels={"job": "0", "run": "r"})
    sink.write({"kind": "meta", "plan": {"num_workers": 4}})
    sink.write(_round_rec(
        0, spans={"server": 2.0}, uplink_bytes=4096.0,
        probes={"slo_burn_max": 1.5, "slo_burn_round_latency": 1.5},
        alarms=[{"rule": "slo_burn"}]))
    jobs = fedwatch.job_table(
        fedwatch.parse_prometheus(reg.render()))
    row = jobs["0"]
    assert row["rounds"] == 1.0 and row["p95_s"] == 2.0
    assert row["clients_s"] == 2.0 and row["up"] == 4096.0
    assert row["burn"] == 1.5 and row["alarms"] == 1.0
    table = fedwatch.render_table(jobs)
    assert table.splitlines()[0].split()[:2] == ["job", "rounds"]
    assert "4096" not in table  # bytes render in MiB
    assert "0.00M" in table


def test_fedwatch_ledger_fallback(tmp_path):
    fedwatch = _load_script("fedwatch")
    base = str(tmp_path / "svc.jsonl")
    with open(base, "w") as f:
        f.write(json.dumps({"kind": "round", "round": 0,
                            "spans": {"t": 1.0}}) + "\n")
        f.write(json.dumps({"kind": "summary",
                            "alarm_fired": {"slo_burn": 3}}) + "\n")
    with open(base + ".job1.jsonl", "w") as f:
        for r in range(2):
            f.write(json.dumps({
                "kind": "round", "round": r, "spans": {"t": 0.5},
                "uplink_bytes": 100.0, "dp_epsilon": 0.5,
                "probes": {"slo_burn_max": 2.0}}) + "\n")
    jobs = fedwatch.ledger_table(base)
    assert jobs["service"]["rounds"] == 1
    assert jobs["service"]["alarms"] == 3
    assert jobs["1"]["rounds"] == 2 and jobs["1"]["up"] == 200.0
    assert jobs["1"]["burn"] == 2.0 and jobs["1"]["eps"] == 0.5
    assert "service" in fedwatch.render_table(jobs)
