"""The readers of the program's resource clock (PR 38: CPU beside wall
on every span, the process's counters on every record, the threads'
stacks of a long round), the idle gaps laid over the host's threads
and the summed set-up: each on records and a trace made by hand, each
on what a program without the fields gives (nothing, and no error),
and all of them driven by a ``--rehearse`` run."""

import pytest

from benchmark import run as harness
from benchmark.lib import hostclock
from test_rehearsal import _run
from test_tracing_metrics import STEPS, _ctx, _record

NEW = ["runtime.stall_ms", "runtime.loop_cpu_ms", "data.loader_cpu_ms",
       "runtime.host_cpu_ms", "runtime.telemetry_ms",
       "device.idle_queued_ms", "entry.warmup_s", "entry.uncovered_s"]
#: these read what schema 8 already records, the others the new fields
FROM_THE_TIMELINE = NEW[5:]


def _round(r, t, period=0.1, first=False):
    """One round from ``t``: client pass 80 ms of wall and 30 of CPU
    (its ``metrics_host`` child 50 / 1: a wait), the recorder's own span
    2 / 1.5 inside it with no parent, server pass 5 / 4, sampler 10 / 2
    with a 6 / 0.5 hand-over wait; 5 ms under no span; the loader's
    thread 20 / 15. ``period`` stretches the wait."""
    m, extra = "MainThread", period - 0.1
    tl = [["client_pass", t, t + .080 + extra, None, m],
          ["round_dispatch", t + .001, t + .003, 0, m],
          ["telemetry.close", t + .003, t + .005, None, m],
          ["metrics_host", t + .010, t + .060 + extra, 0, m],
          ["server_pass", t + .081 + extra, t + .086 + extra, None, m],
          ["sampler", t + .088 + extra, t + .098 + extra, None, m],
          ["data.pop_wait", t + .090 + extra, t + .096 + extra, 5, m],
          ["data.index", t + .010, t + .030, None, "loader-stage"]]
    cpu = [.030, .002, .0015, .001, .004, .002, .0005, .015]
    counters = {"host.cpu_user_s": 0.040, "host.cpu_sys_s": 0.010,
                "host.minflt": 100, "host.majflt": 0, "host.nvcsw": 30,
                "host.nivcsw": 2, "host.gc_s": 0.0, "host.gc_runs": 0}
    if first:
        counters.update({"host.cpus": 13, "host.threads": 4,
                         "host.os_threads": 90})
    rec = _record(r, tl, counters)
    rec["timeline_cpu"] = cpu
    rec["cpu"] = {}
    for e, c in zip(tl, cpu):
        rec["cpu"][e[0]] = rec["cpu"].get(e[0], 0.0) + c
    rec["stall"] = None
    return rec


def _records(n=9, long_at=None, long_s=1.1):
    out, t = [], 10.0
    for r in range(n):
        period = long_s if r == long_at else 0.1
        out.append(_round(r, t, period, first=(r == 0)))
        t += period
    return out


def test_cpu_readers_on_handmade_records(capsys):
    ctx = _ctx(_records())
    read = {n: harness.load("metrics", n).read(ctx) for n in NEW[:5]}
    # the last record has no successor, so no period: 8 rounds count
    assert read["runtime.stall_ms"] == 0.0
    assert read["runtime.loop_cpu_ms"] == pytest.approx(30 + 4 + 2)
    assert read["data.loader_cpu_ms"] == pytest.approx(15.0)
    assert read["runtime.host_cpu_ms"] == pytest.approx(50.0)
    assert read["runtime.telemetry_ms"] == pytest.approx(2.0)
    out = capsys.readouterr().out
    # CPU + wait + what no span covers make the period
    assert "CPU 36.000 + wait 59.000 + under no span 5.000 = 100.000 " \
        "against a mean period of 100.000 (+0.000 %)" in out
    # a span's own wait leaves out its children's: client_pass waited
    # 50 ms in all, 49 of them inside metrics_host
    assert "client_pass 80.000 / 30.000 / 1.000" in out
    assert "metrics_host 50.000 / 1.000 / 49.000" in out
    assert "thread [loader-stage]" in out and "wall 20.000, CPU 15.000" in out
    assert "host.cpus 13, host.threads 4" in out
    assert "= 3.85 % of 13 cores" in out
    assert "telemetry.close: 2.000 ms a round, of it CPU 1.500" in out


def test_a_long_round_is_a_stall_and_an_epoch_opening_is_not(capsys):
    recs = _records(long_at=4)
    recs[4]["stall"] = {"after_s": 1.0, "threads": {
        "MainThread": ["fed_model.py:812 _client_pass", "x.py:1 f"],
        "loader-stage": ["loader.py:40 _stage"]}}
    recs[4]["counters"]["stall.captured"] = 1
    got = harness.load("metrics", "runtime.stall_ms").read(_ctx(recs))
    # 1,100 ms against 3 x the median of 100: 800 beyond, over 8 rounds
    assert got == pytest.approx(800.0 / 8)
    out = capsys.readouterr().out
    assert "in 1 of 8 untraced rounds" in out
    assert "records with a stall field: [4]" in out
    assert "longest round 4: period 1100.000 ms" in out
    assert "metrics_host 1050.000 / 1.000" in out and "host.minflt 100" in out
    assert "MainThread: fed_model.py:812 _client_pass < x.py:1 f" in out
    # a round of 2.1 medians (an epoch's opening) is no stall
    recs = _records(long_at=4, long_s=0.21)
    assert harness.load("metrics", "runtime.stall_ms").read(_ctx(recs)) == 0.0


def test_a_program_without_the_fields_gives_nothing():
    """The parent of the PR that added them: schema 8 records, with a
    timeline but no CPU beside it, no ``host.*`` counter, no span of
    the recorder's own."""
    recs = _records()
    for r in recs:
        r["timeline"] = [e for e in r["timeline"]
                         if e[0] != "telemetry.close"]
        r["spans"].pop("telemetry.close")
        r["counters"] = {}
        del r["timeline_cpu"], r["cpu"], r["stall"]
    ctx = _ctx(recs, trace_dir=STEPS)
    for name in NEW[:5]:
        assert harness.load("metrics", name).read(ctx) is None, name
    # and schema 7's, with no timeline at all, nothing from any of them
    bare = [{"kind": "round", "round": r, "spans": {"sampler": 0.1},
             "counters": {}} for r in range(5)]
    ctx = _ctx(bare, first=3, trace_dir=STEPS)
    ctx["rounds"] = [{"t_end": 90.0 + i} for i in range(5)]
    for name in NEW:
        assert harness.load("metrics", name).read(ctx) is None, name


def test_innermost_overlay_and_clip():
    spans = [(0.0, 10.0, "client_pass"), (1.0, 2.0, "round_dispatch"),
             (3.0, 9.0, "metrics_host"), (12.0, 13.0, "server_pass")]
    segs = hostclock.innermost(spans)
    assert segs == [(0.0, 1.0, "client_pass"), (1.0, 2.0, "round_dispatch"),
                    (2.0, 3.0, "client_pass"), (3.0, 9.0, "metrics_host"),
                    (9.0, 10.0, "client_pass"), (12.0, 13.0, "server_pass")]
    gaps = [(0.5, 1.5), (4.0, 6.0), (9.5, 12.5)]
    assert hostclock.overlay(gaps, segs) == pytest.approx({
        "client_pass": 0.5 + 0.5, "round_dispatch": 0.5,
        "metrics_host": 2.0, "server_pass": 0.5, None: 2.0})
    assert hostclock.clip(gaps, segs, ("metrics_host",)) == [(4.0, 6.0)]


def test_idle_queued_on_the_steps_fixture(capsys):
    """The fixture's round 7 runs from 1,000 to 2,000 us on the trace's
    clock; its ``fed_clock`` marks put the host's tick 5.0 s at 900."""
    from benchmark.lib import tracelib, tracesum
    from commefficient_tpu.telemetry.trace import clock_offset_us
    ctx = {"trace_dir": STEPS, "records": []}
    tr = tracesum.of(ctx)
    (_r, lo, hi), = tr["windows"]
    busy = tracelib._union(tracelib._clip(
        [(float(e["ts"]), float(e["ts"]) + float(e["dur"]))
         for e in tracesum.op_events(ctx)], lo, hi))
    idle = (hi - lo) - sum(b - a for a, b in busy)
    m = "MainThread"
    # the round loop waits in metrics_host from the first operation's
    # end on; the loader's thread stages the next batch all the while
    off = clock_offset_us(tr["events"])

    def t(us):
        return (us - off) / 1e6
    ctx["records"] = [{"kind": "round", "round": 7, "timeline": [
        ["client_pass", t(lo), t(hi), None, m],
        ["round_dispatch", t(lo + 1), t(lo + 5), 0, m],
        ["metrics_host", t(busy[0][1]), t(hi), 0, m],
        ["data.stage", t(lo), t(hi), None, "loader-stage"]]}]
    got = harness.load("metrics", "device.idle_queued_ms").read(ctx)
    before = busy[0][0] - lo
    assert got == pytest.approx((idle - before) / 1e3)
    out = capsys.readouterr().out
    assert "idle gaps by [loader-stage]'s innermost span, s: data.stage" \
        in out
    assert "by the round loop's [MainThread] innermost span" in out
    # no record, or none of this window: nothing to lay the gaps over
    ctx["records"] = []
    assert harness.load("metrics", "device.idle_queued_ms").read(ctx) is None


def test_setup_sums(monkeypatch, capsys):
    from commefficient_tpu.telemetry import core
    monkeypatch.setattr(core, "_SETUP_SPANS", [
        ["data_build", 72.0, 80.0], ["model_build", 81.0, 83.5],
        ["data_build", 150.0, 151.0]])
    m = "MainThread"
    warm = [_record(r, [["client_pass", 85.0 + 2 * r, 86.0 + 2 * r, None,
                         m]]) for r in range(3)]
    ctx = _ctx(warm + [_record(3, [])], first=3)
    ctx["rounds"] = [{"t_end": 86.5 + 2 * r} for r in range(4)]
    # process start at 70 (t_start 100 - set-up 30); warm-up 85 - 90.5
    assert harness.load("metrics", "entry.warmup_s").read(ctx) \
        == pytest.approx(5.5)
    # 30 - (8 + 2.5 + 5.5)
    assert harness.load("metrics", "entry.uncovered_s").read(ctx) \
        == pytest.approx(14.0)
    out = capsys.readouterr().out
    assert "cover 16.00, under none 14.00 (warm-up 15.00-20.50 s" in out
    assert "set-up, s from process start: " in out
    # a span that straddles the warm-up is counted once
    monkeypatch.setattr(core, "_SETUP_SPANS", [["model_build", 80.0, 88.0]])
    assert hostclock.setup_uncovered_s(ctx) == pytest.approx(30 - 10.5)


@pytest.mark.parametrize("cell", ["resnet9_fetchsgd_w1250",
                                  "gpt2_fetchsgd_w8"])
def test_traced_rehearsal_drives_the_resource_clocks_readers(cell):
    res, out = _run(["--workload", cell, "--seed", "57", "--seconds", "4",
                     "--trace", "1", "--rehearse"], 1)
    assert res["correct"] is True and res["metrics"] == {}
    for name in NEW:
        assert f"rehearsal: reader {name} ran" in out, name
    for line in ("stall: ", "longest round ", "round loop [MainThread], ms "
                 "a round over ", "process, a round over ", "idle gaps: ",
                 "telemetry.close: ", "set-up "):
        assert "\n" + line in out, line
