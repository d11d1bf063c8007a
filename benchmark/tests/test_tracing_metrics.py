"""The readers of the program's spans, counters and scopes (PR 25):
each on records and a trace made by hand, each on what a program
without them gives (nothing, and no error), and all of them driven by a
``--rehearse`` run on 1 and 4 virtual devices."""

import os

import pytest

from conftest import ROOT
from benchmark import run as harness
from benchmark.lib import scopes, timeline
from test_rehearsal import _run

NEW = {
    "host": ["data.sample_ms", "data.index_ms", "data.pop_wait_ms",
             "data.epoch_restart_ms", "runtime.account_ms",
             "runtime.uncovered_ms", "entry.data_s", "entry.model_s",
             "entry.compile_s"],
    "device": ["round.fwdbwd_ms", "round.compress_ms", "round.recover_ms",
               "round.head_ms"],
}
STEPS = os.path.join(ROOT, "tests", "fixtures", "steps.trace.json.gz")


def _record(r, timeline_, counters=None, spans=None):
    acc = {}
    for name, t0, t1, _p, _t in timeline_:
        acc[name] = acc.get(name, 0.0) + t1 - t0
    return {"kind": "round", "round": r, "spans": spans or acc,
            "counters": counters or {}, "timeline": timeline_}


def _ctx(records, first=0, first_traced=None, **extra):
    n = len(records)
    win = {"first": first, "last": n, "t_start": 100.0,
           "first_traced": n + 1 if first_traced is None else first_traced}
    return dict({"records": records, "window": win, "setup_s": 30.0,
                 "rounds": []}, **extra)


def _round(r, t, epoch_start=False):
    """One round of 100 ms from ``t``: client pass 40 ms (children 30),
    server pass 10 ms, sampler 45 ms (children 44), 5 ms uncovered."""
    m = "MainThread"
    tl = [["client_pass", t, t + .040, None, m],
          ["h2d", t + .001, t + .011, 0, m],
          ["metrics_host", t + .012, t + .032, 0, m],
          ["server_pass", t + .041, t + .051, None, m],
          ["note_update", t + .045, t + .050, 3, m],
          ["sampler", t + .053, t + .098, None, m],
          ["data.index", t + .053, t + .063, 5, m],
          ["data.pop_wait", t + .063, t + .097, 5, m],
          ["data.collate", t + .010, t + .030, None, "producer"]]
    return _record(r, tl, {"data.epoch_start": 1} if epoch_start else {})


def test_host_readers_on_handmade_records():
    recs = [_round(r, 10.0 + 0.1 * r, epoch_start=(r == 2))
            for r in range(6)]
    ctx = _ctx(recs)
    assert [r["round"] for r in timeline.untraced_records(ctx)] == list(
        range(6))
    assert timeline.span_mean_ms(ctx, ("data.index",)) == pytest.approx(10)
    assert timeline.span_mean_ms(ctx, ("data.index", "data.collate")) \
        == pytest.approx(30)
    assert timeline.span_mean_ms(ctx, ("data.sample",)) is None
    # the period is client_pass to client_pass; the last record has no
    # successor and is left out
    assert timeline.uncovered_ms(ctx) == pytest.approx(5.0)
    total, kids = timeline.children_ms(ctx, "sampler")
    assert total == pytest.approx(45.0)
    assert kids == pytest.approx({"data.index": 10.0, "data.pop_wait": 34.0})
    assert [r["round"] for r in timeline.epoch_start_records(ctx)] == [2]
    timeline.loader_table(ctx)
    for name, want in [("data.index_ms", 30.0), ("data.pop_wait_ms", 34.0),
                       ("data.epoch_restart_ms", 45.0),
                       ("runtime.account_ms", 5.0)]:
        assert harness.load("metrics", name).read(ctx) == pytest.approx(want)
    assert harness.load("metrics", "data.sample_ms").read(ctx) is None


def test_host_readers_leave_the_traced_rounds_out():
    recs = [_round(r, 10.0 + 0.1 * r) for r in range(8)]
    ctx = _ctx(recs, first=2, first_traced=6)
    # record 5 holds the fetch of batch 6, made under the profiler
    assert [r["round"] for r in timeline.untraced_records(ctx)] == [2, 3, 4]
    ctx = _ctx(recs, first=2)
    ctx["window"].pop("first_traced")          # an untraced run
    assert timeline.untraced_records(ctx) == []
    assert timeline.uncovered_ms(ctx) is None


def test_a_program_without_the_spans_gives_nothing():
    """The parent of the PR that added them: records of schema 7, no
    timeline, no loader spans, no compile counters before round 0."""
    recs = [{"kind": "round", "round": r, "spans": {"sampler": 0.1,
             "h2d": 0.01}, "counters": {"compile_events": 0}}
            for r in range(5)]
    ctx = _ctx(recs, first=3, trace_dir=STEPS)
    for name in NEW["host"]:
        if name in ("entry.data_s", "entry.model_s"):
            continue       # these ask the program itself, which has them
        assert harness.load("metrics", name).read(ctx) is None, name
    assert timeline.children_ms(ctx, "sampler") == (None, {})
    assert timeline.loader_table(ctx) is None


def test_setup_readers(monkeypatch):
    from commefficient_tpu.telemetry import core
    monkeypatch.setattr(core, "_SETUP_SPANS", [
        ["data_build", 72.0, 80.0], ["model_build", 81.0, 83.0],
        ["model_build", 83.0, 83.5], ["data_build", 150.0, 151.0]])
    warm = [_record(r, [], {"compile_secs": 2.0, "compile_events": 10,
                            "compile_cache_hits": 3}) for r in range(3)]
    warm[0]["counters"].update(compile_secs_before=1.5,
                               compile_events_before=7,
                               compile_cache_hits_before=1)
    later = _record(3, [], {"compile_secs": 0.0})
    ctx = _ctx(warm + [later], first=3)
    ctx["rounds"] = [{"t_end": 90.0 + i} for i in range(4)]
    # spans that ended after the window opened (t_start 100) are not
    # set-up
    assert timeline.setup_seconds(ctx, "data_build") == pytest.approx(8.0)
    assert timeline.setup_seconds(ctx, "model_build") == pytest.approx(2.5)
    assert timeline.setup_seconds(ctx, "nothing") is None
    assert timeline.setup_compile_seconds(ctx) == pytest.approx(7.5)
    timeline.setup_table(ctx)
    assert harness.load("metrics", "entry.compile_s").read(ctx) \
        == pytest.approx(7.5)


def test_scope_readers_on_the_steps_fixture(capsys):
    ctx = {"trace_dir": STEPS}
    found = scopes.scope_seconds(ctx)
    # round 8 is the last traced round and is dropped by position. In
    # round 7 a helper fusion without a tf_op (50 us) runs between two
    # fwd_bwd operations: it belongs to no scope
    assert found == pytest.approx({"fwd_bwd": 150e-6, "lm_head": 100e-6,
                                   "compress": 100e-6, "select": 20e-6})
    assert scopes.scope_ms(ctx, ("estimates", "select", "resketch")) \
        == pytest.approx(0.020)
    assert scopes.scope_ms(ctx, ("apply",)) is None
    for name, want in [("round.fwdbwd_ms", 0.15), ("round.compress_ms", 0.1),
                       ("round.recover_ms", 0.02), ("round.head_ms", 0.1)]:
        assert harness.load("metrics", name).read(ctx) == pytest.approx(want)
    out = capsys.readouterr().out
    # the 60 us under no scope: the helper fusion and the 10 us of the
    # compiler's while loop that its body leaves open
    assert "under a scope 0.270, under none 0.060, of 0.330 busy" in out
    # the largest operations are named: by their own tf_op, the loop by
    # what runs inside it, the helper fusion by its long_name
    assert "operation fusion.2: 0.050 ms a round in 1 events, scopes " \
        "['fwd_bwd']; jit(client_round)/fwd_bwd/transpose(jvp())/" in out
    assert "operation while.3: 0.030 ms a round in 1 events, scopes None; " \
        "no tf_op; inside the first: jit(server_round)/select/while/body" \
        in out
    assert "operation convert_reduce_fusion: 0.050 ms a round in 1 events, " \
        "scopes None; no tf_op; %convert_reduce_fusion = u32[8,32,128]" in out
    # a scope is a path component, not a substring
    assert scopes._holds("select")("jit(f)/select_n:") is None
    assert scopes._holds("apply")("jit(apply_fn)/mul:") is None
    assert scopes._holds("lm_head")("a/transpose(jvp(lm_head))/dot:")


def test_scope_readers_find_nothing_without_scopes():
    mini = os.path.join(ROOT, "tests", "fixtures", "mini.trace.json.gz")
    ctx = {"trace_dir": mini}
    assert scopes.scope_seconds(ctx) == {}
    for name in NEW["device"]:
        assert harness.load("metrics", name).read(ctx) is None


def test_clock_check_on_the_steps_fixture(capsys):
    rec = {"kind": "round", "round": 7, "timeline": [
        ["client_pass", 5.00011, 5.00061, None, "MainThread"],
        ["round_dispatch", 5.00012, 5.00022, 0, "MainThread"]]}
    timeline.clock_check({"trace_dir": STEPS, "records": [rec]})
    out = capsys.readouterr().out
    assert "clock check: 2 traced spans" in out
    assert "|start| median 0.0 us max 0.0 us" in out


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("cell", ["resnet9_fetchsgd_w1250",
                                  "gpt2_fetchsgd_w8"])
def test_traced_rehearsal_drives_every_new_reader(cell, devices):
    manifest = harness.read_json(ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in manifest["per_layer"]
              if harness.applies(m, cell)}
    res, out = _run(["--workload", cell, "--seed", str(41 + devices),
                     "--seconds", "4", "--trace", "1", "--rehearse"],
                    devices)
    assert res["correct"] is True and res["metrics"] == {}
    assert res["device"]["count"] == devices
    for name in NEW["host"] + NEW["device"]:
        if name not in listed:
            assert f"reader {name} " not in out
            continue
        assert f"rehearsal: reader {name} " in out
        # the host readers have something to read on any backend (an
        # epoch starts inside a tiny preset's window; the rehearsal's
        # Python loader has no ring to pop, but since PR 28 the round
        # loop's wait for the hand-over is a ``data.pop_wait`` too);
        # a CPU trace names no scope
        ran = f"rehearsal: reader {name} ran" in out
        assert ran == (name not in NEW["device"]), name
    for line in ("client_pass ", "server_pass ", "sampler ", "clock check: ",
                 "set-up, s from process start: ", "set-up compile: "):
        assert "\n" + line in out, line
