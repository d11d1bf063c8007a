"""The one span model (telemetry/core.py): a round record's
``timeline`` of ``[name, t0, t1, parent, thread]`` entries beside the
accumulated ``spans``, the shared clock with a profiler trace
(``fed_clock``), the set-up spans, and the trace reader repaired for
the TPU's ``Steps`` line."""

import os
import threading
import time

import pytest

from commefficient_tpu import telemetry
from commefficient_tpu.telemetry import (NULL_TELEMETRY, Telemetry, trace,
                                         validate_record)
from commefficient_tpu.telemetry.core import NULL_SPAN
from commefficient_tpu.telemetry.record import (READABLE_SCHEMA_VERSIONS,
                                                TIMELINE_CAP,
                                                make_round_record)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


class ListSink:
    def __init__(self):
        self.records = []

    def write(self, rec):
        self.records.append(rec)

    def close(self):
        pass


def _entry(rec, name, nth=0):
    hits = [(i, e) for i, e in enumerate(rec["timeline"]) if e[0] == name]
    return hits[nth]


def test_timeline_nesting_parent_and_self_time_on_two_threads():
    sink = ListSink()
    tel = Telemetry([sink])
    tel.begin_round(0)
    ready, go = threading.Event(), threading.Event()

    def producer():
        with tel.span("data.collate"):
            ready.set()
            go.wait(timeout=10)

    t = threading.Thread(target=producer, name="producer")
    with tel.span("client_pass"):
        with tel.span("h2d"):
            t.start()
            assert ready.wait(timeout=10)
        with tel.span("round_dispatch"):
            go.set()
            t.join(timeout=10)
        assert not t.is_alive()
    with tel.span("server_pass"):
        pass
    tel.set_round_bytes(0, 1.0, 2.0)
    tel.close()
    (rec,) = sink.records
    assert validate_record(rec) == []
    names = [e[0] for e in rec["timeline"]]
    assert sorted(names) == sorted(["client_pass", "h2d", "data.collate",
                                    "round_dispatch", "server_pass"])
    i_cp, cp = _entry(rec, "client_pass")
    assert cp[3] is None and cp[4] == "MainThread"
    for child in ("h2d", "round_dispatch"):
        _, e = _entry(rec, child)
        assert e[3] == i_cp and e[4] == "MainThread"
        assert cp[1] <= e[1] <= e[2] <= cp[2]
    # another thread's span has no parent here, and carries its name
    _, col = _entry(rec, "data.collate")
    assert col[3] is None and col[4] == "producer"
    assert _entry(rec, "server_pass")[1][3] is None
    # self time: the parent's duration less what its children cover
    kids = sum(e[2] - e[1] for e in rec["timeline"] if e[3] == i_cp)
    self_time = (cp[2] - cp[1]) - kids
    assert 0.0 <= self_time < cp[2] - cp[1]
    # the accumulated seconds are the timeline's
    for name in set(names):
        total = sum(e[2] - e[1] for e in rec["timeline"] if e[0] == name)
        assert rec["spans"][name] == pytest.approx(total, abs=1e-9)


def test_disabled_path_is_the_shared_noop_and_retains_nothing():
    tel = Telemetry()
    assert tel.begin_round(0) is None
    assert tel.span("client_pass") is NULL_SPAN is trace.NULL_PHASE
    with tel.span("a"):
        with tel.span("b"):
            pass
    tel.count("data.epoch_start")
    assert not tel._records and tel._current is None
    assert not hasattr(tel._open, "stack")      # no per-thread state
    assert NULL_TELEMETRY.span("x") is NULL_SPAN


def test_span_outside_a_round_records_nothing():
    sink = ListSink()
    tel = Telemetry([sink])
    assert tel.span("sampler") is NULL_SPAN      # no round open yet
    tel.close()
    assert sink.records == []


def test_timeline_cap_counts_what_it_drops():
    sink = ListSink()
    tel = Telemetry([sink])
    tel.begin_round(0)
    with tel.span("parent"):
        for _ in range(TIMELINE_CAP + 10):
            with tel.span("child"):
                pass
    tel.set_round_bytes(0, 0.0, 0.0)
    tel.close()
    (rec,) = sink.records
    assert len(rec["timeline"]) == TIMELINE_CAP
    assert rec["counters"]["timeline_dropped"] == 11
    assert validate_record(rec) == []
    # the seconds still accumulate for every span
    assert rec["spans"]["child"] > 0.0


def test_a_span_straddling_begin_round_is_no_parent_of_the_next_round():
    sink = ListSink()
    tel = Telemetry([sink])
    tel.begin_round(0)
    with tel.span("outer"):
        tel.begin_round(1)
        with tel.span("inner"):
            pass
    for r in (0, 1):
        tel.set_round_bytes(r, 0.0, 0.0)
    tel.close()
    r0, r1 = sink.records
    assert [e[0] for e in r0["timeline"]] == ["outer"]
    assert r1["timeline"][0][0] == "inner" and r1["timeline"][0][3] is None
    assert all(validate_record(r) == [] for r in (r0, r1))



def test_a_span_opened_while_a_round_closes_lands_on_the_next(monkeypatch):
    """``begin_round`` finishes the old record (memory statistics,
    emission) with the new one already current: a loader's producer
    thread that wakes just then (the pop preceded the call) records its
    ``data.collate`` on the new round, not nowhere."""
    from commefficient_tpu.telemetry import core
    sink = ListSink()
    tel = Telemetry([sink])

    def meanwhile():
        with tel.span("data.collate"):
            pass
        return 0

    monkeypatch.setattr(core, "hbm_peak_bytes", meanwhile)
    tel.begin_round(0)
    tel.set_round_bytes(0, 0.0, 0.0)
    tel.begin_round(1)
    tel.set_round_bytes(1, 0.0, 0.0)
    tel.close()
    recs = {r["round"]: r for r in sink.records}
    assert "data.collate" not in recs[0]["spans"]
    assert [e[0] for e in recs[1]["timeline"]].count("data.collate") == 1

@pytest.mark.parametrize("version", READABLE_SCHEMA_VERSIONS)
def test_every_schema_version_still_validates(version):
    rec = make_round_record(3)
    rec["schema"] = version
    assert rec["timeline"] == [] and "hbm_reserved_peak_bytes" in rec
    assert validate_record(rec) == []
    if version < 9:     # an older writer's record has no resource clock
        del rec["cpu"], rec["timeline_cpu"], rec["stall"]
        assert validate_record(rec) == []
    if version < 8:     # nor, before that, either of these
        del rec["timeline"], rec["hbm_reserved_peak_bytes"]
        assert validate_record(rec) == []


def test_a_v7_record_with_a_causal_stamp_still_validates():
    """An old ledger's round record: schema 7, no timeline, and the
    ``causal`` key that nothing writes or reads any more, whatever it
    holds."""
    rec = make_round_record(3)
    rec["schema"] = 7
    del rec["timeline"], rec["hbm_reserved_peak_bytes"]
    rec["causal"] = {
        "trace": "jsolo.r3", "job": None, "round": 3, "wall": 0.5,
        "spans": [{"id": "jsolo.r3.s0", "parent": None, "name": "round",
                   "bucket": "host_other", "b": 0.0, "e": 0.5}]}
    assert validate_record(rec) == []
    rec["causal"] = "torn"
    assert validate_record(rec) == []


@pytest.mark.parametrize("timeline,problem", [
    ("x", "not a list"),
    ([["a", 0.0, 1.0, None]], "not [name"),
    ([["a", "0", 1.0, None, "t"]], "non-numeric"),
    ([["a", 0.0, 1.0, 0, "t"]], "earlier index"),
    ([["a", 0.0, 1.0, None, 7]], "not a string"),
])
def test_malformed_timelines_are_reported(timeline, problem):
    rec = make_round_record(0)
    rec["timeline"] = timeline
    assert any(problem in p for p in validate_record(rec))
    rec["timeline"] = [["a", 0.0, None, None, "t"],      # still open
                       ["b", 0.1, 0.2, 0, "t"]]
    rec["timeline_cpu"] = [None, 0.05]
    assert validate_record(rec) == []


@pytest.mark.parametrize("extra", [
    pytest.param(["--num_devices", "1"], id="one-device"),
    pytest.param(["--num_devices", "4"], id="mesh4"),
    pytest.param(["--num_devices", "1", "--async_buffer_size", "2"],
                 id="async-buffer"),
])
def test_top_level_spans_and_the_uncovered_gap_partition_the_round(
        extra, tmp_path, monkeypatch):
    """A real 4-round CV run. On the round loop's thread the parentless
    spans of a record follow one another between its ``begin_round`` and
    the next, so their durations plus the gaps between them are the
    round's period: what no top-level span covers is
    ``runtime.uncovered_ms``, and nothing is counted twice."""
    import json

    from commefficient_tpu.asyncfed.driver import AsyncRoundDriver
    from commefficient_tpu.telemetry import clock
    from commefficient_tpu.train import cv_train

    bounds, folds = [], []
    real_begin, real_close = Telemetry.begin_round, Telemetry.close
    real_step = AsyncRoundDriver.step

    def begin_round(self, index):
        if self.enabled:
            bounds.append(clock.tick())
        return real_begin(self, index)

    def close(self):
        if self.enabled and len(bounds) == 4:
            bounds.append(clock.tick())
        return real_close(self)

    def step(self, batch):
        t0 = clock.tick()
        out = real_step(self, batch)
        folds.append((t0, clock.tick()))
        return out

    monkeypatch.setattr(Telemetry, "begin_round", begin_round)
    monkeypatch.setattr(Telemetry, "close", close)
    monkeypatch.setattr(AsyncRoundDriver, "step", step)
    ledger = str(tmp_path / "run.jsonl")
    cv_train.main([
        "--test", "--dataset_name", "Synthetic", "--mode", "sketch",
        "--error_type", "virtual", "--local_momentum", "0",
        "--virtual_momentum", "0.9", "--num_clients", "20",
        "--num_workers", "4", "--local_batch_size", "4",
        "--num_epochs", "4", "--lr_scale", "0.1", "--pivot_epoch", "1",
        "--seed", "5", "--ledger", ledger, *extra])
    with open(ledger) as f:
        records = [json.loads(line) for line in f]
    rounds = [r for r in records if r["kind"] == "round"]
    assert [r["round"] for r in rounds] == [0, 1, 2, 3]
    assert len(bounds) == 5
    assert len(folds) == (4 if "--async_buffer_size" in extra else 0)
    for rec, begin, end in zip(rounds, bounds, bounds[1:]):
        assert validate_record(rec) == []
        top = [e for e in rec["timeline"] if e[3] is None
               and e[4] == "MainThread" and e[0] != "telemetry.close"]
        names = [e[0] for e in top]
        assert names[:2] == ["client_pass", "server_pass"]
        # the recorder finishes the previous record inside the client
        # pass, after its dispatch: a span with no parent that covers
        # nothing of its own
        closes = [e for e in rec["timeline"] if e[0] == "telemetry.close"]
        assert len(closes) == (0 if rec["round"] == 0 else 1)
        for e in closes:
            _, disp = _entry(rec, "round_dispatch")
            assert e[3] is None and e[4] == "MainThread"
            assert disp[2] <= e[1] <= e[2] <= top[0][2]
        # then the validation pass's wait and the next batch's fetch
        assert set(names[2:]) <= {"metrics_host", "sampler"}
        covered, gaps, at = 0.0, 0.0, begin
        for _, t0, t1, _, _ in top:
            assert at <= t0 <= t1       # in order, none overlapping
            gaps += t0 - at
            covered += t1 - t0
            at = t1
        assert at <= end
        gaps += end - at
        assert covered + gaps == pytest.approx(end - begin, abs=1e-9)
        assert covered > 0.0 and gaps < end - begin
        # the previous server update's support is settled inside the
        # client pass, after its dispatch and before the wait for the
        # round's metrics (PR 30): no child of server_pass, and never
        # a top-level span (``names`` above), so the device's wait for
        # the next dispatch is not behind it
        i_cp, _ = _entry(rec, "client_pass")
        notes = [e for e in rec["timeline"] if e[0] == "note_update"]
        assert len(notes) == (0 if rec["round"] == 0 else 1)
        for note in notes:
            assert note[3] == i_cp
            _, disp = _entry(rec, "round_dispatch")
            _, wait = _entry(rec, "metrics_host")
            assert disp[3] == wait[3] == i_cp
            assert disp[2] <= note[1] <= note[2] <= wait[1]
        assert rec["counters"].get("account.deferred", 0) == len(notes)
        assert "account.inline" not in rec["counters"]
        if folds:
            # the one span the buffered path adds, under client_pass,
            # over the cohort's issue and the arrivals' dequeue
            i_cp, _ = _entry(rec, "client_pass")
            _, fold = _entry(rec, "async_fold")
            assert fold[3] == i_cp
            s0, s1 = folds[rec["round"]]
            assert fold[1] <= s0 <= s1 <= fold[2]
        else:
            assert "async_fold" not in rec["spans"]


def test_fed_clock_places_a_span_on_its_own_annotation(tmp_path):
    """A CPU profiler trace: the timeline entry of every span that
    wrote a ``fed_phase::`` annotation (the outermost open on its
    thread: one level, so that no stretch of the trace lies under two
    phases of a thread), moved by the ``fed_clock`` offset, lies within
    0.1 ms of that annotation."""
    import jax
    import jax.numpy as jnp
    sink = ListSink()
    tel = Telemetry([sink])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        trace.set_tracing(True)
        for r in range(3):
            tel.begin_round(r)
            trace.begin_round_marker(r)
            with tel.span("client_pass"):
                with tel.span("round_dispatch"):
                    jnp.ones((64, 64)).sum().block_until_ready()
                tel.close_round()       # where FedModel calls it
            tel.set_round_bytes(r, 0.0, 0.0)
        trace.set_tracing(False)
    finally:
        jax.profiler.stop_trace()
    tel.close()
    events = trace.load_trace_events(str(tmp_path))
    clocks = [e for e in events
              if e.get("name", "").startswith("fed_clock::")]
    assert len(clocks) == 2 and trace.clock_offset_us(events) is not None
    moved = trace.host_timeline(events, sink.records)
    # the recorder's own span, inside rounds 1 and 2's client pass,
    # writes no annotation either
    assert [s["round"] for s in moved
            if s["name"] == "telemetry.close"] == [1, 2]
    moved = [s for s in moved if s["name"] != "telemetry.close"]
    assert len(moved) == 6 and [s["round"] for s in moved] == [
        0, 0, 1, 1, 2, 2]
    anns = sorted((float(e["ts"]), float(e["ts"]) + float(e["dur"]),
                   e["name"]) for e in events
                  if e.get("name", "").startswith("fed_phase::"))
    # the nested round_dispatch wrote none: it is placed by the clock
    assert len(anns) == 3
    tops = [s for s in moved if s["parent"] is None]
    assert [s["name"] for s in moved if s["parent"] is not None] == [
        "round_dispatch"] * 3
    for span, (ts, end, name) in zip(tops, anns):
        assert name == "fed_phase::" + span["name"]
        assert abs(span["ts"] - ts) < 100.0        # microseconds
        assert abs(span["end"] - end) < 100.0
    # a trace without the marks places nothing
    assert trace.host_timeline(
        [e for e in events
         if not e.get("name", "").startswith("fed_clock::")],
        sink.records) == []


def test_profile_without_a_ledger_keeps_its_phases(tmp_path):
    """A disabled Telemetry inside a trace window still writes the
    ``fed_phase::`` annotation (``--profile`` alone)."""
    import jax
    tel = Telemetry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        trace.set_tracing(True)
        with tel.span("h2d"):
            pass
        trace.set_tracing(False)
    finally:
        jax.profiler.stop_trace()
    assert tel.span("h2d") is NULL_SPAN
    names = {e.get("name") for e in trace.load_trace_events(str(tmp_path))}
    assert "fed_phase::h2d" in names


def test_one_annotation_level_a_thread(tmp_path):
    """Only the outermost span open on the thread that opened the
    window is annotated, with or without a record: not the spans
    nested in it, not another thread's."""
    import threading

    import jax
    sink = ListSink()
    tel, off = Telemetry([sink]), Telemetry()
    tel.begin_round(0)

    def producer():
        with tel.span("data.collate"):
            with tel.span("data.index"):
                pass

    jax.profiler.start_trace(str(tmp_path))
    try:
        trace.set_tracing(True)
        with tel.span("sampler"):
            with tel.span("data.pop_wait"):
                t = threading.Thread(target=producer, name="producer")
                t.start()
                t.join()
        with off.span("client_pass"):
            with off.span("h2d"):
                pass
        with tel.span("server_pass"):       # depth is back at none
            pass
        trace.set_tracing(False)
    finally:
        jax.profiler.stop_trace()
    names = sorted(e["name"] for e in trace.load_trace_events(str(tmp_path))
                   if e.get("name", "").startswith("fed_phase::"))
    assert names == ["fed_phase::client_pass", "fed_phase::sampler",
                     "fed_phase::server_pass"]
    tel.set_round_bytes(0, 0.0, 0.0)
    tel.close()
    # the timeline has them all
    assert sorted(e[0] for e in sink.records[0]["timeline"]) == [
        "data.collate", "data.index", "data.pop_wait", "sampler",
        "server_pass"]


def test_setup_spans_start_the_compile_count():
    import jax
    import jax.numpy as jnp
    from commefficient_tpu.telemetry import core
    before = len(telemetry.setup_spans())
    c0 = dict(core._COMPILE)
    with telemetry.setup_span("data_build"):
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    spans = telemetry.setup_spans()
    assert len(spans) == before + 1
    name, t0, t1 = spans[-1]
    assert name == "data_build" and t1 > t0
    c1 = core._COMPILE
    assert set(c1) == {"events", "secs", "cache_hits"}
    # the listener was registered by the span itself: the jit inside it
    # was counted
    assert c1["events"] > c0["events"] and c1["secs"] > c0["secs"]

    @telemetry.setup_span("model_build")
    def build():
        return 5

    assert build() == 5
    assert telemetry.setup_spans()[-1][0] == "model_build"


def test_first_record_carries_what_compiled_before_it():
    sink = ListSink()
    tel = Telemetry([sink])
    for r in range(2):
        tel.begin_round(r)
        tel.set_round_bytes(r, 0.0, 0.0)
    tel.close()
    first, second = sink.records
    for key in ("compile_events_before", "compile_secs_before",
                "compile_cache_hits_before"):
        assert key in first["counters"] and key not in second["counters"]
    assert "compile_cache_hits" in second["counters"]
    assert first["hbm_reserved_peak_bytes"] is None     # a CPU backend


# --- the resource clock (schema 9) --------------------------------------


def _spin(seconds):
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass


def _closed(tel, sink, last):
    """Close ``tel`` (rounds 0..last get their bytes first) and return
    the round records by index."""
    for r in range(last + 1):
        tel.set_round_bytes(r, 0.0, 0.0)
    tel.close()
    assert all(validate_record(r) == [] for r in sink.records)
    return {r["round"]: r for r in sink.records if r["kind"] == "round"}


@pytest.mark.parametrize("work", ["sleeps", "spins"])
def test_a_span_that_sleeps_has_no_cpu_and_one_that_spins_has_its_wall(
        work):
    sink = ListSink()
    tel = Telemetry([sink])
    tel.begin_round(0)
    with tel.span("client_pass"):
        with tel.span("metrics_host"):
            time.sleep(0.1) if work == "sleeps" else _spin(0.1)
    rec = _closed(tel, sink, 0)[0]
    wall, cpu = rec["spans"]["metrics_host"], rec["cpu"]["metrics_host"]
    assert wall >= 0.1
    if work == "sleeps":
        assert cpu < 0.02           # waited: its thread did not run
    else:
        assert 0.1 <= cpu <= wall + 1e-3
    # the parent's CPU holds the child's, as its wall does
    assert rec["cpu"]["client_pass"] >= cpu
    assert set(rec["cpu"]) == set(rec["spans"])


def test_timeline_entries_keep_five_fields_and_cpu_lies_beside_them():
    sink = ListSink()
    tel = Telemetry([sink])
    rec = tel.begin_round(0)
    with tel.span("client_pass"):
        with tel.span("h2d"):
            # an open span has no CPU reading yet, as it has no end
            assert rec["timeline_cpu"] == [None, None]
            assert validate_record(rec) == []
        for _ in range(TIMELINE_CAP + 5):
            with tel.span("child"):
                pass
    rec = _closed(tel, sink, 0)[0]
    assert all(len(e) == 5 for e in rec["timeline"])
    assert len(rec["timeline_cpu"]) == len(rec["timeline"]) == TIMELINE_CAP
    assert all(isinstance(c, float) and c >= 0.0
               for c in rec["timeline_cpu"])
    # past the cap the seconds still accumulate by name, both clocks
    assert rec["cpu"]["child"] >= sum(
        c for e, c in zip(rec["timeline"], rec["timeline_cpu"])
        if e[0] == "child")


def test_a_span_on_a_second_thread_charges_that_thread():
    sink = ListSink()
    tel = Telemetry([sink])
    tel.begin_round(0)

    def producer():
        with tel.span("data.index"):
            _spin(0.1)

    t = threading.Thread(target=producer, name="loader-stage")
    with tel.span("sampler"):           # the consumer only waits
        t.start()
        t.join(timeout=30)
    rec = _closed(tel, sink, 0)[0]
    by = {e[0]: (e, c) for e, c in zip(rec["timeline"],
                                       rec["timeline_cpu"])}
    assert by["data.index"][0][4] == "loader-stage"
    assert by["data.index"][1] >= 0.1
    assert by["sampler"][0][2] - by["sampler"][0][1] >= 0.1
    assert by["sampler"][1] < 0.05


def test_host_counters_are_deltas_and_follow_a_spinning_round():
    import resource
    sink = ListSink()
    tel = Telemetry([sink])
    for r in range(3):
        tel.begin_round(r)
        with tel.span("client_pass"):
            tel.close_round()           # where FedModel calls it
            if r == 1:
                _spin(0.25)
    recs = _closed(tel, sink, 2)
    keys = {"host.cpu_user_s", "host.cpu_sys_s", "host.minflt",
            "host.majflt", "host.nvcsw", "host.nivcsw", "host.gc_s",
            "host.gc_runs"}
    for r, rec in recs.items():
        c = rec["counters"]
        assert keys <= set(c)
        assert all(c[k] >= 0 for k in c if k.startswith("host."))
        # what the machine is like: on the run's first record only
        assert ("host.cpus" in c) == ("host.threads" in c) == (r == 0)
        assert rec["host_rss_peak_bytes"] <= resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024
        assert rec["host_rss_peak_bytes"] > 0
    first = recs[0]["counters"]
    assert first["host.cpus"] == len(os.sched_getaffinity(0))
    assert first["host.threads"] >= 2       # this thread and the watchdog
    # a record runs from its predecessor's finishing to its own, which
    # FedModel places after the next round's dispatch: round 1's spin
    # lies on record 1, before record 1 was finished in round 2
    # (the spin reads the thread's clock in a loop: part of it is the
    # kernel's time)
    cpu = [recs[r]["counters"]["host.cpu_user_s"]
           + recs[r]["counters"]["host.cpu_sys_s"] for r in range(3)]
    assert cpu[1] >= 0.2 > max(cpu[0], cpu[2])
    assert recs[1]["counters"]["host.cpu_user_s"] > 0.05


def test_collections_are_timed():
    import gc
    sink = ListSink()
    tel = Telemetry([sink])
    tel.begin_round(0)
    gc.collect()
    gc.collect()
    rec = _closed(tel, sink, 0)[0]
    assert rec["counters"]["host.gc_runs"] >= 2
    assert rec["counters"]["host.gc_s"] > 0.0


@pytest.fixture
def quick_stalls(monkeypatch):
    from commefficient_tpu.telemetry import core
    monkeypatch.setattr(core, "STALL_MIN_S", 0.15)
    monkeypatch.setattr(core, "STALL_FACTOR", 3.0)
    return core


@pytest.mark.parametrize("periods,limit", [
    ([], 1.0), ([0.117] * 32, 1.0), ([0.8] * 31 + [4.5], 6.4),
    ([0.2, 0.2, 9.0], 1.6)])
def test_the_stall_limit_is_eight_medians_and_a_second_at_least(periods,
                                                                limit):
    from commefficient_tpu.telemetry import core
    assert core.stall_limit(periods) == pytest.approx(limit)
    assert (core.STALL_MIN_S, core.STALL_FACTOR, core.STALL_PERIODS,
            core.STALL_FRAMES, core.STALL_BYTES) == (1.0, 8.0, 32, 8, 4096)


def test_a_round_held_open_carries_every_threads_stack(quick_stalls):
    sink = ListSink()
    tel = Telemetry([sink])
    release = threading.Event()

    def held_by_the_loader():
        release.wait(timeout=30)

    t = threading.Thread(target=held_by_the_loader, name="loader-stage")
    t.start()

    def held_by_the_round_loop():
        time.sleep(0.8)

    try:
        for r in range(4):
            tel.begin_round(r)
            with tel.span("client_pass"):
                tel.close_round()
                if r == 2:
                    held_by_the_round_loop()
                else:
                    time.sleep(0.005)
    finally:
        release.set()
        t.join(timeout=30)
    recs = _closed(tel, sink, 3)
    for r in (0, 1, 3):             # a short round: neither
        assert recs[r]["stall"] is None
        assert "stall.captured" not in recs[r]["counters"]
    stall = recs[2]["stall"]
    assert recs[2]["counters"]["stall.captured"] == 1
    # taken once, at the limit (3 x the 5 ms median, 150 ms at least),
    # while the round was still open
    assert 0.15 <= stall["after_s"] < 0.8
    assert {"MainThread", "loader-stage"} <= set(stall["threads"])
    assert "telemetry-stall" not in stall["threads"]
    assert any("held_by_the_round_loop" in f
               for f in stall["threads"]["MainThread"])
    assert any("held_by_the_loader" in f
               for f in stall["threads"]["loader-stage"])
    assert all(len(f) <= quick_stalls.STALL_FRAMES
               for f in stall["threads"].values())


def test_the_watchdog_and_the_round_loop_lose_no_update(monkeypatch):
    """More threads than cores open spans while rounds of a few
    milliseconds turn over and the watchdog fires on every fifth: each
    record is whole, in order, and carries a stall only together with
    its counter."""
    import sys
    from commefficient_tpu.telemetry import core
    monkeypatch.setattr(core, "STALL_MIN_S", 0.004)
    monkeypatch.setattr(core, "STALL_FACTOR", 2.0)
    sink = ListSink()
    tel = Telemetry([sink])
    stop = threading.Event()

    def producer():
        while not stop.is_set():
            with tel.span("data.index"):
                pass

    workers = [threading.Thread(target=producer, name=f"producer-{i}")
               for i in range(2 * (os.cpu_count() or 4))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    deadline = time.perf_counter() + 20.0
    rounds = 0
    try:
        for t in workers:
            t.start()
        while rounds < 150 and time.perf_counter() < deadline:
            tel.begin_round(rounds)
            with tel.span("client_pass"):
                tel.close_round()
                if rounds % 5 == 4:
                    time.sleep(0.03)
            tel.set_round_bytes(rounds, 0.0, 0.0)
            rounds += 1
    finally:
        stop.set()
        for t in workers:
            t.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in workers)
    watchdog = [t for t in threading.enumerate()
                if t.name == "telemetry-stall"]
    assert watchdog                 # no exception has ended it
    tel.close()
    assert rounds == 150
    assert [r["round"] for r in sink.records] == list(range(rounds))
    stalls = 0
    for rec in sink.records:
        assert validate_record(rec) == []
        assert len(rec["timeline_cpu"]) == len(rec["timeline"])
        assert (rec["stall"] is not None) == (
            rec["counters"].get("stall.captured") == 1)
        if rec["stall"] is not None:
            stalls += 1
            assert rec["stall"]["after_s"] >= 0.004
            assert "MainThread" in rec["stall"]["threads"]
    assert stalls >= 5              # of the 30 rounds held open


def test_stacks_are_cut_to_eight_frames_and_four_kilobytes(monkeypatch):
    from commefficient_tpu.telemetry import core

    def deep(n):
        return core.thread_stacks() if n == 0 else deep(n - 1)

    stacks = deep(40)
    mine = stacks[threading.current_thread().name]
    assert len(mine) == core.STALL_FRAMES
    assert "thread_stacks" in mine[0] and mine[1].endswith(" deep")
    monkeypatch.setattr(core, "STALL_BYTES", 60)
    stacks = deep(40)
    assert sum(len(f) for fs in stacks.values() for f in fs) <= 60


def test_with_no_sink_nothing_of_the_resource_clock_runs(monkeypatch):
    import gc
    from commefficient_tpu.telemetry import core
    monkeypatch.setattr(gc, "callbacks", [
        cb for cb in gc.callbacks if cb is not core._on_gc])
    before = {t.ident for t in threading.enumerate()}
    tel = Telemetry()
    assert tel.begin_round(0) is None
    assert tel.span("client_pass") is NULL_SPAN
    assert tel.close_round() is None
    tel.begin_round(1)
    tel.close()
    assert core._on_gc not in gc.callbacks       # no callback registered
    assert tel._armed is None and tel._watch_stop is None   # no watchdog
    assert not tel._periods and tel._host_mark is None
    assert {t.ident for t in threading.enumerate()} <= before
    # with the first sink both appear, the watchdog with the first round
    tel = Telemetry([ListSink()])
    assert core._on_gc in gc.callbacks and tel._watch_stop is None
    tel.begin_round(0)
    assert "telemetry-stall" in {t.name for t in threading.enumerate()}
    stop = tel._watch_stop
    tel.close()
    assert stop.is_set()


def test_telemetry_close_lands_on_the_successor_with_no_parent():
    sink = ListSink()
    tel = Telemetry([sink])
    order = []
    sink.write = lambda rec: order.append(rec["round"])
    kept = {}
    for r in range(4):
        kept[r] = tel.begin_round(r)
        tel.set_round_bytes(r, 0.0, 0.0)
        # the previous record is swapped out, not finished: nothing of
        # the recorder's own runs before the round's dispatch
        assert order == list(range(max(r - 1, 0)))
        with tel.span("client_pass"):
            with tel.span("round_dispatch"):
                pass
            if r != 2:
                tel.close_round()       # FedModel, after its dispatch
                tel.close_round()       # a second call finds nothing
        if r != 2:
            assert order == list(range(r))
    # round 2 never called it: begin_round(3) finished record 1 at once,
    # under a span on record 2, and emission order is the rounds'
    tel.close()
    assert order == [0, 1, 2, 3]
    for r in (1, 2, 3):
        closes = [e for e in kept[r]["timeline"]
                  if e[0] == "telemetry.close"]
        assert len(closes) == 1 and closes[0][3] is None
        assert closes[0][4] == "MainThread"
        assert kept[r]["spans"]["telemetry.close"] > 0.0
    assert "telemetry.close" not in kept[0]["spans"]
    # inside client_pass in time, yet not its child
    i, cp = _entry(kept[1], "client_pass")
    _, close = _entry(kept[1], "telemetry.close")
    assert cp[1] <= close[1] <= close[2] <= cp[2]
    # record 2's lies outside any of its spans: begin_round(3) ran it
    _, cp = _entry(kept[2], "client_pass")
    _, close = _entry(kept[2], "telemetry.close")
    assert close[1] >= cp[2]


@pytest.mark.parametrize("key,value,problem", [
    ("cpu", {"a": "1"}, "cpu is not"),
    ("timeline_cpu", [0.1, 0.2], "not the timeline's length"),
    ("timeline_cpu", ["x"], "not a list of seconds"),
    ("stall", {"after_s": 1.0, "threads": {"t": "frame"}}, "stall is not"),
])
def test_malformed_resource_clock_keys_are_reported(key, value, problem):
    rec = make_round_record(0)
    rec["timeline"] = [["a", 0.0, 1.0, None, "t"]]
    rec["timeline_cpu"] = [0.5]
    rec["stall"] = {"after_s": 1.2, "threads": {"t": ["f.py:1 g"]}}
    assert validate_record(rec) == []
    rec[key] = value
    assert any(problem in p for p in validate_record(rec))


# --- the trace reader on a TPU-shaped trace ----------------------------


def test_steps_line_is_neither_operations_nor_round_windows():
    events = trace.load_trace_events(
        os.path.join(FIXTURES, "steps.trace.json.gz"))
    lanes = trace.lane_devices(events)
    assert set(lanes.values()) == {"TPU:0"} and (2, 20) not in lanes
    # windows are the host's annotations, not the device's copies
    assert trace.round_windows(events) == [(7, 1000.0, 2000.0),
                                           (8, 2000.0, 3000.0)]
    buckets = trace.attribute_rounds(events)
    # round 7: ops 1100-1400 and 1420-1450 busy; with the Steps line
    # read as operations it would be 350 us and the window 350 us
    assert buckets[7]["window_s"] == pytest.approx(1e-3)
    assert buckets[7]["busy_s"] == pytest.approx(330e-6)
    assert buckets[8]["busy_s"] == pytest.approx(250e-6)
    assert buckets[7]["host_gap_s"] == pytest.approx(670e-6)
    assert trace.clock_offset_us(events) == pytest.approx(900 - 5e6)
    rec = {"round": 7, "timeline": [["client_pass", 5.00011, 5.00061,
                                     None, "MainThread"]]}
    (span,) = trace.host_timeline(events, [rec])
    assert span["ts"] == pytest.approx(1010.0)
    assert span["end"] == pytest.approx(1510.0)
