"""The one span model (telemetry/core.py): a round record's
``timeline`` of ``[name, t0, t1, parent, thread]`` entries beside the
accumulated ``spans``, the shared clock with a profiler trace
(``fed_clock``), the set-up spans, and the trace reader repaired for
the TPU's ``Steps`` line."""

import os
import threading

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

    monkeypatch.setattr(core, "host_rss_peak_bytes", meanwhile)
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
    if version < 8:     # an older writer's record has neither key
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
        top = [e for e in rec["timeline"]
               if e[3] is None and e[4] == "MainThread"]
        names = [e[0] for e in top]
        assert names[:2] == ["client_pass", "server_pass"]
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
