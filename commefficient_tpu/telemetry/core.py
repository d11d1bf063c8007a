"""Round-ledger telemetry: spans, counters, and record lifecycle.

One ``Telemetry`` instance observes one run.  The hot-path contract:

- **disabled** (no sinks): ``begin_round`` is a single truthiness
  check, ``span()`` returns one shared no-op context manager,
  ``count()`` and ``close_round()`` return immediately — no per-round
  allocation, nothing retained: the whole disabled path is a handful
  of attribute loads per round.
- **enabled**: ``begin_round`` opens a round record; ``span(name)``
  accumulates wall-time into it; ``count(name)`` bumps a counter.
  Records are emitted to every sink in round order once they are (a)
  no longer the current round and (b) carry their uplink/downlink
  bytes (``set_round_bytes``, at the end of the round's client
  pass).  ``close()`` flushes whatever remains.

The span model (one for the whole program): besides the accumulated
seconds in ``rec["spans"][name]``, every span is one entry
``[name, t0, t1, parent, thread]`` of the record's ``timeline``
(``clock.tick`` seconds; ``parent`` = index, in the same timeline, of
the span that was open on the same thread when this one opened, else
None; ``thread`` = the thread's name). Entries are appended when the
span opens, so a thread's entries are in start order and a child can
name its parent; ``t1`` is filled in when it closes. A span's self
time is its duration minus what its children cover. While a profiler
trace window is open the outermost span of the round loop's thread
also is a ``fed_phase::<name>`` annotation (telemetry/trace.py), and
the window's ``fed_clock`` annotations put every timeline entry on
the device trace's clock.

The resource clock (schema 9), beside the wall clock and only while a
sink is attached: every span also reads its thread's CPU clock
(``cpu`` by name as ``spans`` is, ``timeline_cpu`` by the timeline's
index), so a span's wait is its wall minus its CPU minus its
children's; every record carries the process's counters over its life
(``counters["host.*"]``: one ``getrusage`` call where the record is
finished); and a round open longer than ``stall_limit`` gets, once,
every Python thread's stack as ``stall``, from a thread of the
recorder's own that ``begin_round`` only arms.

Code that is built before the run's ``Telemetry`` (the loaders) is
handed it afterwards (``loader.telemetry = model.telemetry``, as the
trainers do) or, failing that, finds it through ``current()``, which
answers only while exactly one ``FedModel``'s is live; set-up, which
precedes every round, is timed by ``setup_span`` into a process-level
list.

Round lifecycle (mirrors runtime/fed_model.py):

    begin_round(r)        # top of FedModel._call_train: swaps records
      span("h2d") ...     # client pass spans
      close_round()       # after the dispatch: finishes r-1 -> emit
      set_round_bytes(r)  # end of _call_train
      span("server") ...  # FedOptimizer.step (record still current)
    begin_round(r+1)      # r-1 not finished yet? at once. Swaps r out

Compile events come from ``jax.monitoring``'s duration listener
(registered once, process-wide); each record carries the delta of
compile count/seconds observed while it was current.
"""

from __future__ import annotations

import contextlib
import gc
import os
import resource
import statistics
import sys
import threading
import weakref
from collections import OrderedDict, deque

from commefficient_tpu.telemetry import clock, trace
from commefficient_tpu.telemetry.record import (TIMELINE_CAP,
                                                make_meta_record,
                                                make_round_record)

#: the shared, allocation-free no-op context manager
NULL_SPAN = trace.NULL_PHASE


class _Span:
    __slots__ = ("_tel", "_rec", "_name", "_top", "_entry", "_index",
                 "_cpu0", "_ann")

    def __init__(self, tel, rec, name, top=False):
        self._tel = tel
        self._rec = rec
        self._name = name
        self._top = top     # no parent, whatever is open on the thread

    def __enter__(self):
        tel, rec, name = self._tel, self._rec, self._name
        stack = tel._open_spans()
        # parent: the span open on this thread, if it is on the same
        # record (a span that straddles begin_round is not)
        parent = (stack[-1][1] if stack and stack[-1][0] is rec
                  and not self._top else None)
        self._ann = trace.phase(name)
        self._ann.__enter__()
        entry = self._entry = [name, clock.tick(), None, parent,
                               threading.current_thread().name]
        self._index = tel._enter_timeline(rec, entry)
        stack.append((rec, self._index))
        self._cpu0 = clock.thread_cpu()
        return self

    def __exit__(self, *exc):
        cpu = clock.thread_cpu() - self._cpu0
        entry, rec, name = self._entry, self._rec, self._name
        entry[2] = t1 = clock.tick()
        self._ann.__exit__(None, None, None)
        self._tel._open_spans().pop()
        spans = rec["spans"]
        spans[name] = spans.get(name, 0.0) + t1 - entry[1]
        rec["cpu"][name] = rec["cpu"].get(name, 0.0) + cpu
        if self._index is not None:
            rec["timeline_cpu"][self._index] = cpu
        return False


# --- process-wide compile-event accounting -----------------------------
# jax.monitoring listeners cannot be unregistered, so one module-level
# listener accumulates and each Telemetry snapshots deltas.
_COMPILE = {"events": 0, "secs": 0.0, "cache_hits": 0}
_LISTENER_STATE = {"done": False}


def compile_mark():
    """Snapshot of the process-wide compile accumulator; pair with
    ``compile_delta`` to attribute the compiles between two points to a
    specific cause (fed_model stamps first-dispatch compiles of a round
    variant onto the round record as ``vcompile_*:<key>`` counters).
    Starts the accumulator if nothing has yet: a mark with no listener
    behind it would read every delta as zero."""
    _ensure_compile_listener()
    return (_COMPILE["events"], _COMPILE["secs"])


def compile_delta(mark):
    """(events, secs) accumulated since ``mark``."""
    ev0, s0 = mark
    return (_COMPILE["events"] - ev0, _COMPILE["secs"] - s0)


def _ensure_compile_listener():
    if _LISTENER_STATE["done"]:
        return
    _LISTENER_STATE["done"] = True
    try:
        from jax import monitoring

        def _on_duration(event, secs, **kw):
            # trace, lower and backend-compile durations. Not every
            # event with "compile" in its name: on a persistent-cache
            # hit jax also reports compile_time_saved_sec, the time
            # that was NOT spent
            if event.startswith("/jax/core/compile/"):
                _COMPILE["events"] += 1
                _COMPILE["secs"] += float(secs)

        def _on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                _COMPILE["cache_hits"] += 1

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    except Exception:  # jax too old/new: compile fields stay zero
        pass


# --- set-up: what happens before any round ------------------------------
# Process-level, always on: the loaders and the model are built before
# a sink exists, often before a Telemetry does. A few dozen entries at
# most, so the list is capped and never cleared.
SETUP_SPAN_CAP = 64
_SETUP_SPANS = []


@contextlib.contextmanager
def setup_span(name: str):
    """Time one piece of set-up (``data_build``, ``model_build``) as a
    ``[name, t0, t1]`` entry of ``setup_spans()``, in ``clock.tick``
    seconds. Also starts the compile accumulator, so that the first
    round record's ``compile_*_before`` counters count from the first
    piece of set-up on."""
    _ensure_compile_listener()
    t0 = clock.tick()
    try:
        yield
    finally:
        if len(_SETUP_SPANS) < SETUP_SPAN_CAP:
            _SETUP_SPANS.append([name, t0, clock.tick()])


def setup_spans() -> list:
    return [list(e) for e in _SETUP_SPANS]


# --- the host as a resource ----------------------------------------------
# Collections are timed process-wide by one ``gc.callbacks`` entry,
# registered with the first sink of the process and never before.
_GC = {"secs": 0.0, "runs": 0, "t0": None}

#: (file, key, seconds a unit) of the cgroup's throttled time: v2, v1;
#: cut to the one that is there at the first sample ([] where none is)
_CPU_STAT = [("/sys/fs/cgroup/cpu.stat", "throttled_usec", 1e-6),
             ("/sys/fs/cgroup/cpu/cpu.stat", "throttled_time", 1e-9)]


def _on_gc(phase, info):
    if phase == "start":
        _GC["t0"] = clock.tick()
    elif _GC["t0"] is not None:
        _GC["secs"] += clock.tick() - _GC["t0"]
        _GC["runs"] += 1
        _GC["t0"] = None


def _ensure_gc_callback():
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def _throttled_s():
    """Seconds the process's cgroup has been held off its CPUs so far;
    None where there is no such file."""
    for source in list(_CPU_STAT):
        path, key, scale = source
        try:
            with open(path) as f:
                for line in f:
                    if line.startswith(key):
                        _CPU_STAT[:] = [source]
                        return int(line.split()[1]) * scale
        except OSError:
            pass
        _CPU_STAT.remove(source)
    return None


def host_sample():
    """(the process's ``host.*`` counters so far, its peak resident
    bytes): one ``getrusage`` call (all threads, the native ring's and
    the runtime's included), the collector's accumulator and, where
    there is one, the cgroup's ``cpu.stat``. A round record carries
    the difference of two samples."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    now = {"host.cpu_user_s": ru.ru_utime, "host.cpu_sys_s": ru.ru_stime,
           "host.minflt": ru.ru_minflt, "host.majflt": ru.ru_majflt,
           "host.nvcsw": ru.ru_nvcsw, "host.nivcsw": ru.ru_nivcsw,
           "host.gc_s": _GC["secs"], "host.gc_runs": _GC["runs"]}
    throttled = _throttled_s()
    if throttled is not None:
        now["host.throttled_s"] = throttled
    return now, int(ru.ru_maxrss) * 1024        # Linux: KiB


def _host_shape():
    """What the first record says of the machine: the cores the
    process may run on and its threads. ``host.threads`` counts the
    Python threads and the native rings' workers, ``host.os_threads``
    every thread the kernel schedules for the process (the runtime's
    too; left out where there is no /proc)."""
    shape = {"host.cpus": len(os.sched_getaffinity(0)),
             "host.threads": threading.active_count()}
    native = sys.modules.get("commefficient_tpu.native")
    if native is not None:
        shape["host.threads"] += native.ring_threads()
    try:
        shape["host.os_threads"] = len(os.listdir("/proc/self/task"))
    except OSError:
        pass
    return shape


# --- the threads' stacks when a round runs long ---------------------------
# A daemon thread over ``sys._current_frames()``, not
# ``faulthandler.dump_traceback_later``: that one would have to be
# re-armed by the round loop every round (a lock and a condition
# variable a call, where arming this is one tuple store), writes
# unbounded text into a file that somebody has to read back, and names
# no thread. What it has over this is that it needs no interpreter
# lock (see ``_watch``).
#: a round open longer than max(STALL_MIN_S, STALL_FACTOR x the median
#: of the last STALL_PERIODS periods) is a stall
STALL_MIN_S = 1.0
STALL_FACTOR = 8.0
STALL_PERIODS = 32
STALL_FRAMES = 8        # innermost frames kept of each thread
STALL_BYTES = 4096      # most characters of frames one record keeps


def stall_limit(periods) -> float:
    """Seconds a round may stay open before its stacks are taken."""
    if not periods:
        return STALL_MIN_S
    return max(STALL_MIN_S, STALL_FACTOR * statistics.median(periods))


def thread_stacks(skip=()):
    """{thread name: [innermost frame first, "file:line function"]} of
    every Python thread but ``skip``, ``STALL_FRAMES`` frames each and
    ``STALL_BYTES`` characters in all."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out, room = {}, STALL_BYTES
    for ident, frame in sys._current_frames().items():
        if ident in skip:
            continue
        frames = []
        while frame is not None and len(frames) < STALL_FRAMES:
            code = frame.f_code
            line = (f"{os.path.basename(code.co_filename)}:"
                    f"{frame.f_lineno} {code.co_name}")[:max(room, 0)]
            room -= len(line)
            if line:
                frames.append(line)
            frame = frame.f_back
        out[names.get(ident, str(ident))] = frames
    return out


def _watch(ref, stop):
    """The stall watchdog's thread: sleeps until the armed record's
    limit and, if that record is still the one armed, takes the stacks
    and leaves them for ``_finish`` to put on it (the record itself is
    written by the round loop's thread alone). Holds its Telemetry
    weakly and only while awake, so a recorder dropped unclosed takes
    the thread with it. A Python thread: it needs the interpreter lock,
    so where the round loop holds that through a long call the stacks
    come late, and ``after_s`` says by how much."""
    done = None
    while not stop.is_set():
        tel = ref()
        if tel is None:
            return
        rec, t_open = tel._armed
        left = t_open + stall_limit(list(tel._periods)) - clock.tick()
        if rec is done:
            left = STALL_MIN_S
        elif left <= 0:
            stall = {
                "after_s": round(clock.tick() - t_open, 6),
                "threads": thread_stacks(skip=(threading.get_ident(),))}
            if tel._armed[0] is rec:    # still the open round
                tel._stalled = (rec, stall)
            done, left = rec, STALL_MIN_S
        del tel, rec
        stop.wait(left)


def _memory_stat(key):
    try:
        from commefficient_tpu.parallel import mesh
        stats = mesh.first_local_device().memory_stats()
        if stats:
            return int(stats.get(key, 0)) or None
    except Exception:
        pass
    return None


def hbm_peak_bytes():
    """Peak accelerator bytes-in-use on local device 0, or None (CPU
    backends don't report; any failure degrades to None)."""
    return _memory_stat("peak_bytes_in_use")


def hbm_reserved_peak_bytes():
    """Peak bytes the runtime reserved on local device 0 beside the
    allocator's own (``peak_bytes_reserved``), or None. On the TPU the
    compiled programs' temporaries live there, so the chip's peak is
    this plus ``hbm_peak_bytes``."""
    return _memory_stat("peak_bytes_reserved")


class Telemetry:
    """Span/counter recorder + sink fan-out for one run."""

    def __init__(self, sinks=None):
        self._sinks = list(sinks or ())
        self._records = OrderedDict()   # round index -> record
        self._closed_rounds = set()     # indices no longer current
        self._alarm_counts = {}         # rule -> fires this run
        self._current = None            # the open round record
        # (record, compile mark at its opening, at its swapping out):
        # the record ``begin_round`` swapped out and nobody finished yet
        self._closing = None
        self._compile_mark = dict(_COMPILE)
        self._host_mark = None          # host_sample() at the last finish
        # the stall watchdog: (the open record, when it opened), the
        # last periods, and the thread's stop, once a round has begun
        self._armed = None
        self._periods = deque(maxlen=STALL_PERIODS)
        self._stalled = None            # (record, its stall), from _watch
        self._watch_stop = None
        self._seen_round = False
        self._shut = False
        # emission hold: a profiler trace window buffers closed
        # records until its trace is parsed, so per-round device-time
        # buckets (schema v3) can merge before the record reaches the
        # sinks. Round ORDER is unchanged — the hold only delays the
        # drain.
        self._hold = False
        # optional callback(round_index, buckets) invoked when trace
        # buckets merge — FedModel points it at the alarm engine's
        # collective-skew check so trace-derived skew can escalate
        # like any other alarm rule
        self.on_device_time = None
        # per-thread stack of the spans open on that thread, as
        # (record, index in its timeline): a span's parent
        self._open = threading.local()
        self._timeline_lock = threading.Lock()
        if self._sinks:
            _ensure_compile_listener()
            _ensure_gc_callback()

    # --- configuration --------------------------------------------------

    @property
    def enabled(self) -> bool:
        return bool(self._sinks)

    def add_sink(self, sink):
        """Attach a sink mid-run (trainers attach the TensorBoard sink
        once the run's logdir exists)."""
        self._sinks.append(sink)
        _ensure_compile_listener()
        _ensure_gc_callback()

    def emit(self, rec):
        for sink in self._sinks:
            sink.write(rec)

    def emit_meta(self, **fields):
        if self._sinks:
            self.emit(make_meta_record(**fields))

    # --- round lifecycle ------------------------------------------------

    def begin_round(self, index: int):
        """Open round ``index`` and swap the previous round's record
        out; ``close_round`` finishes that one (``FedModel`` calls it
        once its program is dispatched; the next ``begin_round`` does
        where nobody has). No-op when disabled."""
        if not self._sinks:
            return None
        rec = make_round_record(index)
        self._records[index] = rec
        self.close_round()
        # the new record is current before the old one is finished
        # (memory statistics, emission): a span another thread opens
        # meanwhile (a loader's producer, woken by the pop that
        # preceded this call) lands on it and is not dropped
        old, self._current = self._current, rec
        mark = dict(_COMPILE)
        if old is not None:
            self._closing = (old, self._compile_mark, mark)
        self._compile_mark = mark
        now = clock.tick()
        if self._armed is not None:
            self._periods.append(now - self._armed[1])
        self._armed = (rec, now)
        if not self._seen_round:
            # what compiled before this run's first round (set-up):
            # with the per-round deltas, all the listener has counted
            self._seen_round = True
            c = rec["counters"]
            c["compile_events_before"] = mark["events"]
            c["compile_secs_before"] = round(mark["secs"], 6)
            c["compile_cache_hits_before"] = mark["cache_hits"]
            self._host_mark = host_sample()[0]
            self._watch_stop = threading.Event()
            threading.Thread(
                target=_watch, args=(weakref.ref(self), self._watch_stop),
                name="telemetry-stall", daemon=True).start()
        return rec

    def close_round(self):
        """Finish the record the last ``begin_round`` swapped out, if
        nobody has: memory statistics, the process's counters, compile
        deltas, emission. The recorder's own work, so it is a span of
        its own (``telemetry.close``, no parent, on the record now
        current) and ``FedModel`` places it after the round's dispatch,
        under the device's program, not before it."""
        closing = self._closing
        if closing is None:
            return
        self._closing = None
        with _Span(self, self._current, "telemetry.close", top=True):
            self._finish(*closing)

    def _finish(self, rec, mark, end):
        now, rec["host_rss_peak_bytes"] = host_sample()
        rec["hbm_peak_bytes"] = hbm_peak_bytes()
        rec["hbm_reserved_peak_bytes"] = hbm_reserved_peak_bytes()
        c = rec["counters"]
        c["compile_events"] = end["events"] - mark["events"]
        c["compile_secs"] = round(end["secs"] - mark["secs"], 6)
        c["compile_cache_hits"] = end["cache_hits"] - mark["cache_hits"]
        if "compile_events_before" in c:    # the run's first record
            c.update(_host_shape())
        was, self._host_mark = self._host_mark, now
        for key, v in now.items():
            c[key] = round(v - was.get(key, v), 6)
        stalled = self._stalled     # never cleared here: the watchdog
        if stalled is not None and stalled[0] is rec:   # alone writes it
            rec["stall"] = stalled[1]
            c["stall.captured"] = 1
        self._closed_rounds.add(rec["round"])
        self._drain()

    def span(self, name: str):
        """Context manager accumulating wall-time into the current
        round record and entering the span on its ``timeline``.
        Outside a round / disabled it records nothing: the shared
        no-op, or, inside a profiler trace window, the bare
        ``fed_phase::<name>`` annotation (``--profile`` without a
        ledger keeps its phases)."""
        rec = self._current
        if rec is None:
            return trace.phase(name)
        return _Span(self, rec, name)

    def _open_spans(self) -> list:
        try:
            return self._open.stack
        except AttributeError:
            stack = self._open.stack = []
            return stack

    def _enter_timeline(self, rec, entry):
        """Append ``entry`` to ``rec``'s timeline and return its index
        there; None (and a ``timeline_dropped`` count) past the cap.
        Under a lock: the loaders' producer threads open spans too."""
        with self._timeline_lock:
            timeline = rec["timeline"]
            if len(timeline) < TIMELINE_CAP:
                timeline.append(entry)
                rec["timeline_cpu"].append(None)
                return len(timeline) - 1
            c = rec["counters"]
            c["timeline_dropped"] = c.get("timeline_dropped", 0) + 1
        return None

    def count(self, name: str, n: int = 1):
        if self._current is not None:
            c = self._current["counters"]
            c[name] = c.get(name, 0) + n

    def set_round_counters(self, index: int, counters: dict):
        """Set counters on round ``index``'s record from values the
        round's program returned (``FedModel.metric_counters``).
        Arrives with the round's metrics, before ``set_round_bytes``."""
        rec = self._records.get(index)
        if rec is not None:
            rec["counters"].update(counters)

    def set_round_bytes(self, index: int, downlink, uplink):
        """Attach the round's FedModel accounting totals. Arrives at
        the end of the client pass."""
        rec = self._records.get(index)
        if rec is None:
            return
        rec["downlink_bytes"] = float(downlink)
        rec["uplink_bytes"] = float(uplink)
        self._drain()

    def set_round_privacy(self, index: int, epsilon, delta, sigma):
        """Stamp the round's DP ledger trail (schema v5): cumulative
        ε(δ) after the round was charged, the δ it is stated at, and
        the effective noise multiplier charged. Arrives right after
        the accountant steps (runtime/fed_model.py) — always before
        emission, which waits on ``set_round_bytes``."""
        rec = self._records.get(index)
        if rec is None:
            return
        rec["dp_epsilon"] = float(epsilon)
        rec["dp_delta"] = float(delta)
        rec["dp_sigma"] = float(sigma)

    def set_round_slo(self, index: int, stamp: dict):
        """Attach the SLO engine's per-objective snapshot (schema v6
        ``slo`` key) to round ``index``'s record. Arrives from the
        round-finish hook (runtime/fed_model.py or the fedservice
        tick), always before emission."""
        rec = self._records.get(index)
        if rec is None or not stamp:
            return
        rec["slo"] = dict(stamp)

    def merge_round_probes(self, index: int, probes: dict):
        """Merge algorithm-probe values onto round ``index``'s record
        (schema v2). Client-pass probes land inside ``metrics_host``;
        server-pass probes merge during ``FedOptimizer.step`` while
        the record is still current — all strictly before the record
        can emit."""
        rec = self._records.get(index)
        if rec is None or not probes:
            return
        if rec.get("probes") is None:
            rec["probes"] = {}
        rec["probes"].update(probes)

    def hold_emission(self, on: bool):
        """Buffer record emission while a profiler trace window is
        open (``on=True``); releasing the hold drains whatever became
        eligible meanwhile. ``close()`` overrides any hold."""
        self._hold = bool(on)
        if not self._hold:
            self._drain()

    def merge_round_device_time(self, index: int, buckets: dict):
        """Attach trace-derived device-time buckets (schema v3) to
        round ``index``'s record — called by the trace window at exit,
        while ``hold_emission`` keeps the records buffered."""
        rec = self._records.get(index)
        if rec is None or not buckets:
            return
        buckets = dict(buckets)
        rec["device_time"] = buckets
        cb = self.on_device_time
        if cb is not None:
            cb(index, buckets)

    def flag_alarm(self, index: int, alarm: dict):
        """Append an alarm dict to round ``index``'s record (schema
        v2 ``alarms`` list) and bump the run's per-rule fire count
        (the ``alarm_fired`` totals ``close()`` emits on the summary
        record). Safe any time before emission."""
        rule = str(alarm.get("rule"))
        self._alarm_counts[rule] = self._alarm_counts.get(rule, 0) + 1
        rec = self._records.get(index)
        if rec is None:
            return
        rec.setdefault("alarms", []).append(alarm)

    def _drain(self, force: bool = False):
        """Emit front records that are closed and byte-complete (or
        everything closed, when forced) — ledger order == round
        order. A trace-window hold defers everything (except forced
        close) until the trace is parsed and merged."""
        if self._hold and not force:
            return
        while self._records:
            idx, rec = next(iter(self._records.items()))
            if idx not in self._closed_rounds:
                break
            if rec["uplink_bytes"] is None and not force:
                break
            self._records.pop(idx)
            self._closed_rounds.discard(idx)
            self.emit(rec)

    # --- non-round records ----------------------------------------------

    def epoch(self, row: dict, epoch: int):
        """Emit the trainer's per-epoch row (TableLogger shape)."""
        if not self._sinks:
            return
        from commefficient_tpu.telemetry.record import make_epoch_record
        self.emit(make_epoch_record(row, epoch))

    # --- shutdown ---------------------------------------------------------

    def close(self):
        """Flush every pending record and close sinks. Idempotent.
        A run in which any alarm fired additionally emits one summary
        record carrying the per-rule ``alarm_fired`` totals, so
        report tooling can show alarm counts without scanning every
        round record; clean runs' ledgers are unchanged."""
        if self._shut:
            return
        self._shut = True
        if self._watch_stop is not None:
            self._watch_stop.set()
        self.close_round()
        rec, self._current = self._current, None
        if rec is not None:
            self._finish(rec, self._compile_mark, dict(_COMPILE))
        self._drain(force=True)
        if self._alarm_counts and self._sinks:
            from commefficient_tpu.telemetry.record import \
                make_summary_record
            self.emit(make_summary_record(
                alarm_fired=dict(sorted(self._alarm_counts.items()))))
        for sink in self._sinks:
            try:
                sink.close()
            except Exception:
                pass
        self._sinks = []


#: module-level disabled instance — importers needing "a telemetry"
#: without plumbing can use this; everything on it no-ops.
NULL_TELEMETRY = Telemetry()

# The Telemetry of each FedModel built and not yet closed, for code
# built before the model and not handed its Telemetry (a loader under
# a harness that builds it first). Weak: a model dropped unclosed
# stops counting.
_LIVE = []


def _live():
    keep = []
    for ref in _LIVE:
        tel = ref()
        if tel is not None and not tel._shut:
            keep.append(ref)
    _LIVE[:] = keep
    return _LIVE


def current() -> Telemetry:
    """The Telemetry of the process's one live ``FedModel``;
    ``NULL_TELEMETRY`` before one is built, and while more than one is
    live (several tenants in one process: a caller that cannot say
    whose it is records nothing rather than on a stranger's record)."""
    live = _LIVE if len(_LIVE) == 1 else _live()
    tel = live[0]() if len(live) == 1 else None
    return NULL_TELEMETRY if tel is None or tel._shut else tel


def set_current(tel: Telemetry):
    """Called by ``FedModel`` when it builds its Telemetry."""
    if all(r() is not tel for r in _live()):
        _LIVE.append(weakref.ref(tel))


def build_telemetry(args, extra_sinks=(), process_index=None,
                    process_count=None) -> Telemetry:
    """Resolve a run's Telemetry from its Config.

    ``--ledger PATH`` attaches a JSONL sink on EVERY process: process
    0 writes the canonical ledger at ``PATH`` (round records carry the
    replicated accounting arrays, so one canonical writer suffices);
    process k > 0 writes the ``PATH.p<k>.jsonl`` shard — its own
    host-phase spans, RSS watermarks, and locally-observed bytes —
    announced once per run so multi-host data is never silently
    dropped. ``scripts/ledger_merge.py`` joins the shards back on
    round id. Records are process-stamped whenever the mesh is
    multi-process. ``--telemetry_console`` attaches the end-of-run
    console summary (process 0 only). The TensorBoard sink is attached
    later by the trainer, which owns the run logdir.

    ``process_index``/``process_count`` default to the live jax
    runtime; tests inject them to exercise the shard layout without a
    multi-process mesh.

    ``--resume`` runs append to the SAME ledger: the sink truncates
    any torn tail the interrupted writer left, then drops replayed
    round records at or below the file's last recorded round id, so
    the resumed ledger's round ids stay monotone and deduplicated
    (replay is bit-exact from the checkpoint, so dropping the
    duplicates loses nothing).
    """
    sinks = list(extra_sinks)
    path = getattr(args, "ledger", "") or ""
    console = bool(getattr(args, "telemetry_console", False))
    if path or console:
        if process_index is None or process_count is None:
            try:
                import jax
                process_index = jax.process_index()
                process_count = jax.process_count()
            except Exception:
                process_index, process_count = 0, 1
        pidx, pcount = int(process_index), int(process_count)
        from commefficient_tpu.telemetry.sinks import (ConsoleSink,
                                                       JSONLSink,
                                                       last_round_index,
                                                       shard_ledger_path)
        if path:
            spath = shard_ledger_path(path, pidx)
            stamp = pidx if pcount > 1 else None
            resume_after = (last_round_index(spath)
                            if getattr(args, "do_resume", False)
                            else None)
            sinks.append(JSONLSink(spath, process=stamp,
                                   resume_after=resume_after))
            if pidx != 0:
                print(f"telemetry: process {pidx}/{pcount} writing "
                      f"ledger shard {spath} (process 0 owns the "
                      f"canonical ledger; merge with "
                      f"scripts/ledger_merge.py)")
        if console and pidx == 0:
            sinks.append(ConsoleSink())
    return Telemetry(sinks)
