"""Opt-in ``jax.profiler`` trace windows (``--profile``).

The structured replacement for the reference's cProfile scaffolding
(fed_aggregator.py:46-52, SURVEY §5): an xplane trace of a bounded
window, written where the rest of the run's observability lands.
``profile_epoch`` keeps its historical shape (trace the first trained
epoch); ``trace_window`` is the generic round-window form for
benches/scripts.

When a ``telemetry`` object rides along, the window becomes the
device-time attribution pipeline (telemetry/trace.py): round markers
activate for the window's duration, record emission is held, and at
exit the written trace is parsed into per-round buckets that merge
onto the buffered records as schema-v3 ``device_time`` fields before
the hold releases. A parse failure degrades to a warning — the run's
ledger still emits, just without device-time fields.
"""

from __future__ import annotations

import os


class trace_window:
    """Context manager: capture a JAX profiler (xplane) trace of the
    enclosed region into ``logdir`` when ``active``. Pass the run's
    ``telemetry`` to attribute the trace back onto the round ledger."""

    def __init__(self, logdir: str, active: bool = True,
                 telemetry=None):
        self.active = bool(active)
        self.logdir = logdir
        self.telemetry = telemetry
        self.round_buckets = {}

    def __enter__(self):
        if self.active:
            import jax

            from commefficient_tpu.telemetry import trace
            os.makedirs(self.logdir, exist_ok=True)
            jax.profiler.start_trace(self.logdir)
            trace.set_tracing(True)
            if self.telemetry is not None and self.telemetry.enabled:
                self.telemetry.hold_emission(True)
        return self

    def __exit__(self, *exc):
        if not self.active:
            return False
        import jax

        from commefficient_tpu.telemetry import trace
        # close any open round marker BEFORE stopping the trace, so
        # its end timestamp lands inside the dump
        trace.set_tracing(False)
        jax.profiler.stop_trace()
        print(f"profiler trace written to {self.logdir}")
        tel = self.telemetry
        if tel is not None and tel.enabled:
            try:
                self.round_buckets = trace.attribute_logdir(self.logdir)
                for ridx, buckets in sorted(self.round_buckets.items()):
                    tel.merge_round_device_time(ridx, buckets)
                if self.round_buckets:
                    n = len(self.round_buckets)
                    busy = sum(b["busy_s"]
                               for b in self.round_buckets.values())
                    win = sum(b["window_s"]
                              for b in self.round_buckets.values())
                    tel.emit_meta(
                        trace_logdir=self.logdir,
                        trace_rounds=n,
                        trace_busy_s=round(busy, 6),
                        trace_window_s=round(win, 6))
            except Exception as e:  # noqa: BLE001 — observability only
                from commefficient_tpu.telemetry.alarms import \
                    DivergenceAbort
                if isinstance(e, DivergenceAbort):
                    # a collective_skew alarm escalated to abort while
                    # the buckets merged — that's the run policy
                    # acting, not an attribution failure; let it stop
                    # the trainer like any other abort
                    raise
                print("WARNING: trace attribution failed "
                      f"({type(e).__name__}: {e}); ledger emits "
                      "without device_time")
            finally:
                tel.hold_emission(False)
        return False


class profile_epoch(trace_window):
    """Trace ONE epoch (the first trained one) into
    ``<logdir>/profile`` when ``--profile``."""

    def __init__(self, args, epoch, start_epoch=0, logdir=None,
                 telemetry=None):
        if logdir is None:
            from commefficient_tpu.utils import make_logdir
            logdir = make_logdir(args)
        super().__init__(
            os.path.join(logdir, "profile"),
            active=(getattr(args, "do_profile", False)
                    and epoch == start_epoch),
            telemetry=telemetry)
