"""Structured run observability: round ledgers, spans, sinks.

The repo's single instrumented source of truth — per-round wall-time
spans, uplink/downlink bytes unified with FedModel's accounting,
memory watermarks, compile events — with pluggable sinks (JSONL
ledger, TensorBoard, console summary) and near-zero overhead when
disabled.  See record.py for the ledger schema, core.py for the span
lifecycle, scripts/telemetry_report.py for rendering/diffing ledgers.

``telemetry.profiler`` (jax.profiler trace windows) is imported
lazily by its users, not here: it reaches back into ``utils`` for
logdir naming and must not cycle through this package import.
"""

from commefficient_tpu.telemetry import clock, trace
from commefficient_tpu.telemetry.core import (NULL_TELEMETRY, Telemetry,
                                              build_telemetry, current,
                                              hbm_peak_bytes,
                                              hbm_reserved_peak_bytes,
                                              set_current, setup_span,
                                              setup_spans)
from commefficient_tpu.telemetry.record import (LEDGER_SCHEMA_VERSION,
                                                make_meta_record,
                                                make_round_record,
                                                validate_record)
from commefficient_tpu.telemetry.flightrec import (FlightRecorder,
                                                   install_crash_hook,
                                                   load_postmortem)
from commefficient_tpu.telemetry.live import (LiveMetricsSink,
                                              LiveRegistry,
                                              attach_live_plane,
                                              live_registry,
                                              shutdown_plane)
from commefficient_tpu.telemetry.sinks import (ConsoleSink, JSONLSink,
                                               TensorBoardSink,
                                               job_index_of_ledger,
                                               job_ledger_path,
                                               recover_ledger_shards)
from commefficient_tpu.telemetry.slo import (SLOEngine, SLOSpec,
                                             build_slo_engine)

__all__ = [
    "clock",
    "trace",
    "NULL_TELEMETRY",
    "Telemetry",
    "build_telemetry",
    "current",
    "set_current",
    "setup_span",
    "setup_spans",
    "hbm_peak_bytes",
    "hbm_reserved_peak_bytes",
    "LEDGER_SCHEMA_VERSION",
    "make_meta_record",
    "make_round_record",
    "validate_record",
    "ConsoleSink",
    "JSONLSink",
    "TensorBoardSink",
    "job_ledger_path",
    "job_index_of_ledger",
    "recover_ledger_shards",
    "FlightRecorder",
    "install_crash_hook",
    "load_postmortem",
    "LiveMetricsSink",
    "LiveRegistry",
    "attach_live_plane",
    "live_registry",
    "shutdown_plane",
    "SLOEngine",
    "SLOSpec",
    "build_slo_engine",
]
