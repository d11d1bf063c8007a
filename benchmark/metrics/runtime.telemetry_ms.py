"""What the recorder's own work costs a round while a sink is attached:
the ``telemetry.close`` span (memory statistics, the process's
counters, compile deltas, emission), which ``FedModel`` runs after the
round's dispatch, under the device's program. The per-layer numbers
describe a program that does this; ``updates_per_s`` one that does
not."""

from benchmark.lib.hostclock import telemetry_ms


def read(ctx):
    return telemetry_ms(ctx)
