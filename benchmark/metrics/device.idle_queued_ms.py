"""Of the traced window's idle gaps, the part in which the round loop
had already dispatched the round's program and was waiting for it
(inside ``note_update`` or ``metrics_host``), per traced round: the
device waited for its input or the runtime, not for the host's code.
The rest of ``device.idle`` is the host being late. Prints every gap by
the innermost span open on the round loop's thread and on each other
thread (``trace.host_timeline`` over ``tracesum``'s busy intervals)."""

from benchmark.lib.hostclock import idle_queued_ms


def read(ctx):
    return idle_queued_ms(ctx)
