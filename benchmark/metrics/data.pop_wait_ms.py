"""Host time per round the consumer waits for a finished batch: the
``data.pop_wait`` span around ``cet_ring_pop`` (only the C++ data plane
is behind it) or around the producer thread's queue, over the untraced
part of the window."""

from benchmark.lib.timeline import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, ("data.pop_wait",))
