"""CPU time per round of the round loop's thread: the ``timeline_cpu``
of its parentless spans (``sampler``, ``client_pass``, ``server_pass``)
over the untraced rounds. Their wall minus this is the thread's wait
(for the device, a lock, a core); the reader prints CPU, wait and what
lies under no span against the mean period."""

from benchmark.lib.hostclock import loop_cpu_ms


def read(ctx):
    return loop_cpu_ms(ctx)
