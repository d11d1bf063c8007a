"""Device time per round of the client-round program, from the trace
(the ``XLA Modules`` events named after ``client_round``)."""

from benchmark.lib import tracesum


def read(ctx):
    s = tracesum.module_seconds(ctx, "client_round")
    n = tracesum.traced_rounds(ctx)
    return None if s is None or not n else 1e3 * s / n
