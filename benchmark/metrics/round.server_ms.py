"""Device time per round of the server-round program, from the trace
(the ``XLA Modules`` events named after ``server_round``)."""

from benchmark.lib import tracesum


def read(ctx):
    s = tracesum.module_seconds(ctx, "server_round")
    n = tracesum.traced_rounds(ctx)
    return None if s is None or not n else 1e3 * s / n
