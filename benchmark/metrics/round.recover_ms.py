"""Device time per round recovering the update on the server: the
``estimates``, ``select`` and ``resketch`` scopes, from the trace."""

from benchmark.lib.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, ("estimates", "select", "resketch"))
