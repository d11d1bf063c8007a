"""Device time per round under the ``compress`` scope (the sketch's
emit, or the local top-k in that mode), from the trace."""

from benchmark.lib.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, ("compress",))
