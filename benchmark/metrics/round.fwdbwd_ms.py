"""Device time per round under the ``fwd_bwd`` scope (the clients'
value-and-grad, the vocab head included), from the trace."""

from benchmark.lib.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, ("fwd_bwd",))
