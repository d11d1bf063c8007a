"""Device time per round under the ``moe_route`` scope alone: router
scores, top-8, the sort that orders the assignments held here and the
gather of their rows, forward and backward; from the trace."""

from benchmark.lib.modelscopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, ("moe_route",))
