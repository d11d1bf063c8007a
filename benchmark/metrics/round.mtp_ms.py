"""Device time per round under the ``mtp`` scope: the multi-token-
prediction module (its projection, block, norm) and its application of
the shared head, forward and backward; from the trace."""

from benchmark.lib.modelscopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, ("mtp",))
