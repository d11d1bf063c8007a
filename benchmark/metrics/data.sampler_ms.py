"""Host wait in ``next(loader)`` per round of the window (the
``sampler`` span of the round records)."""

from benchmark.lib.spans import window_mean_ms


def read(ctx):
    return window_mean_ms(ctx, ("sampler",))
