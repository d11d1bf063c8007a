"""Host time per round blocked on the round's metrics (the
``metrics_host`` span: the device's work plus the copy back)."""

from benchmark.lib.spans import window_mean_ms


def read(ctx):
    return window_mean_ms(ctx, ("metrics_host",))
