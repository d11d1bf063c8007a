"""Host time per round spent dispatching: the h2d, round_dispatch and
server spans of ``runtime/fed_model.py`` (not the wait for the device,
which is ``runtime.sync_ms``)."""

from benchmark.lib.spans import window_mean_ms


def read(ctx):
    return window_mean_ms(ctx, ("h2d", "round_dispatch", "server"))
