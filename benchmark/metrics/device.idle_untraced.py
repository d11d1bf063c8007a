"""1 - (device busy seconds a traced round) / (round period of the
untraced part of the window). The profiler slows a host-bound round
(the ResNet9 cell's traced rounds take over twice as long), so the idle
share of the traced window overstates what an untraced run idles; the
device's own time per round does not change under the profiler.

A mixed reading: the busy seconds come from the device trace, the
period from the host's clock over the untraced rounds (tens of seconds
of them). It is declared ``host_clock``, the less exact of the two."""

from benchmark.lib import tracesum


def read(ctx):
    win = ctx["window"]
    n_untraced = win["first_traced"] - win["first"]
    n_traced = tracesum.traced_rounds(ctx)
    if n_untraced <= 0 or not n_traced:
        return None
    period = (win["t_trace"] - win["t_start"]) / n_untraced
    busy = tracesum.summary(ctx)["device"]["busy_s"] / n_traced
    return 100.0 * (1.0 - busy / period)
