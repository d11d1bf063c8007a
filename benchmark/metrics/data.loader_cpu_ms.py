"""CPU time per round of every Python thread but the round loop's (the
loaders' ``loader-stage``, ``persona-prefetch``, ``tokens-prefetch``):
the ``timeline_cpu`` of their parentless spans over the untraced
rounds, printed beside their wall. A ``data.index`` of 40 ms with 4 ms
of CPU was descheduled, not slower."""

from benchmark.lib.hostclock import loader_cpu_ms


def read(ctx):
    return loader_cpu_ms(ctx)
