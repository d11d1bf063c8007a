"""Seconds of set-up in the three warm-up rounds, from round 0's first
span to the last warm-up round's end: the round programs' first
dispatch (compilation, or the read from the persistent cache) is in
it, which ``entry.compile_s`` counts wherever it happens."""

from benchmark.lib.hostclock import warmup_s


def read(ctx):
    return warmup_s(ctx)
