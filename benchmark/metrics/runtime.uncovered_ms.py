"""The part of the round period under none of the ``sampler``,
``client_pass`` and ``server_pass`` spans, from the round records'
timeline: the coverage number. If it is not small, a span is
missing."""

from benchmark.lib.timeline import clock_check, uncovered_ms


def read(ctx):
    clock_check(ctx)
    return uncovered_ms(ctx)
