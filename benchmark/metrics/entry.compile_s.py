"""Seconds the program's compile listener counted (tracing, lowering,
backend compilation or the fetch from the persistent cache) from its
start to the end of warm-up. A part of the other set-up times, not a
further one."""

from benchmark.lib.timeline import setup_compile_seconds


def read(ctx):
    return setup_compile_seconds(ctx)
