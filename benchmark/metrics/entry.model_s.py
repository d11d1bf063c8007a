"""Seconds of set-up in ``FedModel.__init__`` and
``FedOptimizer.__init__``: the program's ``model_build`` set-up
spans."""

from benchmark.lib.timeline import setup_seconds


def read(ctx):
    return setup_seconds(ctx, "model_build")
