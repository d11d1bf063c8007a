"""Seconds of set-up in the trainer's ``get_data_loaders`` (dataset
prepared or tokenised, loader built): the program's ``data_build``
set-up span."""

from benchmark.lib.timeline import setup_seconds, setup_table


def read(ctx):
    setup_table(ctx)
    return setup_seconds(ctx, "data_build")
