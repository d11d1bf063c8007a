"""The sketch kernel's least time at the cell's d, c, r over its time a
call in the round programs, read from the device trace."""

from benchmark.lib.kernelbench import roofline_share


def read(ctx):
    return roofline_share(ctx, "sketch")
