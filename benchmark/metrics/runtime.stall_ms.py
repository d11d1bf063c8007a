"""The part of a round period (a ``client_pass`` opening to the next)
beyond three times the window's median period, mean per round over the
untraced rounds: 0 in most runs, tens of ms in a run that holds one
round of seconds (an epoch's opening round, about twice the median,
does not count). Prints the longest round's record: each span's wall
and CPU, its ``host.*`` counters, the head of its ``stall`` stacks."""

from benchmark.lib.hostclock import stall_ms


def read(ctx):
    return stall_ms(ctx)
