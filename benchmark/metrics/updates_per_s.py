"""Client updates completed in the window over the window's seconds:
all the rounds, all the time, from the window's start to the moment
the last round's weights are ready."""


def read(ctx):
    win = ctx["window"]
    n = win["last"] - win["first"]
    return ctx["clients_per_round"] * n / (win["t_close"] - win["t_start"])
