"""Process start to the end of warm-up: imports, data and weights from
the seed, assembling the run, and the first three rounds (which compile
on a cold cache)."""


def read(ctx):
    return ctx["setup_s"]
