"""How uneven the routing to the experts held here is: the round
records' ``moe.load_max`` (the fullest (layer, expert) of the round's
fullest client) over ``moe.load_mean`` (the mean over clients, layers
and held experts), averaged over the untraced part of the window. The
program counts both inside the round; None where it does not."""

from benchmark.lib.timeline import untraced_records


def read(ctx):
    ratios = [r["counters"]["moe.load_max"] / r["counters"]["moe.load_mean"]
              for r in untraced_records(ctx)
              if r["counters"].get("moe.load_mean")]
    return sum(ratios) / len(ratios) if ratios else None
