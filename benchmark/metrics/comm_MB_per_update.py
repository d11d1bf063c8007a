"""Uplink + downlink bytes per client update, from the byte counts each
round returns to its trainer (``cv_train``'s "up (MiB)" / "down (MiB)"),
averaged over rounds [0, mark_round): a count that repeats for a seed."""


def read(ctx):
    mark = ctx["cell"]["mark_round"]
    rounds = ctx["rounds"][:mark]
    total = sum(r["down"] + r["up"] for r in rounds)
    return total / (ctx["clients_per_round"] * len(rounds)) / 1e6
