"""90th percentile of the time between successive rounds' results
reaching the host, over every round of the window (the first from the
window's start)."""

from benchmark.lib.stats import percentile


def read(ctx):
    win = ctx["window"]
    ends = [r["t_end"] for r in ctx["rounds"][win["first"]:win["last"]]]
    gaps = [b - a for a, b in zip([win["t_start"]] + ends[:-1], ends)]
    longest = sorted(range(len(gaps)), key=gaps.__getitem__)[-3:]
    print(f"round_ms_p90: {len(gaps)} samples, "
          f"median {1e3 * percentile(gaps, 50):.3f} ms; longest (round of "
          "the window: ms) "
          + ", ".join(f"{i}: {1e3 * gaps[i]:.1f}" for i in longest))
    return 1e3 * percentile(gaps, 90)
