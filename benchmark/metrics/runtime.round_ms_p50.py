"""Median of the time between successive rounds' results reaching the
host, over the untraced part of the window: what ``round_ms_p90`` is
the tail of. Where epochs are short (the ResNet9 cell: 8 rounds) the
p90 is the loader's epoch-restart stall and the median is the ordinary
round."""

from benchmark.lib.stats import percentile


def read(ctx):
    win = ctx["window"]
    ends = [r["t_end"] for r in
            ctx["rounds"][win["first"]:win.get("first_traced", win["last"])]]
    gaps = [b - a for a, b in zip([win["t_start"]] + ends[:-1], ends)]
    return 1e3 * percentile(gaps, 50) if gaps else None
