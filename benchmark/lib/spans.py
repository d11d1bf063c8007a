"""Means over the host spans of a traced run's round records."""


def window_mean_ms(ctx, names):
    """Mean per round, in ms, of the summed ``names`` spans over the
    window's round records; None when the run recorded none."""
    recs = ctx.get("records")
    if not recs:
        return None
    win = ctx["window"]
    vals = [sum(r["spans"].get(n, 0.0) for n in names) for r in recs
            if r.get("spans") is not None and "round" in r
            and win["first"] <= r["round"] < win["last"]]
    return 1e3 * sum(vals) / len(vals) if vals else None
