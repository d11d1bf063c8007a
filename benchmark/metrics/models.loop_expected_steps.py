"""The step a looped language model's exit distribution expects to
leave at: the round records' ``loop.expected_steps`` (the mean over
clients and predicting positions of sum_t t * p_t, between 1 and
``loop.steps``), averaged over the untraced part of the window. The
program counts it inside the round; None where it does not. Prints the
values ``loop.steps``, ``loop.layer_applications`` and
``loop.exit_mass_last`` took on those records (engagement: how many
times the stack ran, and the mass left to the last step)."""

from benchmark.lib.timeline import untraced_records

SHOWN = ("loop.steps", "loop.layer_applications", "loop.exit_mass_last")


def read(ctx):
    recs = [r.get("counters", {}) for r in untraced_records(ctx)]
    steps = [c["loop.expected_steps"] for c in recs
             if "loop.expected_steps" in c]
    if not steps:
        return None
    print(f"counters over {len(recs)} untraced records:", "; ".join(
        f"{name} {sorted({c.get(name) for c in recs}, key=str)[:4]}"
        for name in SHOWN))
    return sum(steps) / len(steps)
