"""How many (query, key) scores the program's attention computes for
each one the causal bands need: the round records' ``attn.pairs`` over
``attn.pairs_needed`` (both counted from the shapes the layers were
built in: a full layer's whole rows against its causal half, a window
layer's slice of keys against its band), averaged over the untraced
part of the window. 1 is a program that computes no masked score; None
where the program counts neither. Prints every value the records'
engagement counters took (``attn.*``, ``moe.dropped``,
``moe.router_pre_attn``, ``select.blocked``, ``sketch.rot_addressed``)."""

from benchmark.lib.timeline import untraced_records

SHOWN = ("attn.window_layers", "attn.full_layers", "attn.blocked",
         "attn.window_keys", "attn.pairs", "attn.pairs_needed",
         "moe.dropped", "moe.router_pre_attn", "select.blocked",
         "sketch.rot_addressed")


def read(ctx):
    recs = [r.get("counters", {}) for r in untraced_records(ctx)]
    ratios = [c["attn.pairs"] / c["attn.pairs_needed"] for c in recs
              if c.get("attn.pairs_needed")]
    if not ratios:
        return None
    print(f"counters over {len(recs)} untraced records:", "; ".join(
        f"{name} {sorted({c.get(name) for c in recs}, key=str)}"
        for name in SHOWN))
    return sum(ratios) / len(ratios)
