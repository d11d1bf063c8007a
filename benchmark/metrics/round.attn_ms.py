"""Device time per round under the ``gqa_attn`` scope: the scores,
softmax and value product of the grouped-query heads held here
(``models/nemotron_h.py``), forward and backward; the four projections
are outside it. From the trace; None where the program names no such
scope. A part of ``round.fwdbwd_ms``."""

from benchmark.lib.modelscopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, ("gqa_attn",))
