"""Device time per round under the ``attn_window`` scope: the scores,
softmax and value product of the attention layers that see a window of
their past (``models/smallthinker.py``: a block of queries against the
slice of keys its band reaches), forward and backward. A part of
``round.attn_ms``. From the trace; None where the program names no such
scope."""

from benchmark.lib.modelscopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, ("attn_window",))
