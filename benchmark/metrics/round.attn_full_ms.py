"""Device time per round under the ``attn_full`` scope: the scores,
softmax and value product of the attention layers that see their whole
past (``models/smallthinker.py``: a block of queries against every
key), forward and backward. A part of ``round.attn_ms``. From the
trace; None where the program names no such scope."""

from benchmark.lib.modelscopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, ("attn_full",))
