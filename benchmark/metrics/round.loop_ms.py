"""Device time per round under the ``ut_loop`` scope: the passes of a
looped language model over its stack of shared-weight blocks, final
norms included (``models/ouro.py``: ``total_ut_steps`` applications of
every held layer), forward and backward; the blocks' ``gqa_attn``,
``rope`` and ``dense_mlp`` lie inside it, the head and the exit gate
outside. A part of ``round.fwdbwd_ms``. From the trace; None where the
program names no such scope."""

from benchmark.lib.modelscopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, ("ut_loop",))
