"""Device time per round under the ``dense_mlp`` scope: the gated SiLU
feed-forward part of every block of ``models/granite_hybrid.py`` (both
products and the gate; its norm outside), forward and backward. From
the trace; None where the program names no such scope. A part of
``round.fwdbwd_ms``."""

from benchmark.lib.modelscopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, ("dense_mlp",))
