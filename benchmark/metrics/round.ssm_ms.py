"""Device time per round under the ``ssm_mixer`` scope: the whole
Mamba-2 mixer of ``models/nemotron_h.py`` (its two projections, the
conv, the chunked scan, the gate and its grouped norm), forward and
backward, over the ``M`` layers. From the trace; None where the program
names no such scope. A part of ``round.fwdbwd_ms``."""

from benchmark.lib.modelscopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, ("ssm_mixer",))
