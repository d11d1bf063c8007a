"""Device time per round under the ``ssm_scan`` scope: the Mamba-2
recurrence as ``models/nemotron_h.py ssd_chunked`` computes it (the
decay sums, the in-chunk masked products, the scan that carries the
state from chunk to chunk), forward and backward; the conv, the gate's
norm and the projections are outside it. From the trace; None where the
program names no such scope. A part of ``round.ssm_ms``."""

from benchmark.lib.modelscopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, ("ssm_scan",))
