"""Device time per round under the ``exit_gate`` scope: a looped
language model's exit gate over every step's states, the exit
distribution and its entropy (``models/ouro.py``), forward and
backward. A part of ``round.fwdbwd_ms``. From the trace; None where the
program names no such scope."""

from benchmark.lib.modelscopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, ("exit_gate",))
