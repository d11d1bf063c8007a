"""Device time per round under the ``lm_head`` scope (GPT-2's tied
vocab head, forward and backward; a part of ``round.fwdbwd_ms``), from
the trace."""

from benchmark.lib.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, ("lm_head",))
