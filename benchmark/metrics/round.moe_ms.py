"""Device time per round in the expert layers: the union of the scopes
``moe_route`` (scores, top-8, the sort that orders the assignments, the
gathers), ``moe_experts`` (the held experts' products, forward and
backward), ``moe_combine`` (gates, scatter-adds, the shared expert's
add) and of the compiler's own ragged-product kernels, whose events
carry ``ragged-dot-none`` in place of the program's scope path (this
cell has no other ragged product). From the trace. A part of
``round.fwdbwd_ms``; the MTP module's expert layer counts here and under
``round.mtp_ms``."""

from benchmark.lib.modelscopes import scopes_ms


def read(ctx):
    return scopes_ms(ctx, ("moe_route", "moe_experts", "moe_combine",
                           "ragged-dot"))
