"""Host time per round in the byte accounting: the ``account`` span
(``FedModel._account_bytes``) and the ``note_update`` span (the support
bitmap and ``FedModel.note_update``), over the untraced part of the
window."""

from benchmark.lib.timeline import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, ("account", "note_update"))
