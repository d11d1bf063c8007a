"""Host time per round turning a round of the sampler into storage rows
(``NativeFedLoader._spec_to_indices``: Python on the consumer thread)
or collating it (``FedLoader`` / ``PersonaFedLoader.collate``, on the
producer thread where the loader has one): the ``data.index`` and
``data.collate`` spans, over the untraced part of the window."""

from benchmark.lib.timeline import loader_table, span_mean_ms


def read(ctx):
    loader_table(ctx)
    return span_mean_ms(ctx, ("data.index", "data.collate"))
