"""Host time per round advancing the sampler inside ``next(loader)``:
the ``data.sample`` span of ``data/loader.py``, over the untraced part
of the window."""

from benchmark.lib.timeline import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, ("data.sample",))
