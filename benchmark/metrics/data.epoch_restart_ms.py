"""The ``sampler`` span of the rounds whose ``next(loader)`` entered a
fresh ``__iter__`` (counter ``data.epoch_start``), mean in ms over the
untraced part of the window: what one round in an epoch pays for the
loader's restart, and what ``round_ms_p90`` reads where epochs are
short. None where no epoch starts inside the window."""

from benchmark.lib.timeline import (children_ms, epoch_start_records,
                                    span_mean_ms)


def read(ctx):
    recs = epoch_start_records(ctx)
    if not recs:
        return None
    _, kids = children_ms(ctx, "sampler", recs)
    print(f"epoch starts in the untraced window: {len(recs)} rounds; "
          "the sampler span's children there, ms:",
          {k: round(v, 1) for k, v in kids.items()})
    return span_mean_ms(ctx, ("sampler",), recs)
