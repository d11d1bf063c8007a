"""``setup_s`` minus the union of the program's ``data_build`` and
``model_build`` set-up spans and the warm-up rounds: imports, the
builder's own work, the first batch's fetch. The coverage number of
set-up, as ``runtime.uncovered_ms`` is a round's."""

from benchmark.lib.hostclock import setup_uncovered_s
from benchmark.lib.timeline import setup_table


def read(ctx):
    setup_table(ctx)
    return setup_uncovered_s(ctx)
