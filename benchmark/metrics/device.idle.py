"""1 - busy / traced window, busy being the union of the intervals in
which an operation ran on the device (``tracelib.attribute_rounds``),
averaged over the chips used."""

from benchmark.lib import tracesum


def read(ctx):
    d = tracesum.summary(ctx)["device"]
    return None if not d["window_s"] else \
        100.0 * (1.0 - d["busy_s"] / d["window_s"])
