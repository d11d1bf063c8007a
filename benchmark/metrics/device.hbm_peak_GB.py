"""The result line's ``memory_peak_bytes`` in GB: ``peak_bytes_in_use``
+ ``peak_bytes_reserved`` of ``memory_stats()`` on the fullest chip,
read after the window and before the reference runs. On this chip the
round programs' temporaries live in the reserved region, which the
allocator's own peak leaves out (PERF.md section 3)."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 1e9
