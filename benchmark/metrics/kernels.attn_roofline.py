"""The least time the chip could take for a round's attention (the
pairs the masks let through, forward and backward, at the bf16 peak)
over the time a round of every device operation of the flash kernel,
read from the device trace. None where the trace names no such
operation. Prints the values ``attn.kernel_layers`` took on the
untraced round records (the layers the kernel built)."""

from benchmark.lib.attnbench import roofline_share
from benchmark.lib.timeline import untraced_records


def read(ctx):
    built = sorted({r.get("counters", {}).get("attn.kernel_layers")
                    for r in untraced_records(ctx)}, key=str)
    print(f"counters: attn.kernel_layers {built}")
    return roofline_share(ctx)
