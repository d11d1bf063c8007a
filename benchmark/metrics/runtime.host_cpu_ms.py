"""User + system CPU time per round of the whole process, every thread
the kernel schedules for it (the native ring's and the runtime's too):
the round records' ``host.cpu_user_s`` + ``host.cpu_sys_s`` deltas over
the untraced rounds. Printed against ``host.cpus`` x the mean period,
with the faults, switches, collections and throttled seconds a round."""

from benchmark.lib.hostclock import host_cpu_ms


def read(ctx):
    return host_cpu_ms(ctx)
