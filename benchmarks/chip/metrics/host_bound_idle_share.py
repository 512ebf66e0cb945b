"""Device: device-idle time under a program span (`scope.*`) over the
window (%): the part of `device_idle_share` the program causes, as against
a lull in traffic (`bench.wait`)."""
from harness import program_trace


def read(run):
    return program_trace.host_bound_idle_share(run.trace)
