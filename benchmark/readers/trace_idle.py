"""Share of the traced window in which no operation ran on the device."""
from .. import trace_reduce


def read(ctx):
    return 100.0 * trace_reduce.idle_share(ctx["trace"])
