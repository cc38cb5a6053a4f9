"""Device milliseconds a training step spends in the operations whose
name matches ``pattern``: their summed time inside whole runs of the step
program in the traced window, over the steps traced."""
from .. import trace_reduce
from ._work import traced_work


def read(ctx, pattern, within_modules=None):
    seconds, n = trace_reduce.op_time_s(ctx["trace"], pattern,
                                        within_modules)
    steps = traced_work(ctx).get("steps")
    if not n or not steps:
        return None
    return 1e3 * seconds / steps
