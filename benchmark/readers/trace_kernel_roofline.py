"""A kernel's share of its roofline: the least time the chip could take
for what the algorithm needs (``work`` names a count of the family's
``KERNEL_WORK`` table, ``bound`` says whether FLOP/s or bytes/s bounds it)
over the summed device time of the operations whose name matches
``pattern``."""
from .. import trace_reduce
from ._work import traced_work


def read(ctx, pattern, work, bound, within_modules=None):
    seconds, n = trace_reduce.op_time_s(ctx["trace"], pattern,
                                        within_modules)
    if not n or seconds <= 0:
        return None
    counts = ctx["family"].KERNEL_WORK
    if work not in counts:
        raise ValueError(f"{ctx['family'].__name__} counts no {work!r}; "
                         f"it counts: {', '.join(sorted(counts))}")
    need = counts[work](ctx["model"], traced_work(ctx))
    if not need:
        return None
    peak = ctx["peak"]["flops_per_s_bf16" if bound == "flops"
                       else "bytes_per_s_hbm"]
    return 100.0 * (need / peak) / seconds
