"""A kernel's share of its roofline: the least time the chip could take
for what the algorithm needs (``work`` names a count of ``_work.py``,
``bound`` says whether FLOP/s or bytes/s bounds it) over the summed
device time of the operations whose name matches ``pattern``."""
from .. import costs, trace_reduce
from ._work import traced_work


def read(ctx, pattern, work, bound, within_modules=None):
    seconds, n = trace_reduce.op_time_s(ctx["trace"], pattern,
                                        within_modules)
    if not n or seconds <= 0:
        return None
    w = traced_work(ctx)
    if work == "decode_kv_bytes":
        need = costs.kv_read_bytes(ctx["model"], w.get("decode_pairs", 0))
    elif work == "flash_flops":
        need = w.get("flash_flops", 0)
    else:
        raise ValueError(f"unknown work {work!r}")
    if not need:
        return None
    peak = ctx["peak"]["flops_per_s_bf16" if bound == "flops"
                       else "bytes_per_s_hbm"]
    return 100.0 * (need / peak) / seconds
