"""The whole step's share of the chip's peak: model FLOPs of the work done
in the traced window (the family's count, by the rules of
``benchmark/costs.py``: matmul parameters only, causal attention once,
nothing recomputed) over the summed device time of the step program's
runs there times the peak FLOP/s."""
from .. import trace_reduce
from ._work import model_flops


def read(ctx, pattern):
    runs = trace_reduce.module_durations_s(ctx["trace"], pattern)
    if not runs:
        return None
    flops = model_flops(ctx)
    if not flops:
        return None
    return 100.0 * flops / (sum(runs) * ctx["peak"]["flops_per_s_bf16"])
