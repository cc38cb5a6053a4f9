"""Device time of a program's runs in the traced window: ``stat`` of the
durations of the module events whose name matches ``pattern``."""
import statistics

from .. import trace_reduce


def read(ctx, pattern, stat="p50", scale=1.0):
    runs = trace_reduce.module_durations_s(ctx["trace"], pattern)
    if not runs:
        return None
    value = {"p50": statistics.median, "mean": statistics.fmean,
             "sum": sum}[stat](runs)
    return scale * value
