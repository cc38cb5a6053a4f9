"""Host time of one of the program's spans in the traced window, from the
program's own record: ``stat`` of the durations of the spans called
``name``. With ``per`` (the name of a step's root span, ``engine.step``)
the durations are first summed within each root's ``step``, over the
steps that have such a span. The medians of the spans directly under
the measured ones go to the run's log."""
import statistics

from . import _program

STATS = {"p50": statistics.median, "mean": statistics.fmean, "sum": sum}


def read(ctx, name, per=None, stat="p50", scale=1.0):
    recs = _program.spans(ctx)
    mine = [r for r in recs if r["name"] == name]
    if not mine:
        return None
    if per is None:
        values = [r["dur"] for r in mine]
    else:
        steps = {(r["pid"], r["step"]) for r in recs if r["name"] == per}
        total = {}
        for r in mine:
            key = (r["pid"], r["step"])
            if key in steps:
                total[key] = total.get(key, 0.0) + r["dur"]
        values = list(total.values())
        if not values:
            return None
    ids = {r["id"] for r in mine}
    under = {}
    for r in recs:
        if r.get("parent") in ids:
            under.setdefault(r["name"], []).append(r["dur"])
    for child, durs in sorted(under.items()):
        _program.log(f"{name} > {child}: p50 "
                     f"{scale * statistics.median(durs):.4f} over "
                     f"{len(durs)}")
    return scale * STATS[stat](values)
