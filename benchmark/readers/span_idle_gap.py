"""Device idle time the program itself is answerable for: the part of the
traced window in which no operation ran on the device AND one of the
program's spans whose name starts with ``prefix`` was open on the host,
per such root span (``root``, one a step). The spans come from the
program's record and are moved onto the trace's clock by the offset
``_program.clock_offset_ns`` gives."""
from .. import trace_reduce
from . import _program


def overlap_ns(gaps, spans) -> int:
    """Summed overlap of two lists of [start, end], each sorted and
    disjoint within itself."""
    total, j = 0, 0
    for a, b in gaps:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < b:
            total += max(0, min(b, spans[k][1]) - max(a, spans[k][0]))
            k += 1
    return total


def read(ctx, prefix, root, scale=1.0):
    offset = _program.clock_offset_ns(ctx)
    if offset is None:
        return None
    recs = [r for r in _program.spans(ctx) if r["name"].startswith(prefix)]
    roots = sum(r["name"] == root for r in recs)
    if not roots:
        return None
    trace = ctx["trace"]
    planes = trace_reduce.device_planes(trace)
    if not planes:
        return None
    t0, t1 = trace_reduce.window_of(trace)
    busy = trace_reduce.union(trace_reduce.clip(
        planes[sorted(planes)[0]].get(trace_reduce.OPS_LINE, []), t0, t1))
    edges = [t0] + [t for iv in busy for t in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    open_ = trace_reduce.union(
        [[r["name"], int(r["ts"] * 1e9 + offset), int(r["dur"] * 1e9)]
         for r in recs])
    return scale * overlap_ns(gaps, open_) / 1e9 / roots
