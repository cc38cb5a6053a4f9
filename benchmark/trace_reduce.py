"""From a profiler trace to numbers: busy union, idle share, per-module and
per-op device time, idle gaps labelled by what the host was doing.

A trace here is plain data, ``{"planes": {plane: {line: [[name, start_ns,
duration_ns], ...]}}}``, which ``read_xplane`` makes from the ``.xplane.pb``
the JAX profiler writes (via ``jax.profiler.ProfileData``, nothing else)
and which tests keep as a small JSON file. Device planes are named
``/device:TPU:<n>``; their ``XLA Modules`` line has one event per program
run and ``XLA Ops`` one per operation. Host spans are the driver's own
``jax.profiler.TraceAnnotation`` events, found by their ``bench:`` prefix
on any host line.
"""
import bisect
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench:"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """An operation's event name is its whole HLO line: keep the name
    before " = " and, for a custom call, its target, which is how a
    Pallas kernel (``tpu_custom_call``) is told from a fusion while the
    program gives its kernels no names."""
    head = name.split(" = ", 1)[0].lstrip("%")
    target = TARGET.search(name)
    return f"{head} {target.group(1)}" if target else head


def read_xplane(path: str) -> dict:
    """Device planes whole (modules and ops lines), host lines cut to the
    driver's own spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULES_LINE, OPS_LINE):
                    lines[line.name] = [
                        [short_name(e.name), int(e.start_ns),
                         int(e.duration_ns)] for e in line.events]
            planes[plane.name] = lines
        elif plane.name.startswith("/host:"):
            spans = []
            for line in plane.lines:
                spans += [[e.name, int(e.start_ns), int(e.duration_ns)]
                          for e in line.events
                          if e.name.startswith(HOST_PREFIX)]
            if spans:
                planes.setdefault("host", {}).setdefault(
                    "spans", []).extend(sorted(spans, key=lambda s: s[1]))
    return {"planes": planes}


def load(path: str) -> dict:
    """A trace kept as (gzipped) JSON."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def device_planes(trace: dict) -> dict:
    return {n: p for n, p in trace["planes"].items()
            if DEVICE_PLANE.match(n)}


def host_spans(trace: dict) -> list:
    return trace["planes"].get("host", {}).get("spans", [])


def window_of(trace: dict, span_name: str = HOST_PREFIX + "window"):
    """(start_ns, end_ns) of the driver's window span; without one, the
    extent of the device events."""
    for name, start, dur in host_spans(trace):
        if name == span_name:
            return start, start + dur
    evs = [e for p in device_planes(trace).values()
           for e in p.get(OPS_LINE, [])]
    if not evs:
        raise ValueError("the trace holds no device operation")
    return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def clip(events, t0: int, t1: int):
    """Events cut to [t0, t1): [name, start, duration] of the part inside."""
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append([name, a, b - a])
    return out


def union(events):
    """Sorted, merged [start, end] intervals of the events."""
    merged = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return merged


def busy_ns(events) -> int:
    return sum(b - a for a, b in union(events))


def busy_and_window_s(trace: dict):
    """(seconds in which an operation ran on the device, averaged over
    the device planes; seconds of the window)."""
    t0, t1 = window_of(trace)
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy = [busy_ns(clip(p.get(OPS_LINE, []), t0, t1))
            for p in planes.values()]
    return sum(busy) / len(busy) / 1e9, (t1 - t0) / 1e9


def idle_share(trace: dict) -> float:
    busy, window = busy_and_window_s(trace)
    return 1.0 - busy / window


def _first_plane(trace: dict) -> dict:
    planes = device_planes(trace)
    return planes[sorted(planes)[0]] if planes else {}


def module_durations_s(trace: dict, pattern: str):
    """Durations (seconds) of the program runs whose module name matches,
    on the first device, whole runs inside the window only."""
    t0, t1 = window_of(trace)
    rx = re.compile(pattern)
    return [dur / 1e9 for name, start, dur
            in _first_plane(trace).get(MODULES_LINE, [])
            if rx.search(name) and start >= t0 and start + dur <= t1]


def op_time_s(trace: dict, pattern: str, within_modules: str = None):
    """Summed device time (seconds) of the operations whose name matches,
    on the first device inside the window; with ``within_modules`` only
    those that ran inside a whole run of a matching program. Returns
    (seconds, number of events)."""
    t0, t1 = window_of(trace)
    rx = re.compile(pattern)
    plane = _first_plane(trace)
    ops = [e for e in plane.get(OPS_LINE, [])
           if rx.search(e[0]) and e[1] >= t0 and e[1] + e[2] <= t1]
    if within_modules is not None:
        mrx = re.compile(within_modules)
        runs = union([m for m in plane.get(MODULES_LINE, [])
                      if mrx.search(m[0]) and m[1] >= t0
                      and m[1] + m[2] <= t1])
        starts = [a for a, _ in runs]
        inside = []
        for e in ops:
            i = bisect.bisect_right(starts, e[1]) - 1
            if i >= 0 and e[1] + e[2] <= runs[i][1]:
                inside.append(e)
        ops = inside
    return sum(e[2] for e in ops) / 1e9, len(ops)


def leaf_ops(events):
    """The events that hold no other event: a ``while`` or a call spans
    the operations of its body, which are on the same line."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, start, dur) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[1] < start + dur \
                and nxt[1] + nxt[2] <= start + dur and dur > 0:
            continue                      # the next event lies inside
        out.append([name, start, dur])
    return out


def top_ops(trace: dict, n: int = 10):
    """[[name, seconds]] of the operations with most device time in the
    window, bodies counted and not the loops around them; the run number
    in a name (``fusion.123``) stays, since the next issue reads these
    names as the trace gives them."""
    t0, t1 = window_of(trace)
    total = {}
    for name, _, dur in leaf_ops(
            clip(_first_plane(trace).get(OPS_LINE, []), t0, t1)):
        total[name] = total.get(name, 0) + dur
    best = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in best]


def idle_gaps(trace: dict, n: int = 10):
    """[[label, seconds]]: the device's idle time inside the window,
    summed by what the host was doing in each gap (the driver's span that
    covers most of it, ``unlabelled`` where none does), largest first."""
    t0, t1 = window_of(trace)
    busy = union(clip(_first_plane(trace).get(OPS_LINE, []), t0, t1))
    edges = [t0] + [t for iv in busy for t in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [s for s in host_spans(trace) if s[0] != HOST_PREFIX + "window"]
    total, k = {}, 0
    for a, b in gaps:
        # spans are sorted by start; skip those that ended before the gap
        while k < len(spans) and spans[k][1] + spans[k][2] <= a:
            k += 1
        cover, j = {}, k
        while j < len(spans) and spans[j][1] < b:
            name, start, dur = spans[j]
            part = min(b, start + dur) - max(a, start)
            if part > 0:
                cover[name] = cover.get(name, 0) + part
            j += 1
        label = max(cover, key=cover.get)[len(HOST_PREFIX):] if cover \
            else "unlabelled"
        total[label] = total.get(label, 0) + (b - a)
    best = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[label, ns / 1e9] for label, ns in best]
