"""paddle_tpu.profiler — profiling with scheduled windows + Chrome export.

TPU-native re-imagination of the reference profiler
(/root/reference/python/paddle/profiler/profiler.py:346 Profiler,
:117 make_scheduler, :215 export_chrome_tracing): host spans are recorded
by the native C++ tracer (paddle_tpu/core/cc/tracer.cc — the HostTracer
analog, ~40ns/span instead of CUPTI); device-side tracing delegates to
``jax.profiler`` (xprof), the TPU equivalent of the reference's CudaTracer
(SURVEY.md §5.1). Both merge into one Chrome trace.

API parity:
    prof = Profiler(targets=[ProfilerTarget.CPU, ProfilerTarget.TPU],
                    scheduler=make_scheduler(closed=1, ready=1, record=3),
                    on_trace_ready=export_chrome_tracing('./log'))
    prof.start(); ...; prof.step(); ...; prof.stop()
    prof.summary()
plus RecordEvent spans and the throughput ``benchmark`` step timer
(timer.py analog).
"""
from __future__ import annotations

import json
import os
import re
import threading
import time
from enum import Enum
from typing import Callable, Dict, List, Optional

__all__ = [
    "Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
    "make_scheduler", "export_chrome_tracing", "export_protobuf",
    "load_profiler_result", "SummaryView", "benchmark",
    "device_trace_summary",
]


# -- reading a jax.profiler capture -------------------------------------------

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
# the roots of the program's own spans on the host plane
# (utils/telemetry.span in jit.TrainStep and ServingEngine.step)
PROGRAM_SPANS = ("train_step", "engine.")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_LAYER = re.compile(r"\blayer\d+/")


def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: varints
    as ints, length-delimited fields as memoryviews, fixed ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire, val = key & 7, None
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, wire, val


def _each(buf, num, wire=2):
    return (v for n, w, v in _fields(buf) if n == num and w == wire)


def _first(buf, num, wire=2, default=None):
    return next(_each(buf, num, wire), default)


def _text(buf, num) -> str:
    return bytes(_first(buf, num, default=b"")).decode()


def _op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {operation's event name: its ``tf_op`` stat}}.

    ``jax.profiler.ProfileData`` gives an event's own stats but not
    those of its XEventMetadata, and that is where libtpu (0.0.34, jax
    0.9.0) keeps the HLO ``op_name`` — the ``jax.named_scope`` path —
    as the stat ``tf_op``; the event's name is the HLO line WITHOUT its
    ``metadata={op_name=...}``. So this walks the file's wire format
    for just that: XSpace.planes=1; XPlane.name=2, .event_metadata=4,
    .stat_metadata=5 (maps: key=1, value=2); XEventMetadata.name=2,
    .stats=5; XStatMetadata.name=2; XStat.metadata_id=1, .str_value=5,
    .ref_value=7 (tsl/profiler/protobuf/xplane.proto)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for plane in _each(space, 1):
        name = _text(plane, 2)
        if not DEVICE_PLANE.match(name):
            continue
        stat_names = {
            _first(entry, 1, 0, 0): _text(_first(entry, 2, default=b""), 2)
            for entry in _each(plane, 5)}
        scopes = out.setdefault(name, {})
        for entry in _each(plane, 4):
            meta = _first(entry, 2, default=b"")
            for stat in _each(meta, 5):
                if stat_names.get(_first(stat, 1, 0)) != "tf_op":
                    continue
                ref = _first(stat, 7, 0)
                scope = _text(stat, 5) or stat_names.get(ref)
                if scope:
                    scopes[_text(meta, 2)] = scope
    return out


def scope_of(tf_op: str) -> str:
    """``jit(step)/fwd_bwd/transpose(jvp(layer1/mlp))/dot_general:`` ->
    ``fwd_bwd/transpose(jvp(layer*/mlp))``: the program's name and the
    primitive go, the layer index is folded, and ``transpose(jvp(...))``
    stays to mark the backward half."""
    parts = tf_op.rstrip(":").split("/")
    if parts and parts[0].startswith(("jit(", "pjit(")):
        parts = parts[1:]
    # the primitive is the last part; a part that closes a wrapper opened
    # earlier ("mlp))") belongs to the scope
    if parts and parts[-1].count(")") <= parts[-1].count("("):
        parts = parts[:-1]
    return _LAYER.sub("layer*/", "/".join(parts)) or "(no scope)"


def kernel_of(event_name: str) -> Optional[str]:
    """The ``name=`` of the Pallas kernel an operation's event ran, the
    instance number folded (``%flash_fwd.2 = ... custom_call_target=
    "tpu_custom_call"`` -> ``flash_fwd``); None for any other op."""
    m = _TARGET.search(event_name)
    if m is None or m.group(1) != "tpu_custom_call":
        return None
    return event_name.split(" = ", 1)[0].lstrip("%").rsplit(".", 1)[0]


def _leaf_events(events):
    """Events that hold no other event of their line: a ``while`` or a
    call spans the operations of its body."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, ev in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and ev[2] > 0 and nxt[1] < ev[1] + ev[2] \
                and nxt[1] + nxt[2] <= ev[1] + ev[2]:
            continue
        out.append(ev)
    return out


def _ranked(total: dict, n: int):
    return [[k, ns / 1e9, cnt] for k, (ns, cnt)
            in sorted(total.items(), key=lambda kv: -kv[1][0])[:n]]


def device_trace_summary(trace_dir: str, top: int = 20) -> dict:
    """Where the device's time went in a jax.profiler (xprof) capture,
    by the names the program gave: reads the newest ``.xplane.pb``
    under ``trace_dir`` through ``jax.profiler.ProfileData`` and returns

    - ``device_lanes`` / ``device_events``: the ``/device:TPU:n`` planes
      and their operation events ([] / 0 on a host-only capture);
    - ``by_scope``: [[scope, seconds, events]] of the first device, by
      ``jax.named_scope`` path (``scope_of``), loop bodies counted and
      not the loops around them;
    - ``by_kernel``: the same by Pallas kernel ``name=``;
    - ``top_ops``: [[op, scope, seconds, events]] of single operations
      (``fusion.318``), which is how a run number gets a layer's name;
    - ``top_kernels``: names of the most frequent operations;
    - ``host_spans``: [[name, count, total seconds, median seconds]] of
      the program's own spans (``PROGRAM_SPANS``) on the host plane."""
    import glob
    import statistics
    from collections import Counter

    out = {"device_lanes": [], "device_events": 0, "top_kernels": [],
           "by_scope": [], "by_kernel": [], "top_ops": [],
           "host_spans": []}
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return out
    from jax.profiler import ProfileData
    data = ProfileData.from_file(paths[-1])
    scopes = _op_scopes(paths[-1])
    calls = Counter()
    host: Dict[str, List[float]] = {}
    first = None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            out["device_lanes"].append(plane.name)
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events]
                out["device_events"] += len(evs)
                calls.update(e[0].split(" = ", 1)[0].lstrip("%")
                             for e in evs)
                if first is None or plane.name < first[0]:
                    first = (plane.name, evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_SPANS):
                        host.setdefault(e.name, []).append(
                            e.duration_ns / 1e9)
    out["device_lanes"].sort()
    out["top_kernels"] = [k for k, _ in calls.most_common(5)]
    out["host_spans"] = [[k, len(v), sum(v), statistics.median(v)]
                         for k, v in sorted(host.items())]
    if first is None:
        return out
    by_scope, by_kernel, by_op = {}, {}, {}
    names = scopes.get(first[0], {})

    def add(total, key, dur):
        ns, cnt = total.get(key, (0, 0))
        total[key] = (ns + dur, cnt + 1)

    for name, _, dur in _leaf_events(first[1]):
        scope = scope_of(names[name]) if name in names else "(no scope)"
        add(by_scope, scope, dur)
        add(by_op, (name.split(" = ", 1)[0].lstrip("%"), scope), dur)
        kernel = kernel_of(name)
        if kernel is not None:
            add(by_kernel, kernel, dur)
    out["by_scope"] = _ranked(by_scope, top)
    out["by_kernel"] = _ranked(by_kernel, top)
    out["top_ops"] = [[op, scope, s, n] for (op, scope), s, n
                      in _ranked(by_op, top)]
    return out


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1     # accepted for API compat; maps to the accelerator
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3  # last record step of a window


def make_scheduler(*, closed: int, ready: int, record: int,
                   repeat: int = 0, skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Window scheduler parity
    (/root/reference/python/paddle/profiler/profiler.py:117): step_num →
    state, cycling [closed, ready, record] after skip_first steps."""
    period = closed + ready + record
    if record <= 0:
        raise ValueError("record span must be positive")

    def fn(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return fn


def _default_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready callback writing a chrome://tracing JSON file."""
    seq = {"n": 0}

    def handle(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        # counter suffix: two windows can close within the same millisecond
        path = os.path.join(
            dir_name, f"{name}_time_{int(time.time() * 1000)}"
                      f"_{seq['n']}.paddle_trace.json")
        seq["n"] += 1
        prof._export_chrome(path)
        prof._last_export_path = path
    return handle


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    """Reference exports a protobuf dump; here the same event list is
    serialized as JSON-lines (stable, dependency-free)."""
    def handle(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}.paddle_trace.jsonl")
        with open(path, "w") as f:
            for ev in prof._events:
                f.write(json.dumps(ev) + "\n")
        prof._last_export_path = path
    return handle


def load_profiler_result(path: str) -> List[dict]:
    with open(path) as f:
        if path.endswith(".jsonl"):
            return [json.loads(l) for l in f if l.strip()]
        data = json.load(f)
        return data.get("traceEvents", data)


# ---------------------------------------------------------------------------
# RecordEvent
# ---------------------------------------------------------------------------

_active_profiler: Optional["Profiler"] = None


class RecordEvent:
    """User-instrumented span (parity: event_tracing RecordEvent). Usable
    as context manager or begin()/end(). Costs two clock reads + one
    lock-free native ring write when a profiler is recording; no-op
    otherwise."""

    def __init__(self, name: str, event_type: str = "UserDefined"):
        self.name = name
        self.event_type = event_type
        self._t0 = None
        self._prof = None

    def begin(self):
        prof = _active_profiler
        if prof is not None and prof._recording:
            self._prof = prof
            self._t0 = prof._tracer.now_ns() if prof._tracer else \
                time.perf_counter_ns()
        return self

    def end(self):
        prof = self._prof
        if prof is None or self._t0 is None:
            return
        if prof._tracer is not None:
            prof._tracer.end(prof._tracer.intern(self.name), self._t0)
        else:
            prof._py_events.append(
                (self.name, 0, self._t0, time.perf_counter_ns()))
        self._prof = None

    def __enter__(self):
        return self.begin()

    def __exit__(self, *exc):
        self.end()
        return False


# ---------------------------------------------------------------------------
# Profiler
# ---------------------------------------------------------------------------

class Profiler:
    def __init__(self, *, targets: Optional[list] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 timer_only: bool = False, record_shapes: bool = False,
                 profile_memory: bool = False, with_flops: bool = False):
        if isinstance(scheduler, (tuple, list)):  # (start, end) batch range
            start, end = scheduler
            scheduler = make_scheduler(closed=max(start, 0), ready=0,
                                       record=end - start, repeat=1)
        self.scheduler = scheduler or _default_scheduler
        self.on_trace_ready = on_trace_ready
        self.targets = targets or [ProfilerTarget.CPU]
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._recording = False
        self._events: List[dict] = []      # current window's chrome events
        self._delivered_events: List[dict] = []  # past windows (delivered)
        self._py_events: list = []         # fallback span store
        self._tracer = None
        self._device_trace_dir = None
        self._last_export_path = None
        self._step_info = _StepInfo()
        if not timer_only:
            try:
                from ..core.native import NativeTracer
                self._tracer = NativeTracer()
            except Exception:
                self._tracer = None

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        global _active_profiler
        _active_profiler = self
        self._step_info.reset()
        self.current_state = self.scheduler(self.step_num)
        self._apply_state(self.current_state)

    def stop(self):
        global _active_profiler
        if self._recording:
            self._recording = False  # before _drain: tracer must disable
            self._drain()
            self._stop_device_trace()
        if self.on_trace_ready is not None and self._events:
            self.on_trace_ready(self)
            self._delivered_events.extend(self._events)
            self._events = []  # delivered — don't re-export on next window
        _active_profiler = None

    def step(self, num_samples: Optional[int] = None):
        """Advance one training step; applies the scheduler transition."""
        self._step_info.step(num_samples)
        if self._recording:
            self._mark_step_boundary()
        prev = self.current_state
        self.step_num += 1
        self.current_state = self.scheduler(self.step_num)
        # RECORD_AND_RETURN marks the window's last step: deliver even if
        # the next window starts immediately (closed=0, ready=0)
        if prev == ProfilerState.RECORD_AND_RETURN or (
                prev == ProfilerState.RECORD
                and self.current_state not in (
                    ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)):
            # window closed → deliver trace
            self._recording = False  # before _drain: tracer must disable
            self._drain()
            self._stop_device_trace()
            if self.on_trace_ready is not None:
                self.on_trace_ready(self)
                self._delivered_events.extend(self._events)
                self._events = []  # each window exports only its own spans
        self._apply_state(self.current_state)

    def _apply_state(self, st: ProfilerState):
        if st in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            if not self._recording:
                self._recording = True
                if self._tracer is not None:
                    self._tracer.enable(True)
                self._start_device_trace()

    @property
    def device_trace_dir(self):
        """Directory of the device (xprof) capture for the current or
        last recording window; None when no device target was traced.
        Feed it to device_trace_summary() for the TPU-lane proof."""
        return self._device_trace_dir

    # -- device (xprof) ----------------------------------------------------
    def _start_device_trace(self):
        if not any(t in (ProfilerTarget.TPU, ProfilerTarget.GPU)
                   for t in self.targets):
            return
        try:
            import jax
            self._device_trace_dir = f"/tmp/paddle_tpu_xprof_{os.getpid()}_" \
                                     f"{self.step_num}"
            jax.profiler.start_trace(self._device_trace_dir)
        except Exception:
            self._device_trace_dir = None

    def _stop_device_trace(self):
        if self._device_trace_dir is None:
            return
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:
            pass

    # -- event collection --------------------------------------------------
    def _mark_step_boundary(self):
        now = (self._tracer.now_ns() if self._tracer
               else time.perf_counter_ns())
        self._events.append({
            "name": f"ProfileStep#{self.step_num}", "ph": "i",
            "ts": now / 1000.0, "pid": os.getpid(), "tid": 0,
            "s": "g", "cat": "Step",
        })

    def _drain(self):
        if self._tracer is not None:
            spans = self._tracer.drain()
            # keep recording if mid-window (export() can be called while
            # the scheduler is still in a RECORD state)
            self._tracer.enable(self._recording)
        else:
            spans, self._py_events = self._py_events, []
        for name, tid, t0, t1 in spans:
            self._events.append({
                "name": name, "ph": "X", "ts": t0 / 1000.0,
                "dur": (t1 - t0) / 1000.0, "pid": os.getpid(),
                "tid": tid, "cat": "Host",
            })

    def _export_chrome(self, path: str):
        with open(path, "w") as f:
            json.dump({"traceEvents": self._events,
                       "displayTimeUnit": "ms"}, f)

    def export(self, path: str, format: str = "json"):
        self._drain()
        self._export_chrome(path)

    @property
    def events(self) -> List[dict]:
        """All captured events — delivered windows + the current one."""
        return self._delivered_events + self._events

    # -- summaries ---------------------------------------------------------
    def summary(self, sorted_by=None, op_detail: bool = True,
                thread_sep: bool = False, time_unit: str = "ms") -> str:
        """Aggregated span table (profiler_statistic.py analog)."""
        stats: Dict[str, List[float]] = {}
        for ev in self.events:
            if ev.get("ph") != "X":
                continue
            stats.setdefault(ev["name"], []).append(ev["dur"] / 1000.0)
        unit = {"s": 1e-3, "ms": 1.0, "us": 1e3}.get(time_unit, 1.0)
        rows = []
        for name, durs in sorted(stats.items(),
                                 key=lambda kv: -sum(kv[1])):
            tot = sum(durs) * unit
            rows.append((name, len(durs), tot, tot / len(durs),
                         max(durs) * unit, min(durs) * unit))
        header = f"{'Name':<40}{'Calls':>8}{'Total(' + time_unit + ')':>14}" \
                 f"{'Avg':>12}{'Max':>12}{'Min':>12}"
        lines = [header, "-" * len(header)]
        for name, calls, tot, avg, mx, mn in rows:
            lines.append(f"{name[:39]:<40}{calls:>8}{tot:>14.3f}"
                         f"{avg:>12.3f}{mx:>12.3f}{mn:>12.3f}")
        lines.append("-" * len(header))
        lines.append(self._step_info.summary())
        return "\n".join(lines)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class SummaryView(Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6


# ---------------------------------------------------------------------------
# benchmark step timer — reference timer.py (ips logging used by
# hybrid-parallel training loops)
# ---------------------------------------------------------------------------

class _StepInfo:
    def __init__(self):
        self.reset()

    def reset(self):
        self._t0 = time.perf_counter()
        self._last = self._t0
        self._steps = 0
        self._samples = 0
        self._step_times: List[float] = []

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        self._step_times.append(now - self._last)
        self._last = now
        self._steps += 1
        if num_samples:
            self._samples += num_samples

    @property
    def ips(self) -> float:
        elapsed = self._last - self._t0
        if elapsed <= 0:
            return 0.0
        if self._samples:
            return self._samples / elapsed
        return self._steps / elapsed

    def summary(self) -> str:
        if not self._step_times:
            return "no steps recorded"
        import numpy as np
        st = np.asarray(self._step_times[1:] or self._step_times)
        what = "samples/s" if self._samples else "steps/s"
        return (f"steps: {self._steps}  avg step: {st.mean()*1000:.2f}ms  "
                f"p50: {np.percentile(st, 50)*1000:.2f}ms  "
                f"throughput: {self.ips:.2f} {what}")


class _Benchmark:
    """paddle.profiler.benchmark() parity — global step timer usable
    without a Profiler instance."""

    def __init__(self):
        self._info = _StepInfo()
        self._lock = threading.Lock()

    def begin(self):
        self._info.reset()

    def step(self, num_samples: Optional[int] = None):
        with self._lock:
            self._info.step(num_samples)

    def end(self):
        return self._info.summary()

    def speed_average(self) -> float:
        return self._info.ips

    def step_info(self, unit=None) -> str:
        return self._info.summary()


_benchmark = _Benchmark()


def benchmark() -> _Benchmark:
    return _benchmark
