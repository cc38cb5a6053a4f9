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


def device_trace_summary(trace_dir: str) -> dict:
    """Summarize the DEVICE lanes of a jax.profiler (xprof) capture —
    the hardware proof that the §5.1 profiler row records real TPU
    kernel timelines, not just host spans (the reference's CudaTracer
    analog: /root/reference/paddle/fluid/platform/profiler/
    cuda_tracer.h). Parses the trace.json.gz the xprof plugin writes
    next to the .xplane.pb and returns {"device_lanes": [...],
    "device_events": N, "top_kernels": [...]} ({} lanes / 0 events on
    a host-only capture)."""
    import glob
    import gzip
    from collections import Counter

    out = {"device_lanes": [], "device_events": 0, "top_kernels": []}
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz")))
    if not paths:
        return out
    tr = json.loads(gzip.open(paths[-1]).read())
    evs = tr.get("traceEvents", [])
    procs = {e["pid"]: e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e.get("name") == "process_name"
             and "name" in e.get("args", {})}
    # /device:CUSTOM:* planes (e.g. the "Megascale Trace" libtpu adds
    # to every capture once the TPU library is loaded, chip or not)
    # are host-side bookkeeping, not accelerator timelines
    dev_pids = {pid for pid, nm in procs.items()
                if "/device:" in nm and "CPU" not in nm
                and "CUSTOM" not in nm}
    kernels = Counter()
    n = 0
    for e in evs:
        if e.get("ph") == "X" and e.get("pid") in dev_pids:
            n += 1
            kernels[e.get("name", "?")] += 1
    out["device_lanes"] = sorted(procs[p] for p in dev_pids)
    out["device_events"] = n
    out["top_kernels"] = [k for k, _ in kernels.most_common(5)]
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
