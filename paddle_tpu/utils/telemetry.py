"""Serving telemetry: span tracing, a flight-recorder ring buffer with
Perfetto export, and a unified metrics registry (ISSUE 12).

The engine composes six subsystems inside ONE device program per step
(PRs 5-11), so host-side visibility is the scarce resource: everything
interesting happens between two dispatches. This module is the
host-side answer — three small, allocation-light primitives every
serving subsystem shares:

- ``Tracer``: per-request SPANS (queued → admitted → prefill chunk i →
  splice-wait → decode → preempt/recompute → migrate →
  done/aborted/failed, each carrying req_id/tenant/replica attributes)
  and per-step EVENTS (dispatch width bucket / rows / tokens, retry,
  injected fault, breaker strike), held in a bounded FLIGHT-RECORDER
  ring buffer (old records fall off; ``dropped`` counts them) with
  Chrome-trace/Perfetto JSON export (``Tracer.export(path)``). A
  request is ONE async span for its whole life — the trace id
  propagates through preemption-recompute and cross-replica migration
  (``ServingEngine.adopt_request(trace_id=...)``), so a migrated
  request renders as a single continuous span crossing two replica
  process tracks in Perfetto.
- ``MetricsRegistry``: counters / gauges / fixed-bucket histograms.
  The engine/fleet/cache/chaos ``stats()`` dicts publish into it under
  namespaced keys ("engine.preemptions", "fleet.failovers", ...), so
  the registry is the unified cross-subsystem view and the per-call
  dicts are views over the same numbers (parity is pinned by
  tests/test_telemetry.py); span durations and ITL/TTFT/latency
  samples additionally feed fixed-bucket histograms live.
- ``Reservoir``: seeded Algorithm-R uniform sampling — the bound on
  the raw per-token ITL sample aggregation in ServingEngine.stats() /
  Router.stats() (exact below capacity, p50/p99-within-tolerance
  above it).

The program observatory (ISSUE 14) adds the PROGRAM-level half — the
requests were observable, the compiled programs the engine lives on
were not:

- ``CompileWatch``: the runtime twin of flightcheck's static FC2xx
  recompilation rules. Every serving program family registers its
  jitted callable; after each dispatch the engine asks the watch to
  compare the jit cache size against its ledger — growth IS a
  trace+lower+compile, recorded as an explicit ``compile`` span in the
  trace (family, operand-shape signature, wall; XLA
  ``cost_analysis()``/``memory_analysis()`` flops/bytes when
  ``analyze=True`` and the jax version exposes them) and counted in
  the registry. ``seal()`` declares the program set complete (after
  warmup): ANY later compile increments ``unexpected_recompiles`` and
  fires an ``unexpected_recompile`` event carrying the offending
  signature — a silent mid-serving XLA retrace stops being an
  unexplained ITL spike and becomes an assertable gate failure.
  Detection reads only the jit cache size (two host attribute reads
  per dispatch), so the steady state pays nothing.
- counter tracks: ``Tracer.counter(name, value, pid)`` records gauge
  samples that export as Perfetto ``ph: "C"`` counter events, so
  resource timelines (running slots, free/cached blocks, queue depth,
  in-flight chunks, acceptance EMA, per-replica load) render next to
  the request spans.
- ``SLOPolicy`` / ``SLOMonitor``: declared per-class latency targets
  (ttft/itl pXX) evaluated over multi-duration sliding windows with
  SRE-style burn rates (observed violation fraction over the allowed
  error budget); surfaced through ``stats()["slo"]`` and the Router's
  per-replica headroom rollup — the input SLO-aware routing needs.
- ``MetricsRegistry.to_openmetrics()`` / ``openmetrics_text()``: a
  jax-free OpenMetrics/Prometheus text exporter over the registry
  snapshot (``tools/metrics_export.py`` runs it standalone over an
  exported trace).

Spans inside the program (ISSUE 27) are ONE primitive,
``span(name, **attrs)``: a context manager that always enters a
``jax.profiler.TraceAnnotation`` (a TraceMe on the profiler's host
plane, attrs as its stats) and, WHILE SOMEONE LISTENS, appends one span
record to a ``Tracer`` ring with its own ``id``, the ``parent`` id of
the enclosing open span on this thread (the span that caused it) and
the ``step`` its unit of work shares (a trainer / engine step index,
inherited by children). Someone listens when a ``Tracer`` was attached
explicitly (``tracer=``) or a jax profiler session is live
(``TraceAnnotation.is_enabled()``: ``jax.profiler.start_trace`` /
``trace()``, ``paddle_tpu.profiler.Profiler``, the benchmark's
``--trace 1``); in the second case records go to ``default_tracer()``,
a process-wide ring that outlives the engine or trainer that wrote to
it and so covers exactly the interval the device trace covers. There
is no flag: starting the profiler is what switches the program's spans
on. ``TrainStep`` (``train_step`` + ``.build/.args/.dispatch/.rebind``)
and ``ServingEngine.step`` (``engine.step`` + ``.deadlines/.admit/
.plan/.dispatch/.collect/.deliver``) are instrumented with it.

One clock: the ring stamps ``time.perf_counter()``, the xplane stamps
nanoseconds of the profiler's clock, and every span is in both. Any
span present in both gives the offset (``xplane start_ns - ring ts *
1e9``); the benchmark's ``bench:window`` annotation does, because the
harness reads ``perf_counter`` right after entering it. Off cost, per
span, on this repo's CPU host (jax 0.9.0): one ``is_enabled()`` (0.02
us), one TraceMe enter/exit (0.36 us) and the Python object around them,
0.9 us in all (1.7 us with three attrs), nothing appended; recorded into
a ring it is 4.6 us (the registry's counter and histogram included).

Compile path: ``jax.monitoring`` listeners, registered once on first
use, turn jax's own trace / lower / backend-compile / cache-load
durations into ``compile.*`` events in the default ring ALWAYS (they
fire only when something compiles, so the steady state pays nothing)
and into the default registry's ``compile.trace_s / lower_s /
backend_s / cache_load_s / cache_hits / cache_requests`` counters. In
jax 0.9.0 ``backend_compile_duration`` wraps ``compile_or_get_cached``,
so a cache load is INSIDE ``backend_s``: ``cache_load_s`` is its part
and is never added to it. A jitted function traced inside another's
trace fires its own trace event inside the outer one's interval; each
event carries ``self_s`` (its seconds minus the events nested in it),
and the counters add ``self_s``, so sums are wall seconds.

Overhead contract: ``tracer=None`` (the default everywhere) is a
BITWISE no-op — every hook is behind an ``if tracer is not None``
guard, no PRNG key is drawn, no device call is made, no schedule array
changes. Enabled, the hot path appends small dicts to a deque and
never touches a traced array or forces a host sync (the tracer reads
only host-side scheduler state — flightcheck's FC301 family stays at
zero findings over this module and its call sites). What the enabled
ring costs a training step is in PERF.md (PR 27); a serving step's cost
is not measured on the chip.

Export format: Chrome Trace Event JSON (the ``traceEvents`` array
form), loadable by Perfetto (ui.perfetto.dev) and chrome://tracing.
Request lifecycles are nestable async events (``ph: "b"/"e"``, matched
on ``cat + id`` across process tracks); per-phase slices are complete
events (``ph: "X"`` with ``ts``/``dur``); per-step events are instants
(``ph: "i"``). Engine events land on ``pid = replica_id``; fleet-level
records (routing, breaker, migration, the request async spans) land on
the dedicated ``FLEET_PID`` track.
"""
from __future__ import annotations

import itertools
import json
import threading
import time
import warnings
from bisect import bisect_right
from collections import deque
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.monitoring
import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "MetricsRegistry", "Reservoir", "CompileWatch",
           "SLOPolicy", "SLOMonitor", "FLEET_PID",
           "DEFAULT_TIME_BUCKETS_S", "openmetrics_text", "span",
           "default_tracer", "listening", "compile_seconds"]

# the pid Chrome-trace track fleet-level records render on (routing,
# breaker transitions, migration, request async spans); engine records
# use pid = replica_id (0 for a single engine), so the two can never
# collide for any plausible fleet size
FLEET_PID = 1000

# fixed histogram buckets for second-valued observations (ITL, TTFT,
# latency, span durations): roughly log-spaced 0.5 ms .. 60 s
DEFAULT_TIME_BUCKETS_S = (
    0.0005, 0.001, 0.002, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5,
    1.0, 2.0, 5.0, 10.0, 30.0, 60.0)


class Reservoir:
    """Seeded Algorithm-R reservoir: a bounded uniform sample of an
    unbounded stream. Exact (every sample retained, in order) while the
    stream is <= k items; beyond that each seen item has equal
    probability k/n of being retained, so quantiles stay within
    sampling tolerance while memory is O(k). Deterministic: the same
    seed + the same stream reproduces the same sample (the RNG is
    private — engine PRNG streams are untouched)."""

    def __init__(self, k: int = 4096, seed: int = 0):
        self.k = int(k)
        self._rng = np.random.RandomState(seed)
        self.samples: List[float] = []
        self.n = 0                      # items seen (>= len(samples))

    def append(self, x: float):
        if self.n < self.k:
            self.samples.append(float(x))
        else:
            j = int(self._rng.randint(0, self.n + 1))
            if j < self.k:
                self.samples[j] = float(x)
        self.n += 1

    def extend(self, xs: Sequence[float]):
        for x in xs:
            self.append(x)

    def __len__(self) -> int:
        return len(self.samples)

    def __iter__(self):
        return iter(self.samples)

    @staticmethod
    def merge(parts, k: int = 4096, seed: int = 0) -> List[float]:
        """Combine several (samples, n_seen) parts — Reservoir objects
        or (list, n) tuples — into ONE bounded sample whose composition
        is proportional to each part's true stream size (concatenating
        raw reservoirs would over-weight small streams). Exact
        concatenation when everything fits in k."""
        norm = []
        for p in parts:
            if isinstance(p, Reservoir):
                norm.append((p.samples, p.n))
            else:
                s, n = p
                norm.append((list(s), int(n)))
        norm = [(s, n) for s, n in norm if s]
        total = sum(n for _, n in norm)
        if total <= k:
            return [x for s, _ in norm for x in s]
        rng = np.random.RandomState(seed)
        out: List[float] = []
        for s, n in norm:
            want = max(1, int(round(k * n / total)))
            if want >= len(s):
                out.extend(s)
            else:
                idx = rng.choice(len(s), size=want, replace=False)
                out.extend(s[i] for i in idx)
        return out


class _Histogram:
    """Fixed-bucket histogram: counts[i] = observations <= buckets[i]
    boundary (last slot is the overflow), plus n/sum for means."""

    def __init__(self, buckets: Sequence[float]):
        self.buckets = tuple(float(b) for b in buckets)
        self.counts = [0] * (len(self.buckets) + 1)
        self.n = 0
        self.sum = 0.0

    def observe(self, v: float, n: int = 1):
        self.counts[bisect_right(self.buckets, float(v))] += int(n)
        self.n += int(n)
        self.sum += float(v) * int(n)

    def snapshot(self) -> dict:
        return {"buckets": list(self.buckets),
                "counts": list(self.counts),
                "n": self.n, "sum": self.sum,
                "mean": (self.sum / self.n) if self.n else None}


class MetricsRegistry:
    """Unified counters/gauges/histograms across engine, fleet, cache
    and chaos. Two feeding paths:

    - live: ``inc(name)`` from the tracer's event/span hooks (event
      counts, span-duration histograms) — cheap dict ops;
    - published: ``publish(prefix, stats_dict)`` mirrors a subsystem's
      ``stats()`` dict under namespaced keys (ints -> counters, floats
      -> gauges; None/bool/nested values skipped), making the stats
      dicts views over the registry — ``registry.value("engine.X") ==
      engine.stats()["X"]`` for every numeric key (tested).

    Thread-safety: all dict membership mutations and ``snapshot()``
    take one lock, so a watchdog-thread export can never hit a
    dictionary-changed-during-iteration crash while the engine thread
    records a first-seen event/histogram name. Individual histogram
    ``observe`` calls stay lockless (they mutate an existing object in
    place); a concurrent snapshot may read a histogram mid-update,
    which is tolerable for a post-mortem."""

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.histograms: Dict[str, _Histogram] = {}
        self._sources: List[tuple] = []
        self._warned: set = set()

    def add_source(self, prefix: str, ref):
        """A subsystem whose numbers live elsewhere (a layer's counters
        on the device) and are asked for when the registry is exported:
        ``ref`` is a weak reference (``weakref.ref`` / ``WeakMethod``) to
        a callable returning a stats dict, which ``snapshot`` publishes
        under ``prefix`` (``value`` reads what was last published and asks
        nobody: a source may wait for the device). Of two live sources
        with one prefix the later one's numbers stand. What was last
        published stays once the subsystem is gone; a source that raises
        is skipped, with one warning."""
        with self._lock:
            self._sources.append((prefix, ref))

    def _pull(self):
        with self._lock:
            sources = list(self._sources)
        for source in sources:
            prefix, ref = source
            fn = ref()
            if fn is None:
                with self._lock:
                    if source in self._sources:
                        self._sources.remove(source)
                continue
            try:
                stats = fn()
            except Exception as e:          # telemetry never fails the program
                if prefix not in self._warned:
                    self._warned.add(prefix)
                    warnings.warn(f"metrics source {prefix!r} raised "
                                  f"{e!r}; its last numbers stay",
                                  RuntimeWarning)
                continue
            self.publish(prefix, stats)

    def inc(self, name: str, n: float = 1):
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def set_gauge(self, name: str, v: float):
        with self._lock:
            self.gauges[name] = float(v)

    def histogram(self, name: str,
                  buckets: Sequence[float] = DEFAULT_TIME_BUCKETS_S
                  ) -> _Histogram:
        h = self.histograms.get(name)
        if h is None:
            with self._lock:
                h = self.histograms.get(name)
                if h is None:
                    h = self.histograms[name] = _Histogram(buckets)
        return h

    def publish(self, prefix: str, stats: dict):
        with self._lock:
            for key, v in stats.items():
                name = f"{prefix}.{key}"
                if v is None:
                    # a stat that went back to None (e.g. percentiles
                    # after clear_finished) must not leave its stale
                    # pre-reset value in the registry/export
                    self.counters.pop(name, None)
                    self.gauges.pop(name, None)
                    continue
                if isinstance(v, bool):
                    continue
                if isinstance(v, (int, np.integer)):
                    self.counters[name] = int(v)
                elif isinstance(v, (float, np.floating)):
                    self.gauges[name] = float(v)

    def value(self, name: str):
        with self._lock:
            if name in self.counters:
                return self.counters[name]
            return self.gauges.get(name)

    def snapshot(self) -> dict:
        self._pull()
        with self._lock:
            return {"counters": dict(self.counters),
                    "gauges": dict(self.gauges),
                    "histograms": {k: h.snapshot()
                                   for k, h in self.histograms.items()}}

    def to_openmetrics(self) -> str:
        """The registry as OpenMetrics/Prometheus text (counters with
        the ``_total`` suffix, gauges, cumulative-bucket histograms,
        terminated by ``# EOF``). Pure host formatting — scrapeable by
        any Prometheus-compatible collector; ``tools/metrics_export.py``
        runs the same formatter over an exported trace's snapshot."""
        return openmetrics_text(self.snapshot())


def _om_name(name: str) -> str:
    """Sanitize a dotted registry name into the OpenMetrics charset
    ([a-zA-Z0-9_:], non-digit first)."""
    s = "".join(ch if (ch.isalnum() and ch.isascii()) or ch in "_:"
                else "_" for ch in str(name))
    if not s or s[0].isdigit():
        s = "_" + s
    return s


def _om_num(v) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return format(f, ".10g")


def openmetrics_text(snapshot: dict) -> str:
    """Format a ``MetricsRegistry.snapshot()`` dict as OpenMetrics /
    Prometheus text exposition. jax-free on purpose: the exporter must
    run anywhere the snapshot JSON does (a metrics sidecar, a laptop
    reading a trace artifact — see tools/metrics_export.py)."""
    lines: List[str] = []
    for name, v in sorted((snapshot.get("counters") or {}).items()):
        n = _om_name(name)
        lines.append(f"# TYPE {n} counter")
        lines.append(f"{n}_total {_om_num(v)}")
    for name, v in sorted((snapshot.get("gauges") or {}).items()):
        n = _om_name(name)
        lines.append(f"# TYPE {n} gauge")
        lines.append(f"{n} {_om_num(v)}")
    for name, h in sorted((snapshot.get("histograms") or {}).items()):
        n = _om_name(name)
        lines.append(f"# TYPE {n} histogram")
        cum = 0
        counts = list(h.get("counts", ()))
        buckets = list(h.get("buckets", ()))
        for b, c in zip(buckets, counts):
            cum += int(c)
            lines.append(f'{n}_bucket{{le="{_om_num(b)}"}} {cum}')
        if counts:
            cum += int(counts[-1])        # the overflow slot
        lines.append(f'{n}_bucket{{le="+Inf"}} {cum}')
        lines.append(f"{n}_sum {_om_num(h.get('sum', 0.0))}")
        lines.append(f"{n}_count {int(h.get('n', 0))}")
    lines.append("# EOF")
    return "\n".join(lines) + "\n"


class CompileWatch:
    """Per-program-family compile ledger + sealed-set retrace sentinel
    (ISSUE 14) — the runtime twin of flightcheck's static FC2xx rules.

    The engine registers every jitted serving program family
    (``register``), then calls ``observe(fn, t0, t1, args)`` after each
    dispatch. Detection is the jit cache size: growth since the last
    observation means that call TRACED+LOWERED+COMPILED (the call wall
    is the compile wall, execution being async), independent of any
    host-side model of what should retrace — a weak-type flip, a dtype
    drift or an unstable cache key is caught exactly like a new shape.
    The offending operand-shape signature is derived lazily (compiles
    only), so the steady state pays two host attribute reads.

    ``seal()`` declares the program set complete — warmup's contract.
    Any compile observed after sealing increments
    ``unexpected_recompiles`` and fires an ``unexpected_recompile``
    tracer event with the signature; chaos legs and the serving bench
    assert the counter stays zero.

    jax-free by duck typing: the jitted callable just needs
    ``_cache_size()`` (and ``lower()`` for the opt-in ``analyze``
    mode); a callable without it simply isn't watched."""

    MAX_RECORDS = 512

    def __init__(self, tracer: Optional["Tracer"] = None,
                 analyze: bool = False):
        self.tracer = tracer
        self.metrics = (tracer.metrics if tracer is not None
                        else MetricsRegistry())
        # analyze=True: on the FIRST observed compile of each family,
        # re-lower abstractly and pull XLA cost/memory analysis
        # (flops / bytes accessed / temp+output bytes) into the compile
        # record. Costs one extra trace+lower+compile per family —
        # off by default so traced production runs keep the <5%
        # overhead contract; tests and one-off investigations opt in.
        self.analyze = bool(analyze)
        self.pid = 0
        self.sealed = False
        self.compiles = 0
        self.unexpected_recompiles = 0
        self.records: List[dict] = []
        self._families: Dict[str, dict] = {}
        self._by_id: Dict[int, str] = {}

    def bind(self, tracer: Optional["Tracer"], pid: int = 0):
        """(Re)attach the tracer/registry sink and the replica pid —
        called by ServingEngine.set_telemetry."""
        self.tracer = tracer
        if tracer is not None:
            self.metrics = tracer.metrics
        self.pid = int(pid)

    @staticmethod
    def _size(jfn) -> int:
        try:
            return int(jfn._cache_size())
        except Exception:       # noqa: BLE001 — unwatchable callable
            return -1

    def register(self, family: str, jfn, **info):
        """Track one jitted program family. ``info`` (decoder build
        fingerprint, tp degree, ...) rides every compile record."""
        self._families[family] = {"fn": jfn, "size": self._size(jfn),
                                  "info": dict(info), "analyzed": False}
        self._by_id[id(jfn)] = family

    def family_of(self, fn) -> Optional[str]:
        return self._by_id.get(id(fn))

    @property
    def families(self) -> List[str]:
        return list(self._families)

    @staticmethod
    def signature_of(args, skip: int = 3, limit: int = 200) -> str:
        """Compact dtype[shape] signature of the VARYING operands —
        the first ``skip`` args (weights, k, v by the engine's calling
        convention) are engine-static and elided."""
        parts: List[str] = []

        def walk(x):
            if isinstance(x, (tuple, list)):
                for y in x:
                    walk(y)
            elif isinstance(x, dict):
                for k in sorted(x):
                    walk(x[k])
            elif hasattr(x, "shape") and hasattr(x, "dtype"):
                shape = "x".join(str(int(d)) for d in x.shape)
                dt = np.dtype(x.dtype).str.lstrip("<>|=")
                parts.append(f"{dt}[{shape}]")

        for a in list(args)[skip:]:
            walk(a)
        sig = ",".join(parts)
        return sig if len(sig) <= limit else sig[:limit] + "..."

    def _analyze(self, fn, args) -> dict:
        """Best-effort AOT lower/compile for XLA cost+memory analysis.
        Duck-typed and fully guarded: a jax version (or a sharded
        program) that refuses any step just yields fewer fields."""
        out: Dict[str, float] = {}
        try:
            lowered = fn.lower(*args)
        except Exception:       # noqa: BLE001 — best-effort contract
            return out
        try:
            ca = lowered.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if isinstance(ca, dict):
                if "flops" in ca:
                    out["flops"] = float(ca["flops"])
                if "bytes accessed" in ca:
                    out["bytes_accessed"] = float(ca["bytes accessed"])
        except Exception:       # noqa: BLE001
            pass
        try:
            compiled = lowered.compile()
            ma = compiled.memory_analysis()
            out["temp_bytes"] = float(
                getattr(ma, "temp_size_in_bytes", 0))
            out["output_bytes"] = float(
                getattr(ma, "output_size_in_bytes", 0))
            out["argument_bytes"] = float(
                getattr(ma, "argument_size_in_bytes", 0))
        except Exception:       # noqa: BLE001
            pass
        return out

    def observe(self, fn, t0: float, t1: float, args=()
                ) -> "tuple[int, int]":
        """Post-dispatch check: did this call grow ``fn``'s jit cache?
        Returns (new_compiles, unexpected_compiles). A cache that
        SHRANK (jax.clear_caches between bench suites) just resyncs."""
        name = self._by_id.get(id(fn))
        if name is None:
            return 0, 0
        fam = self._families[name]
        if fam["size"] < 0:
            return 0, 0
        cur = self._size(fn)
        if cur < 0:
            fam["size"] = -1
            return 0, 0
        prev = fam["size"]
        fam["size"] = cur
        if cur <= prev:
            return 0, 0
        n = cur - prev
        wall = max(0.0, float(t1) - float(t0))
        rec = {"family": name, "signature": self.signature_of(args),
               "wall_s": wall, "sealed": self.sealed}
        # jax's own account of the interval: tracing, lowering and the
        # backend's compile (or its load from the compile cache)
        rec.update(compile_seconds(t0, t1))
        rec.update(fam["info"])
        if self.analyze and not fam["analyzed"]:
            fam["analyzed"] = True
            rec.update(self._analyze(fn, args))
        self.compiles += n
        if len(self.records) < self.MAX_RECORDS:
            self.records.append(rec)
        m = self.metrics
        m.inc("compile.total", n)
        m.inc(f"compile.{name}")
        m.histogram("compile.wall_s").observe(wall)
        if "flops" in rec:
            m.set_gauge(f"compile.{name}.flops", rec["flops"])
        if "bytes_accessed" in rec:
            m.set_gauge(f"compile.{name}.bytes_accessed",
                        rec["bytes_accessed"])
        if self.tracer is not None:
            attrs = {k: v for k, v in rec.items() if k != "wall_s"}
            self.tracer.span("compile", None, t0, t1, pid=self.pid,
                             **attrs)
        unexpected = n if self.sealed else 0
        if unexpected:
            self.unexpected_recompiles += unexpected
            m.inc("compile.unexpected", unexpected)
            if self.tracer is not None:
                self.tracer.event("unexpected_recompile", pid=self.pid,
                                  family=name, signature=rec["signature"])
        return n, unexpected

    def seal(self):
        """Declare the program set complete: resync every family's
        cache size, then flag every later compile as unexpected (the
        runtime FC2xx — asserted zero by chaos legs and the bench)."""
        for fam in self._families.values():
            if fam["size"] >= 0:
                fam["size"] = self._size(fam["fn"])
        self.sealed = True
        self.metrics.set_gauge("compile.sealed", 1.0)
        if self.tracer is not None:
            self.tracer.event("programs_sealed", pid=self.pid,
                              families=len(self._families))


@dataclass
class SLOPolicy:
    """One declared latency objective over a traffic class: "p99 TTFT
    under ``ttft_p99_s`` and p99 ITL under ``itl_p99_s`` for requests
    matched by ``class_selector``" (None targets are unmonitored; a
    None selector matches all traffic). ``class_selector`` receives a
    small attrs dict ({"adapter_id": ..., "priority": ...}) so classes
    can be cut by tenant or priority without the monitor knowing the
    Request type."""
    name: str
    ttft_p99_s: Optional[float] = None
    itl_p99_s: Optional[float] = None
    class_selector: Optional[Callable[[dict], bool]] = None
    quantile: float = 0.99


class SLOMonitor:
    """Sliding-window SLO evaluation with multi-window burn rates.

    Samples arrive timestamped from the engine's collection paths
    (``observe``; ttft once per request, itl per delivered token with a
    count so a T-token chunk is one append). ``evaluate`` computes, per
    policy and metric, the observed quantile plus the BURN RATE of each
    window — (violating fraction) / (allowed fraction, 1 - quantile) —
    the SRE error-budget form: burn 1.0 spends the budget exactly,
    14.4x on a 1h window is the classic page threshold. A policy is
    ``violating`` when both the shortest and longest populated windows
    burn above 1.0 (the multi-window AND: a transient spike or a stale
    long tail alone doesn't page). ``headroom`` is (target - pXX) /
    target over the longest populated window, the per-replica scalar
    the fleet Router rolls up for SLO-aware routing (1.0 = idle/no
    data, negative = violating by that relative margin).

    Deterministic and jax-free: tests drive it with synthetic
    timestamps (``now=``); the engine feeds perf_counter."""

    DEFAULT_WINDOWS_S = (60.0, 300.0, 1800.0)
    METRICS = ("ttft", "itl")

    def __init__(self, policies, windows_s: Optional[Sequence[float]]
                 = None, max_samples: int = 4096):
        if isinstance(policies, SLOPolicy):
            policies = [policies]
        self.policies: List[SLOPolicy] = list(policies)
        names = [p.name for p in self.policies]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate SLO policy names: {names}")
        self.windows = tuple(sorted(
            float(w) for w in (windows_s or self.DEFAULT_WINDOWS_S)))
        if not self.windows or any(w <= 0 for w in self.windows):
            raise ValueError(f"windows_s must be positive: "
                             f"{self.windows}")
        self.max_samples = int(max_samples)
        # (policy, metric) -> deque of (ts, value, count); bounded like
        # the PR-12 reservoirs so unbounded runs stay O(k)
        self._dq: Dict[tuple, deque] = {
            (p.name, m): deque(maxlen=self.max_samples)
            for p in self.policies for m in self.METRICS}

    @staticmethod
    def coerce_policies(slo) -> List[SLOPolicy]:
        """Normalize the ``slo=`` constructor surface (None / one
        policy / a monitor whose policies serve as the template / a
        sequence of policies) into a plain policy list — shared by
        ServingEngine and Router so the accepted forms can't drift."""
        if slo is None:
            return []
        if isinstance(slo, SLOMonitor):
            return list(slo.policies)
        if isinstance(slo, SLOPolicy):
            return [slo]
        return list(slo)

    @staticmethod
    def _target(p: SLOPolicy, metric: str) -> Optional[float]:
        return p.ttft_p99_s if metric == "ttft" else p.itl_p99_s

    def observe(self, metric: str, value: float, attrs: Optional[dict]
                = None, n: int = 1, now: Optional[float] = None):
        if metric not in self.METRICS:
            raise ValueError(f"metric must be one of {self.METRICS}, "
                             f"got {metric!r}")
        now = time.perf_counter() if now is None else float(now)
        for p in self.policies:
            if self._target(p, metric) is None:
                continue
            sel = p.class_selector
            if sel is not None and not sel(attrs or {}):
                continue
            self._dq[(p.name, metric)].append(
                (now, float(value), int(n)))

    def evaluate(self, now: Optional[float] = None) -> dict:
        now = time.perf_counter() if now is None else float(now)
        policies: Dict[str, dict] = {}
        any_viol = False
        heads: List[float] = []
        for p in self.policies:
            metrics: Dict[str, dict] = {}
            p_viol = False
            p_heads: List[float] = []
            for metric in self.METRICS:
                target = self._target(p, metric)
                if target is None:
                    continue
                samples = list(self._dq[(p.name, metric)])
                allowed = max(1e-9, 1.0 - p.quantile)
                wins: Dict[str, dict] = {}
                burns: List[float] = []
                for w in self.windows:
                    vals = [(v, k) for ts, v, k in samples
                            if now - ts <= w]
                    nn = sum(k for _, k in vals)
                    bad = sum(k for v, k in vals if v > target)
                    burn = ((bad / nn) / allowed) if nn else None
                    wins[f"{int(w)}s"] = {
                        "n": nn, "violations": bad,
                        "burn_rate": (round(burn, 4)
                                      if burn is not None else None)}
                    if nn:
                        burns.append(burn)
                pxx = None
                longest = [(v, k) for ts, v, k in samples
                           if now - ts <= self.windows[-1]]
                if longest:
                    arr = np.repeat([v for v, _ in longest],
                                    [k for _, k in longest])
                    pxx = float(np.quantile(arr, p.quantile))
                viol = (len(burns) > 0 and burns[0] > 1.0
                        and burns[-1] > 1.0)
                head = (None if pxx is None
                        else (target - pxx) / target)
                metrics[metric] = {
                    "target_s": target,
                    "p_s": (round(pxx, 6) if pxx is not None else None),
                    "windows": wins, "violating": viol,
                    "headroom": (round(head, 4)
                                 if head is not None else None)}
                p_viol = p_viol or viol
                if head is not None:
                    p_heads.append(head)
            head = min(p_heads) if p_heads else 1.0
            policies[p.name] = {"metrics": metrics,
                                "violating": p_viol,
                                "headroom": round(head, 4)}
            any_viol = any_viol or p_viol
            heads.append(head)
        return {"policies": policies, "violating": any_viol,
                "min_headroom": (round(min(heads), 4)
                                 if heads else 1.0)}

    def reset(self):
        """Drop every window (the clear_finished contract: post-warmup
        stats reflect only real traffic)."""
        for dq in self._dq.values():
            dq.clear()


class Tracer:
    """Flight recorder + span tracer. See the module docstring for the
    vocabulary; the record stream is a bounded deque of small dicts:

    - ``{"kind": "begin"/"end", "name": "request", "trace": id, ...}``
      — request lifecycle (async span endpoints);
    - ``{"kind": "span", "name": phase, "trace": id, "ts": t0,
      "dur": seconds, ...}`` — one completed per-life phase;
    - ``{"kind": "event", "name": ..., ...}`` — per-step instants.

    Timestamps are ``time.perf_counter()`` values (the engine's own
    clock); export rebases them to microseconds from the tracer's
    construction. Thread-safe (the watchdog thread reads ``summary()``
    while the engine appends)."""

    DEFAULT_CAPACITY = 1 << 16

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 metrics: Optional[MetricsRegistry] = None,
                 id_base: int = 1):
        self.capacity = int(capacity)
        self._ring: deque = deque(maxlen=self.capacity)
        self.appended = 0
        self.metrics = metrics or MetricsRegistry()
        # id_base (ISSUE 19): a worker-process Tracer starts its trace
        # ids at a per-(replica, generation) disjoint base, so records
        # forwarded over the transport and ingested into the parent
        # ring can never collide with the parent's own ids (default 1:
        # single-process behavior unchanged)
        self._ids = itertools.count(int(id_base))
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()

    # -- recording -----------------------------------------------------------
    def _record(self, rec: dict):
        with self._lock:
            self._ring.append(rec)
            self.appended += 1

    @property
    def dropped(self) -> int:
        """Records that fell off the ring (flight-recorder semantics:
        the newest ``capacity`` records always survive)."""
        return self.appended - len(self._ring)

    def begin_request(self, req_id: int, tenant=None, replica: int = 0,
                      **attrs) -> int:
        """Open one request-lifetime async span; returns its trace id
        (propagate it through adopt_request so a migrated request stays
        ONE span)."""
        tid = next(self._ids)
        args = {"req_id": int(req_id), "replica": int(replica)}
        if tenant is not None:
            args["tenant"] = str(tenant)
        args.update(attrs)
        self._record({"kind": "begin", "name": "request", "trace": tid,
                      "pid": FLEET_PID, "ts": time.perf_counter(),
                      "args": args})
        self.metrics.inc("trace.requests")
        return tid

    def end_request(self, trace_id: Optional[int], state: str,
                    replica: int = 0, **attrs):
        if trace_id is None:
            return
        args = {"state": state, "replica": int(replica)}
        args.update(attrs)
        self._record({"kind": "end", "name": "request",
                      "trace": int(trace_id), "pid": FLEET_PID,
                      "ts": time.perf_counter(), "args": args})
        self.metrics.inc(f"trace.requests_{state}")

    def reopen_request(self, trace_id: Optional[int]) -> bool:
        """Rescind the most recent end record of ``trace_id`` — the
        fleet Router calls this when it migrates a request whose
        fault-burst FAILURE already closed the span (the engine failed
        it before the breaker tripped): the migration supersedes the
        terminal state, so the span must stay open until the adopted
        continuation ends it (one continuous span across replicas).
        Returns False when no end record is in the ring (it either
        never existed or already fell off)."""
        if trace_id is None:
            return False
        with self._lock:
            for r in reversed(self._ring):
                if r["kind"] == "end" and r["trace"] == trace_id:
                    self._ring.remove(r)
                    self.appended -= 1
                    state = r["args"].get("state")
                    if state:
                        self.metrics.inc(f"trace.requests_{state}", -1)
                    return True
        return False

    def span(self, name: str, trace_id: Optional[int], t0: float,
             t1: float, pid: int = 0, id: Optional[int] = None,
             parent: Optional[int] = None, step: Optional[int] = None,
             **attrs):
        """One completed slice [t0, t1] (perf_counter seconds) on the
        replica track ``pid``: a request's per-life phase (``trace_id``
        set) or a program span (``telemetry.span``). ``id`` is its own,
        ``parent`` the span that caused it, ``step`` the unit of work
        both belong to; left out, they are a fresh id and the innermost
        span open on the calling thread, so a request phase closed
        inside ``engine.step`` hangs under the phase that closed it."""
        if id is None:
            id = next(_span_ids)
            parent, open_step = _enclosing()
            step = open_step if step is None else step
        self._record({"kind": "span", "name": name,
                      "trace": (int(trace_id) if trace_id is not None
                                else None),
                      "pid": int(pid), "ts": float(t0),
                      "dur": max(0.0, float(t1) - float(t0)),
                      "id": id, "parent": parent, "step": step,
                      "args": attrs})
        self.metrics.inc(f"spans.{name}")
        self.metrics.histogram(f"span.{name}_s").observe(
            max(0.0, float(t1) - float(t0)))

    def event(self, name: str, trace: Optional[int] = None,
              pid: int = 0, **attrs):
        """One per-step instant (dispatch, retry, injected fault,
        breaker strike, kv alloc/evict/splice/rollback, ...). Returns
        the record as the ring holds it."""
        rec = {"kind": "event", "name": name,
               "trace": (int(trace) if trace is not None else None),
               "pid": int(pid), "ts": time.perf_counter(), "args": attrs}
        self._record(rec)
        self.metrics.inc(f"events.{name}")
        return rec

    def counter(self, name: str, value, pid: int = 0):
        """One counter-track sample (ISSUE 14): exports as a Perfetto
        ``ph: "C"`` event so the value renders as a resource TIMELINE
        next to the request spans (running slots, free blocks, queue
        depth, ...). The latest value also lands in the registry as a
        ``track.*`` gauge (per-replica suffix off the pid), so the
        OpenMetrics export carries the instantaneous view."""
        v = float(value)
        self._record({"kind": "counter", "name": name, "trace": None,
                      "pid": int(pid), "ts": time.perf_counter(),
                      "args": {"value": v}})
        suffix = ("" if pid == 0
                  else ".fleet" if pid == FLEET_PID
                  else f".r{int(pid)}")
        self.metrics.set_gauge(f"track.{name}{suffix}", v)

    # -- cross-process forwarding (ISSUE 19) ---------------------------------
    def drain_since(self, mark: int) -> tuple:
        """``(records appended since `mark`, new mark)`` — the worker
        side of transport telemetry forwarding: each step/stats reply
        piggybacks only the NEW records (reconstructed from the ring
        tail via the ``appended`` counter; records that already fell
        off the ring are lost exactly like flight-recorder semantics
        lose them locally)."""
        with self._lock:
            new = self.appended - int(mark)
            if new <= 0:
                return [], self.appended
            recs = list(self._ring)
            return (recs[-new:] if new < len(recs) else recs,
                    self.appended)

    def ingest(self, records: List[dict], ts_offset: float = 0.0):
        """Append records forwarded from ANOTHER process's Tracer into
        this ring, mirroring each kind's registry side-effects (the
        merged registry / validate_trace / trace_report views must
        agree with a single-process run). ``ts_offset`` shifts worker
        timestamps onto the parent clock — 0.0 on Linux, where
        perf_counter is CLOCK_MONOTONIC and shared across processes."""
        for r in records:
            rec = dict(r)
            if ts_offset:
                rec["ts"] = float(rec["ts"]) + ts_offset
            self._record(rec)
            kind, name = rec.get("kind"), rec.get("name")
            if kind == "begin":
                self.metrics.inc("trace.requests")
            elif kind == "end":
                state = rec.get("args", {}).get("state")
                if state:
                    self.metrics.inc(f"trace.requests_{state}")
            elif kind == "span":
                self.metrics.inc(f"spans.{name}")
                self.metrics.histogram(f"span.{name}_s").observe(
                    max(0.0, float(rec.get("dur", 0.0))))
            elif kind == "event":
                self.metrics.inc(f"events.{name}")
            elif kind == "counter":
                pid = int(rec.get("pid", 0))
                suffix = ("" if pid == 0
                          else ".fleet" if pid == FLEET_PID
                          else f".r{pid}")
                self.metrics.set_gauge(
                    f"track.{name}{suffix}",
                    float(rec["args"]["value"]))

    # -- reading -------------------------------------------------------------
    def records(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    def summary(self, last: int = 25) -> str:
        """Human-readable tail of the flight recorder (the watchdog
        appends this to its hang report)."""
        recs = self.records()
        lines = [f"flight recorder: {self.appended} records "
                 f"({self.dropped} dropped, capacity {self.capacity}); "
                 f"last {min(last, len(recs))}:"]
        for r in recs[-last:]:
            t = r["ts"] - self._t0
            extra = f" dur={r['dur'] * 1e3:.2f}ms" if "dur" in r else ""
            tidp = f" trace={r['trace']}" if r.get("trace") else ""
            lines.append(f"  +{t:9.3f}s [{r['kind']}] {r['name']}"
                         f"{tidp} pid={r['pid']}{extra} {r['args']}")
        return "\n".join(lines) + "\n"

    # -- export --------------------------------------------------------------
    def _us(self, t: float) -> float:
        return max(0.0, (t - self._t0) * 1e6)

    def export(self, path: str) -> str:
        """Write the flight recorder as Chrome-trace / Perfetto JSON
        (plus the metrics-registry snapshot under ``"metrics"``).
        Returns ``path``."""
        recs = self.records()
        evts: List[dict] = []
        pids = sorted({r["pid"] for r in recs})
        for pid in pids:
            name = ("fleet" if pid == FLEET_PID
                    else f"replica{pid}")
            evts.append({"ph": "M", "name": "process_name", "pid": pid,
                         "tid": 0, "ts": 0,
                         "args": {"name": name}})
        for r in recs:
            tid = r["trace"] if r.get("trace") is not None else 0
            if r["kind"] == "begin":
                evts.append({"ph": "b", "cat": "request",
                             "id": str(r["trace"]),
                             "name": f"req{r['args'].get('req_id', '')}",
                             "pid": r["pid"], "tid": tid,
                             "ts": self._us(r["ts"]),
                             "args": r["args"]})
            elif r["kind"] == "end":
                evts.append({"ph": "e", "cat": "request",
                             "id": str(r["trace"]), "name": "request",
                             "pid": r["pid"], "tid": tid,
                             "ts": self._us(r["ts"]),
                             "args": r["args"]})
            elif r["kind"] == "span":
                evts.append({"ph": "X", "cat": "phase",
                             "name": r["name"], "pid": r["pid"],
                             "tid": tid, "ts": self._us(r["ts"]),
                             "dur": r["dur"] * 1e6,
                             "id": r.get("id"), "parent": r.get("parent"),
                             "step": r.get("step"), "args": r["args"]})
            elif r["kind"] == "counter":
                evts.append({"ph": "C", "cat": "track",
                             "name": r["name"], "pid": r["pid"],
                             "tid": 0, "ts": self._us(r["ts"]),
                             "args": r["args"]})
            else:
                evts.append({"ph": "i", "cat": "step",
                             "name": r["name"], "pid": r["pid"],
                             "tid": tid, "ts": self._us(r["ts"]),
                             "s": "t", "args": r["args"]})
        doc = {"traceEvents": evts, "displayTimeUnit": "ms",
               "otherData": {"dropped_records": self.dropped,
                             "appended_records": self.appended},
               "metrics": self.metrics.snapshot()}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


# -- spans inside the program, on the profiler's clock (ISSUE 27) ------------

_open = threading.local()       # .stack: the spans open on this thread
_span_ids = itertools.count(1)  # process-wide, so ids are unique across rings
_default_lock = threading.Lock()
_default: Optional[Tracer] = None


def default_tracer() -> Tracer:
    """The process-wide ring: where spans go while a profiler session is
    live and no ``Tracer`` was attached, and where the ``compile.*``
    events always go. It outlives the engine or trainer that wrote to
    it, so a reader can ask for it after the system is freed."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = Tracer()
    return _default


def listening(tracer: Optional[Tracer] = None) -> Optional[Tracer]:
    """The ring a span opened now would be recorded in: the attached
    ``tracer``, else the default ring while a jax profiler session is
    live, else None (nobody listens, nothing is recorded)."""
    if tracer is not None:
        return tracer
    return default_tracer() if TraceAnnotation.is_enabled() else None


def _enclosing():
    """(id, step) of the innermost span open on this thread."""
    stack = getattr(_open, "stack", None)
    return stack[-1] if stack else (None, None)


class span:
    """``with span("engine.plan", tracer=self.tracer, T=8): ...``

    Always a TraceMe on the profiler's host plane (``annotation`` picks
    ``jax.profiler.StepTraceAnnotation`` for a step root); a record in
    the ring only while someone ``listening``. ``step`` names the unit
    of work (children inherit their parent's); ``trace`` is a request's
    trace id; ``attrs`` may be added to until the span closes
    (``sp.attrs["W"] = 16``): the ring gets them all, the TraceMe those
    known at entry plus ``set`` ones."""

    __slots__ = ("name", "ring", "attrs", "step", "trace", "pid", "id",
                 "parent", "t0", "_me")

    def __init__(self, name: str, tracer: Optional[Tracer] = None,
                 step: Optional[int] = None, trace: Optional[int] = None,
                 pid: int = 0, annotation=TraceAnnotation, **attrs):
        self.name = name
        self.ring = listening(tracer)
        self.attrs = attrs
        self.step, self.trace, self.pid = step, trace, pid
        if step is not None:
            attrs = dict(attrs, step=step)
        self._me = annotation(name, **attrs)

    def set(self, **attrs):
        """Attributes learnt inside the span, to both records (to
        neither while nobody listens)."""
        if self.ring is not None:
            self.attrs.update(attrs)
            self._me.set_metadata(**attrs)

    def __enter__(self):
        self._me.__enter__()
        if self.ring is not None:
            self.parent, step = _enclosing()
            if self.step is None:
                self.step = step
            self.id = next(_span_ids)
            stack = getattr(_open, "stack", None)
            if stack is None:
                stack = _open.stack = []
            stack.append((self.id, self.step))
            self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.ring is not None:
            t1 = time.perf_counter()
            _open.stack.pop()
            self.ring.span(self.name, self.trace, self.t0, t1, pid=self.pid,
                           id=self.id, parent=self.parent, step=self.step,
                           **self.attrs)
        self._me.__exit__(*exc)
        return False


# jax.monitoring event -> (ring event name, registry counter); durations
# first, plain counts after
_COMPILE_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("compile.trace", "compile.trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("compile.lower", "compile.lower_s"),
    "/jax/core/compile/backend_compile_duration":
        ("compile.backend", "compile.backend_s"),
    "/jax/compilation_cache/cache_retrieval_time_sec":
        ("compile.cache_load", "compile.cache_load_s"),
}
_COMPILE_COUNTS = {
    "/jax/compilation_cache/cache_hits":
        ("compile.cache_hit", "compile.cache_hits"),
    "/jax/compilation_cache/compile_requests_use_cache":
        ("compile.cache_request", "compile.cache_requests"),
}
# (start, seconds) of the events of each kind not yet found nested in a
# later one; a jitted function traced inside another's trace ends first
_compile_tail: Dict[str, List[tuple]] = {}
_COMPILE_TAIL_MAX = 4096
# events shorter than this (a process fires thousands: every eager
# operation and every jitted helper traced inside a program's trace)
# share one ring record per kind and second, so that they cannot push a
# run's set-up out of the ring
_COMPILE_SMALL_S = 1e-3
_compile_small: Dict[str, dict] = {}


def _on_compile_duration(event: str, seconds: float, **kw):
    names = _COMPILE_DURATIONS.get(event)
    if names is None:
        return
    kind, counter = names
    seconds = float(seconds)
    end = time.perf_counter()
    start = end - seconds
    small = seconds < _COMPILE_SMALL_S
    ring = default_tracer()
    with _default_lock:
        tail = _compile_tail.setdefault(kind, [])
        nested = 0.0
        while tail and tail[-1][0] >= start:
            nested += tail.pop()[1]
        if len(tail) >= _COMPILE_TAIL_MAX:
            del tail[:_COMPILE_TAIL_MAX // 2]
        tail.append((start, seconds))
        self_s = max(0.0, seconds - nested)
        shared = _compile_small.get(kind) if small else None
        if shared is not None and end - shared["ts"] >= 1.0:
            shared = None
        if shared is not None:
            args = shared["args"]
            args["seconds"] += seconds
            args["self_s"] += self_s
            args["n"] += 1
    if shared is not None:
        ring.metrics.inc(f"events.{kind}")
    elif small:
        rec = ring.event(kind, fun_name="(under 1 ms each)",
                         seconds=seconds, self_s=self_s, n=1)
        with _default_lock:
            _compile_small[kind] = rec
    else:
        ring.event(kind, fun_name=str(kw.get("fun_name", "")),
                   seconds=seconds, self_s=self_s)
    ring.metrics.inc(counter, self_s)


def _on_compile_event(event: str, **kw):
    names = _COMPILE_COUNTS.get(event)
    if names is not None:
        ring = default_tracer()
        ring.event(names[0])
        ring.metrics.inc(names[1])


def compile_seconds(t0: float, t1: float) -> Dict[str, float]:
    """{"trace_s", "lower_s", "backend_s", "cache_load_s"}: wall seconds
    of the ``compile.*`` events the default ring holds that ENDED inside
    [t0, t1] (``perf_counter``), each kind's nested events counted once;
    kinds with no event are left out."""
    out: Dict[str, float] = {}
    by_event = {ev: ctr.split(".", 1)[1]
                for ev, ctr in _COMPILE_DURATIONS.values()}
    for r in default_tracer().records():
        key = by_event.get(r["name"]) if r["kind"] == "event" else None
        if key is not None and t0 <= r["ts"] <= t1:
            out[key] = out.get(key, 0.0) + r["args"]["self_s"]
    return out


jax.monitoring.register_event_duration_secs_listener(_on_compile_duration)
jax.monitoring.register_event_listener(_on_compile_event)
