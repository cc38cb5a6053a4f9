"""Comm/step watchdog: hang detection for training loops and collectives.

Reference parity: CommTask / CommTaskManager timeouts
(/root/reference/paddle/phi/core/distributed/comm_task_manager.h:37, with
the per-task timeout handling at :52) and the store-barrier timeout of
init_parallel_env — the first tool you reach for when a multi-host job
wedges.

TPU-native shape: collectives are in-program (GSPMD), so a hang shows up
as a device step (or an eager collective dispatch) that never completes.
The watchdog is a daemon thread watching two signals:
- step progress: TrainStep (or any loop calling ``notify_step``) bumps a
  heartbeat; no bump for ``timeout`` seconds => hang report.
- active sections: ``watch_section("all_reduce")`` wraps blocking calls
  (the eager collective facade uses it); a section still active past its
  deadline is reported with its name and age.

A hang report dumps every Python thread's stack, the device/mesh state,
and the last-completed step, to stderr and (optionally) a file; an
optional callback supports tests and custom telemetry. Enabled via flags:
FLAGS_enable_watchdog / FLAGS_watchdog_timeout_s, or explicitly.
"""
from __future__ import annotations

import io
import os
import sys
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional

from ..utils.flags import define_flag, FLAGS

__all__ = ["StepWatchdog", "watch_section", "watch_engine",
           "get_default_watchdog", "enable_watchdog", "notify_step"]

define_flag("enable_watchdog", False,
            "start the step/comm watchdog on first TrainStep call")
define_flag("watchdog_timeout_s", 300.0,
            "seconds without step progress (or section completion) "
            "before a hang report")
define_flag("watchdog_dump_path", "",
            "optional file path to append hang reports to")


class StepWatchdog:
    """Daemon monitor thread. Thread-safe; one instance can watch the
    whole process."""

    def __init__(self, timeout: Optional[float] = None,
                 poll_interval: float = 1.0,
                 on_hang: Optional[Callable[[str], None]] = None,
                 dump_path: Optional[str] = None,
                 extra_dump: Optional[Callable[[io.StringIO],
                                              None]] = None):
        self.timeout = float(timeout if timeout is not None
                             else FLAGS.watchdog_timeout_s)
        self.poll_interval = poll_interval
        self.on_hang = on_hang
        self.dump_path = dump_path or (FLAGS.watchdog_dump_path or None)
        # optional domain-specific section of the hang report (e.g.
        # watch_engine appends the serving engine's scheduler state)
        self.extra_dump = extra_dump
        self._lock = threading.Lock()
        self._last_beat = time.monotonic()
        self._step = 0
        self._sections: Dict[int, tuple] = {}   # id -> (name, start, ddl)
        self._next_sid = 0
        self._reported = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle --
    def start(self):
        if self._thread is not None:
            return self
        self._stop = threading.Event()   # fresh event: stop() poisons it
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="paddle_tpu-watchdog")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.poll_interval)
            self._thread = None

    # -- signals --
    def notify_step(self, step: Optional[int] = None):
        with self._lock:
            self._step = self._step + 1 if step is None else step
            self._last_beat = time.monotonic()
            self._reported = False

    def section(self, name: str, timeout: Optional[float] = None):
        return _Section(self, name, timeout or self.timeout)

    def _begin(self, name, timeout):
        with self._lock:
            sid = self._next_sid
            self._next_sid += 1
            now = time.monotonic()
            self._sections[sid] = (name, now, now + timeout)
        return sid

    def _end(self, sid):
        with self._lock:
            self._sections.pop(sid, None)
            self._last_beat = time.monotonic()
            self._reported = False

    # -- monitor --
    def _run(self):
        while not self._stop.wait(self.poll_interval):
            now = time.monotonic()
            with self._lock:
                expired = [(n, now - t0) for (n, t0, ddl)
                           in self._sections.values() if now > ddl]
                stalled = (now - self._last_beat) > self.timeout
                reported = self._reported
            if (expired or stalled) and not reported:
                self._report(expired, now)
                with self._lock:
                    self._reported = True

    def _report(self, expired: List[tuple], now: float):
        buf = io.StringIO()
        buf.write("\n========== paddle_tpu WATCHDOG: hang detected "
                  "==========\n")
        with self._lock:
            buf.write(f"last completed step: {self._step}; "
                      f"{now - self._last_beat:.1f}s since last "
                      f"progress (timeout {self.timeout:.1f}s)\n")
            active = list(self._sections.values())
        for name, age in expired:
            buf.write(f"  STUCK section: {name!r} running {age:.1f}s\n")
        for name, t0, _ in active:
            buf.write(f"  active section: {name!r} ({now - t0:.1f}s)\n")
        self._dump_env(buf)
        if self.extra_dump is not None:
            try:
                self.extra_dump(buf)
            except Exception as e:           # noqa: BLE001
                buf.write(f"(extra dump failed: {e})\n")
        buf.write("---- python thread stacks ----\n")
        frames = sys._current_frames()
        for tid, frame in frames.items():
            tname = next((t.name for t in threading.enumerate()
                          if t.ident == tid), str(tid))
            buf.write(f"-- thread {tname} --\n")
            buf.write("".join(traceback.format_stack(frame)))
        buf.write("====================================================\n")
        text = buf.getvalue()
        sys.stderr.write(text)
        sys.stderr.flush()
        if self.dump_path:
            try:
                with open(self.dump_path, "a") as f:
                    f.write(text)
            except OSError:
                pass
        if self.on_hang is not None:
            try:
                self.on_hang(text)
            except Exception:
                pass

    def _dump_env(self, buf):
        buf.write("---- device / mesh state ----\n")
        try:
            import jax
            buf.write(f"backend={jax.default_backend()} "
                      f"process={jax.process_index()}/"
                      f"{jax.process_count()} "
                      f"local_devices={len(jax.local_devices())}\n")
        except Exception as e:
            buf.write(f"(jax state unavailable: {e})\n")
        try:
            from .fleet import get_hybrid_communicate_group
            hcg = get_hybrid_communicate_group()
            if hcg is not None:
                buf.write(f"hybrid topology: {hcg.describe()}\n")
        except Exception:
            pass
        for k in ("PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
                  "MASTER_ADDR", "MASTER_PORT"):
            if k in os.environ:
                buf.write(f"{k}={os.environ[k]}\n")


class _Section:
    def __init__(self, wd: StepWatchdog, name: str, timeout: float):
        self._wd = wd
        self._name = name
        self._timeout = timeout
        self._sid = None

    def __enter__(self):
        self._sid = self._wd._begin(self._name, self._timeout)
        return self

    def __exit__(self, *exc):
        self._wd._end(self._sid)
        return False


_default: Optional[StepWatchdog] = None
_default_lock = threading.Lock()


def get_default_watchdog(create: bool = False) -> Optional[StepWatchdog]:
    global _default
    with _default_lock:
        if _default is None and create:
            _default = StepWatchdog().start()
        return _default


def enable_watchdog(timeout: Optional[float] = None, **kw) -> StepWatchdog:
    """Start (or return) the process-wide watchdog."""
    global _default
    with _default_lock:
        if _default is None:
            _default = StepWatchdog(timeout=timeout, **kw).start()
        return _default


def notify_step(step: Optional[int] = None):
    wd = get_default_watchdog()
    if wd is not None:
        wd.notify_step(step)


def watch_engine(engine, timeout: Optional[float] = None,
                 poll_interval: float = 1.0,
                 on_hang: Optional[Callable[[str], None]] = None,
                 dump_path: Optional[str] = None) -> StepWatchdog:
    """Wrap a ServingEngine's step() with the stall detector (ISSUE 4
    satellite): a dedicated StepWatchdog whose hang report includes the
    engine's scheduler snapshot — per-request states, queue/pipeline
    depth, robustness counters and KV-pool occupancy (debug_dump) —
    on top of the usual thread stacks and device state.

    Each step() runs inside a watched section (a single WEDGED step —
    e.g. a dispatch that never returns from a lost device — is
    reported with its age even though the step never completed) and
    bumps the heartbeat on completion, so "engine alive but stuck" and
    "engine not being stepped" both trip after `timeout` seconds.

    Returns the started watchdog; call .stop() to detach monitoring
    (the step wrapper stays installed but becomes inert sections)."""

    def _dump(buf: io.StringIO):
        # debug_dump() opens with its own "serving engine state:" header
        buf.write(engine.debug_dump())
        # flight recorder (ISSUE 12): the hang report carries the tail
        # of the telemetry ring — what dispatched, retried, preempted
        # or faulted right before the wedge — and, when the report is
        # going to a file, the FULL Perfetto export lands next to it
        # so every hang ships its own post-mortem timeline
        tracer = getattr(engine, "tracer", None)
        if tracer is not None:
            buf.write(tracer.summary())
            if dump_path:
                try:
                    p = tracer.export(dump_path + ".trace.json")
                    buf.write(f"flight recorder exported: {p}\n")
                except Exception as e:     # noqa: BLE001 — the hang
                    # report must survive any export failure
                    buf.write(f"(flight recorder export failed: {e})\n")

    wd = StepWatchdog(timeout=timeout, poll_interval=poll_interval,
                      on_hang=on_hang, dump_path=dump_path,
                      extra_dump=_dump)
    inner = engine.step

    def step():
        with wd.section("ServingEngine.step"):
            out = inner()
        wd.notify_step()
        return out

    engine.step = step
    engine._step_watchdog = wd
    return wd.start()


def watch_section(name: str, timeout: Optional[float] = None):
    """Context manager marking a blocking call (eager collective, store
    barrier) the watchdog should report if it never completes. No-op when
    the watchdog isn't running."""
    wd = get_default_watchdog()
    if wd is None:
        class _Null:
            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False
        return _Null()
    return wd.section(name, timeout)
