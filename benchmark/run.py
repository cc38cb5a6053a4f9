"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

builds the cell's system from its configuration file and ``--seed``, warms
the programs its traffic reaches (set-up), drives the cell's traffic mix
for ``--seconds`` seconds, frees the system, holds what the timed path
produced against the plain reference, and prints one JSON object as the
last line of standard output. It runs on the machine it is started on and
fails, with no result line, where JAX finds no TPU that
``benchmark/peaks.json`` knows or fewer chips than the cell asks for.
"""
import time
T_START = time.perf_counter()

import argparse         # noqa: E402
import json             # noqa: E402
import os               # noqa: E402
import shutil           # noqa: E402
import sys              # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# one compile cache at a fixed path: the caller's, else the checkout's
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def log(msg):
    print(f"[bench {time.perf_counter() - T_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


def device_info(chips: int, peaks: dict) -> dict:
    """The only place that decides whether this machine may measure."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0].platform is "
                     f"{d0.platform!r}")
    if d0.device_kind not in peaks:
        raise NoChip(f"device kind {d0.device_kind!r} is not in "
                     f"benchmark/peaks.json")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Hooks:
    """What a driver tells the harness while it runs: when the window
    opens and closes, and a tick at every turn of its loop, on which a
    traced run starts and stops the profiler around its sub-window."""

    def __init__(self, trace: bool, mix: dict, seconds: float):
        self.clock = time.perf_counter
        self.trace = bool(trace)
        span = min(float(mix.get("trace_s", 5.0)), seconds)
        self.trace_steps = int(mix.get("trace_steps", 0))
        # a sub-window by the clock lies at the window's end, so that
        # stopping the profiler (seconds, for a long trace) holds up no
        # request of the window; one counted in steps lies in its middle
        self.trace_at = (max(0.0, seconds - span) / 2.0 if self.trace_steps
                         else seconds - span)
        self.trace_until = self.trace_at + span
        self.stats = {}
        self.t_open = self.t_close = None
        self.compiles_open = self.compiles_close = None
        self.trace_t0 = self.trace_t1 = None
        self._win = None
        self._ticks = 0
        self.setup_s = None

    @staticmethod
    def compiles(system) -> int:
        """Compiles the system's own watch has counted."""
        watch = getattr(system, "compile_watch", None)
        return watch.compiles if watch is not None else 0

    def window_open(self, system):
        if hasattr(system, "clear_finished"):
            system.clear_finished()
        self.compiles_open = self.compiles(system)
        self.t_open = self.clock()
        self.setup_s = self.t_open - T_START

    def window_close(self, system):
        self.t_close = self.clock()
        self._stop_trace()
        self.compiles_close = self.compiles(system)
        if hasattr(system, "stats"):
            self.stats = system.stats()

    def tick(self, t_rel: float, step_boundary: bool = False):
        if not self.trace or self.t_open is None:
            return
        if self.trace_t0 is None and t_rel >= self.trace_at:
            self._start_trace()
            self._ticks = 0
        elif self.trace_t0 is not None and self.trace_t1 is None:
            self._ticks += 1
            if (self.trace_steps and step_boundary
                    and self._ticks >= self.trace_steps) \
                    or (not self.trace_steps
                        and t_rel >= self.trace_until):
                self._stop_trace()

    def _start_trace(self):
        import jax
        from jax.profiler import TraceAnnotation
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = None
        if hasattr(jax.profiler, "ProfileOptions"):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
        kw = {"profiler_options": opts} if opts is not None else {}
        t0 = self.clock()
        jax.profiler.start_trace(TRACE_DIR, **kw)
        log(f"profiler started in {self.clock() - t0:.2f} s")
        self._win = TraceAnnotation("bench:window")
        self._win.__enter__()
        self.trace_t0 = self.clock()

    def _stop_trace(self):
        import jax
        if self._win is None:
            return
        self.trace_t1 = self.clock()
        self._win.__exit__(None, None, None)
        self._win = None
        jax.profiler.stop_trace()
        log(f"profiler stopped in {self.clock() - self.trace_t1:.2f} s")


def read_per_layer(names, ctx):
    """{name: value} of the per-layer metrics whose reader found
    something to read."""
    from benchmark import manifest
    out = {}
    for name in names:
        spec = manifest.metric_file(name)
        reader = manifest.module("readers", spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the reduced trace (JSON, gzipped) here")
    args = ap.parse_args(argv)

    from benchmark import manifest, traffic, check
    from benchmark.readers import _program
    cell = manifest.workload(args.workload)
    cfg = manifest.config_of(cell)
    mix = traffic.load_mix(cell["traffic"])
    peaks = manifest.peaks()
    try:
        device = device_info(cell["chips"], peaks)
    except NoChip as e:
        log(f"refusing to measure: {e}")
        return 2
    peak = peaks[device["kind"]]
    log(f"device {device}; compile cache "
        f"{os.environ['JAX_COMPILATION_CACHE_DIR']}")

    driver = manifest.module("drivers", mix["driver"])
    system, setup = driver.setup(cfg, mix, args.seed, log)
    log(f"set-up detail: {json.dumps(setup.get('programs', []))}")

    hooks = Hooks(args.trace, mix, args.seconds)
    res = driver.run(system, mix, cfg["model"]["vocab_size"], args.seed,
                     args.seconds, hooks)
    log(f"window closed: attempted {res['attempted']} failed "
        f"{res['failed']}; set-up {hooks.setup_s:.2f} s")
    log("program counters: " + json.dumps(
        _program.counters(("compile.", "loss."))))
    device["memory_peak_bytes"] = memory_peak_bytes(cell["chips"])
    in_window = hooks.compiles_close - hooks.compiles_open
    driver.release(system)
    del system
    log("system freed; the reference follows")

    numbers = driver.check_numbers(cfg, mix, args.seed, res, setup)
    log("reference done")
    numbers["compiles_in_window"] = in_window
    limits = dict(check.load_limits(args.workload), compiles_in_window=0)
    ok, checks = check.judge(numbers, limits)
    correct = bool(ok and res["attempted"] > 0 and res["failed"] == 0)

    e2e = dict(res["end_to_end"], setup_s=hooks.setup_s)
    units = {m["name"]: m["unit"] for m in
             manifest.benchmark()["end_to_end"]}
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"]}
    if args.trace:
        from benchmark import trace_reduce
        trace = trace_reduce.read_xplane(trace_reduce.find_xplane(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if args.keep_trace:
            import gzip
            with gzip.open(args.keep_trace, "wt") as f:
                json.dump(trace, f)
        busy, window = trace_reduce.busy_and_window_s(trace)
        device["busy_s"], device["window_s"] = busy, window
        ctx = {"model": cfg["model"], "cfg": cfg, "mix": mix, "peak": peak,
               "family": manifest.family_of(cfg),
               "stats": hooks.stats, "clock": res["clock"],
               "trace": trace, "res": res,
               "trace_clock": (hooks.trace_t0, hooks.trace_t1)}
        line["metrics"] = read_per_layer(
            manifest.metrics_for(args.workload, "per_layer"), ctx)
        line["breakdown"] = {
            "device_ops": trace_reduce.top_ops(trace),
            "idle_gaps": trace_reduce.idle_gaps(trace)}
    else:
        line["metrics"] = {
            name: {"value": float(e2e[name]), "unit": units[name]}
            for name in manifest.metrics_for(args.workload, "end_to_end")
            if name in e2e}
    line["device"] = device
    line["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    log(f"correct: {correct}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
