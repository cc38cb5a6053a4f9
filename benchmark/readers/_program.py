"""The program's own record: the span and event records of
``paddle_tpu.utils.telemetry``'s process-wide ring, which the program
fills while a profiler session is live (spans) and whenever something
compiles (``compile.*`` events), and which outlives the system the
driver freed. Ring times are ``time.perf_counter()`` seconds; the
trace's are nanoseconds of the profiler's clock. The harness reads
``perf_counter`` right after it enters ``bench:window``, so that span's
start in the trace and ``ctx["trace_clock"][0]`` are the same instant
on the two clocks."""
import sys

from .. import trace_reduce

WINDOW = trace_reduce.HOST_PREFIX + "window"


def records(ctx) -> list:
    """The ring's records; ``ctx["program_record"]`` where a test hands
    one in; nothing where the program keeps no such ring."""
    if "program_record" in ctx:
        return ctx["program_record"]
    try:
        from paddle_tpu.utils import telemetry
        return telemetry.default_tracer().records()
    except (ImportError, AttributeError):
        return []


def counters(prefixes) -> dict:
    """The counters of the program's process-wide registry whose names
    start with one of ``prefixes`` (``compile.`` says what set-up
    compiled or loaded, ``loss.`` which loss body a step was built
    from); nothing where the program keeps no such registry."""
    try:
        from paddle_tpu.utils import telemetry
        found = telemetry.default_tracer().metrics.snapshot()["counters"]
    except (ImportError, AttributeError):
        return {}
    return {k: v for k, v in sorted(found.items())
            if k.startswith(tuple(prefixes))}


def spans(ctx) -> list:
    """Span records that lie inside the traced sub-window."""
    t0, t1 = ctx["trace_clock"]
    if t0 is None or t1 is None:
        return []
    return [r for r in records(ctx) if r.get("kind") == "span"
            and r["ts"] >= t0 and r["ts"] + r["dur"] <= t1]


def clock_offset_ns(ctx):
    """What to add to ``perf_counter() * 1e9`` to get the trace's
    nanoseconds; None without a ``bench:window`` span to anchor on."""
    t0 = ctx["trace_clock"][0]
    for name, start, _ in trace_reduce.host_spans(ctx["trace"]):
        if name == WINDOW and t0 is not None:
            return start - t0 * 1e9
    return None


def log(msg: str):
    print(f"[bench reader] {msg}", file=sys.stderr, flush=True)
