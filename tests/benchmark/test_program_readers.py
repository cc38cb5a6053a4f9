"""The readers of the program's own record (spans and ``compile.*``
events of ``paddle_tpu.utils.telemetry``'s ring): on the recorded trace
``data/train_two_steps.json.gz`` with a synthetic record written for it
(``data/train_two_steps.ring.json``), and on a hand-made trace whose
answers can be worked out on paper."""
import json
import os

import pytest

import tiny_tree  # noqa: F401  (puts the repo on sys.path)
from benchmark import manifest, trace_reduce as tr
from benchmark.readers import (_program, compile_time, span_idle_gap,
                               span_time)

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def span(id_, parent, name, t0_ms, dur_ms, step, pid=0):
    return {"kind": "span", "name": name, "trace": None, "pid": pid,
            "ts": 50.0 + t0_ms / 1e3, "dur": dur_ms / 1e3, "id": id_,
            "parent": parent, "step": step, "args": {}}


def hand_made(records):
    """A traced window of 100 ms on the trace's clock that the harness
    entered at perf_counter 50.0 (trace nanosecond 7 ms): the device runs
    10-24 ms and 50-70 ms after the window opened."""
    base = 7 * MS
    ops = [["fusion.1", base + 10 * MS, 14 * MS],
           ["fusion.2", base + 50 * MS, 20 * MS]]
    return {"trace": {"planes": {
                "/device:TPU:0": {tr.MODULES_LINE: [], tr.OPS_LINE: ops},
                "host": {"spans": [["bench:window", base, 100 * MS],
                                   ["bench:step", base + 1 * MS, 9 * MS]]}}},
            "trace_clock": (50.0, 50.1), "res": {"window": (40.0, 82.0)},
            "program_record": records}


def recorded():
    trace = tr.load(os.path.join(HERE, "data", "train_two_steps.json.gz"))
    with open(os.path.join(HERE, "data",
                           "train_two_steps.ring.json")) as f:
        ring = json.load(f)
    return {"trace": trace, "trace_clock": tuple(ring["trace_clock"]),
            "res": {"window": tuple(ring["window"])},
            "program_record": ring["records"]}


# -- the clock ----------------------------------------------------------------

def test_offset_is_the_window_span_against_the_harness_clock():
    ctx = hand_made([])
    assert _program.clock_offset_ns(ctx) == 7 * MS - 50.0 * 1e9
    del ctx["trace"]["planes"]["host"]       # nothing to anchor on
    assert _program.clock_offset_ns(ctx) is None


def test_only_spans_inside_the_traced_window_count():
    recs = [span(1, None, "train_step", -1, 3, 0),     # began before it
            span(2, None, "train_step", 2, 3, 1),
            span(3, None, "train_step", 99, 3, 2)]     # ended after it
    assert [r["id"] for r in _program.spans(hand_made(recs))] == [2]
    ctx = dict(hand_made(recs), trace_clock=(None, None))
    assert _program.spans(ctx) == []


# -- span_time ----------------------------------------------------------------

def test_span_time_is_the_median_of_the_named_spans(capsys):
    recs = [span(1, None, "train_step", 2, 3.0, 0),
            span(2, 1, "train_step.dispatch", 3, 2.0, 0),
            span(3, None, "train_step", 40, 5.0, 1),
            span(4, 3, "train_step.dispatch", 41, 3.0, 1),
            span(5, None, "train_step", 80, 4.0, 2)]
    ctx = hand_made(recs)
    assert span_time.read(ctx, name="train_step", scale=1000.0) == \
        pytest.approx(4.0)
    assert span_time.read(ctx, name="train_step", stat="mean") == \
        pytest.approx(0.004)
    # the children's medians go to the log, in the metric's unit
    assert "train_step > train_step.dispatch: p50 2.5000 over 2" in \
        capsys.readouterr().err


def test_span_time_per_step_sums_within_a_step_first():
    recs = [span(1, None, "engine.step", 0, 10, 7),
            span(2, 1, "engine.collect", 1, 2.0, 7),
            span(3, 1, "engine.collect", 4, 1.0, 7),     # a flush: two
            span(4, None, "engine.step", 20, 10, 8),
            span(5, 4, "engine.collect", 21, 5.0, 8),
            span(6, None, "engine.step", 40, 10, 9),     # none: left out
            span(7, None, "engine.step", 60, 10, 7, pid=1),
            span(8, 7, "engine.collect", 61, 9.0, 7, pid=1)]
    ctx = hand_made(recs)
    # per step: 3, 5 and, on the other replica, 9
    assert span_time.read(ctx, name="engine.collect", per="engine.step",
                          scale=1000.0) == pytest.approx(5.0)
    assert span_time.read(ctx, name="engine.plan", per="engine.step") \
        is None


# -- span_idle_gap ------------------------------------------------------------

def test_idle_gap_under_the_programs_spans_by_hand():
    """Idle: 0-10, 24-50, 70-100 ms. The program's step spans are open
    2-12 ms (8 ms of it idle) and 45-55 ms (5 ms idle); a span of another
    name does not count."""
    recs = [span(1, None, "train_step", 2, 10, 0),
            span(2, 1, "train_step.dispatch", 4, 7, 0),   # inside: no more
            span(3, None, "train_step", 45, 10, 1),
            span(4, None, "other", 80, 10, 1)]
    ctx = hand_made(recs)
    got = span_idle_gap.read(ctx, prefix="train_step", root="train_step",
                             scale=1000.0)
    assert got == pytest.approx((8.0 + 5.0) / 2)
    # moving the harness's reading of the clock by 1 ms moves every span
    # 1 ms later on the trace: 3-13 (7 idle) and 46-56 (4 idle)
    ctx["trace_clock"] = (49.999, 50.1)
    assert span_idle_gap.read(ctx, prefix="train_step", root="train_step",
                              scale=1000.0) == pytest.approx(5.5)


def test_overlap_of_interval_lists():
    assert span_idle_gap.overlap_ns([(0, 10), (20, 30)],
                                    [[5, 25], [28, 40]]) == 5 + 5 + 2
    assert span_idle_gap.overlap_ns([(0, 10)], []) == 0


def test_idle_gap_on_the_recorded_trace():
    ctx = recorded()
    got = span_idle_gap.read(ctx, prefix="train_step", root="train_step",
                             scale=1000.0)
    # brute force over the same trace
    trace = ctx["trace"]
    t0, t1 = tr.window_of(trace)
    busy = tr.union(tr.clip(
        trace["planes"]["/device:TPU:0"][tr.OPS_LINE], t0, t1))
    off = t0 - ctx["trace_clock"][0] * 1e9
    roots = [r for r in _program.spans(ctx) if r["name"] == "train_step"]
    assert [r["step"] for r in roots] == [3, 4]
    idle = 0
    for r in roots:
        a = r["ts"] * 1e9 + off
        b = a + r["dur"] * 1e9
        inside = sum(max(0, min(b, y) - max(a, x)) for x, y in busy)
        idle += (b - a) - inside
    assert got == pytest.approx(idle / 1e6 / 2, rel=1e-6)
    # step 4's span covers most of the 4.85 ms the device waited there
    assert 2.0 < got < 4.9
    assert got * 2 / 1e3 <= (t1 - t0) / 1e9 - tr.busy_and_window_s(trace)[0]


# -- compile_time -------------------------------------------------------------

def test_compile_time_before_the_window_only(capsys):
    ctx = recorded()
    assert compile_time.read(
        ctx, events=["compile.trace", "compile.lower"]) == \
        pytest.approx(0.25 + 1.25 + 2.0)
    assert "compile.trace 1.500, compile.lower 2.000" in \
        capsys.readouterr().err
    spec = manifest.metric_file("backend_compile_s.setup")
    assert compile_time.read(ctx, **spec["args"]) == pytest.approx(1.5)
    err = capsys.readouterr().err
    assert "compile.backend 1.500, compile.cache_load 0.750, " \
           "compile.cache_hit 1.000, compile.cache_request 2.000" in err


# -- nothing to read ----------------------------------------------------------

def test_readers_return_nothing_on_an_empty_record():
    ctx = hand_made([])
    assert span_time.read(ctx, name="train_step") is None
    assert span_idle_gap.read(ctx, prefix="train_step",
                              root="train_step") is None
    assert compile_time.read(ctx, events=["compile.backend"]) is None


def test_a_program_without_the_ring_reads_as_an_empty_record(monkeypatch):
    """What the parent commit looks like to these readers."""
    from paddle_tpu.utils import telemetry
    monkeypatch.delattr(telemetry, "default_tracer")
    ctx = hand_made([])
    del ctx["program_record"]
    assert _program.records(ctx) == []
    assert span_time.read(ctx, name="train_step") is None


def test_the_live_ring_is_read_when_no_record_is_handed_in():
    from paddle_tpu.utils import telemetry
    ctx = hand_made([])
    del ctx["program_record"]
    assert _program.records(ctx) == telemetry.default_tracer().records()


@pytest.mark.parametrize("name", [
    "host_ms_per_step.train", "dispatch_gap_ms_per_step.train",
    "trace_lower_s.setup", "backend_compile_s.setup",
    "plan_ms_per_step.itl", "dispatch_ms_per_step.itl",
    "collect_wait_ms_per_step.itl"])
def test_metric_file_reads_through_its_reader(name):
    """Every new metric file names a reader and arguments that read the
    recorded pair (the serving ones find no engine span there)."""
    import importlib
    spec = manifest.metric_file(name)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    value = reader.read(recorded(), **spec.get("args", {}))
    if name.endswith(".itl"):
        assert value is None
    else:
        assert value is not None and value > 0
