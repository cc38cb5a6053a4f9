"""One span primitive on the profiler's clock (ISSUE 27):
``telemetry.span`` (id / parent / step, thread-local nesting, who
listens), the ``compile.*`` events jax's monitoring feeds, the spans of
``jit.TrainStep`` and ``ServingEngine.step``, the names the device side
carries (named scopes in the lowered programs, ``name=`` on every Pallas
kernel), ``profiler.device_trace_summary`` on a trace cut from a chip
run, and ``tools/trace_report.py``'s self time."""
import ast
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer, profiler
from paddle_tpu.inference import SamplingParams, ServingEngine
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.utils import telemetry
from paddle_tpu.utils.telemetry import CompileWatch, Tracer, span

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = llama_tiny(hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=2, intermediate_size=96,
                 num_hidden_layers=2, vocab_size=256,
                 max_position_embeddings=256)
KW = dict(max_batch_size=3, num_blocks=24, block_size=8,
          prompt_buckets=(8, 16, 32), chunk_size=4, prefill_chunk=8)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(CFG)
    m.eval()
    return m


def _prompt(n=12, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, n).astype(np.int32)


def _spans(tracer, name=None):
    return [r for r in tracer.records() if r["kind"] == "span"
            and (name is None or r["name"] == name)]


def _serve(model, tracer=None, ragged=True, **kw):
    eng = ServingEngine(model, **dict(KW, ragged=ragged, **kw))
    if tracer is not None:
        eng.set_telemetry(tracer)
    rids = [eng.add_request(_prompt(n, seed=n),
                            SamplingParams(max_new_tokens=6))
            for n in (5, 11, 20)]
    eng.run_to_completion()
    return eng, [eng.result(r) for r in rids]


def _trainer():
    paddle.seed(3)
    m = LlamaForCausalLM(CFG)
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, lambda out, lab: m.loss(out, lab), opt)
    ids = paddle.to_tensor(_prompt(32).reshape(2, 16))
    return step, ids


# -- the primitive -----------------------------------------------------------

class TestSpan:
    def test_records_id_parent_and_inherited_step(self):
        tr = Tracer()
        with span("root", tracer=tr, step=7, why="x") as root:
            with span("child", tracer=tr) as child:
                with span("leaf", tracer=tr):
                    pass
                child.set(rows=3)
            with span("sibling", tracer=tr):
                pass
        by = {r["name"]: r for r in _spans(tr)}
        assert by["root"]["parent"] is None and by["root"]["step"] == 7
        assert by["child"]["parent"] == by["root"]["id"] == root.id
        assert by["leaf"]["parent"] == by["child"]["id"]
        assert by["sibling"]["parent"] == by["root"]["id"]
        assert {r["step"] for r in by.values()} == {7}
        assert len({r["id"] for r in by.values()}) == 4
        assert by["child"]["args"] == {"rows": 3}
        assert by["root"]["args"] == {"why": "x"}
        # a child lies inside its parent, on the ring's clock
        for kid, parent in (("child", "root"), ("leaf", "child")):
            k, p = by[kid], by[parent]
            assert p["ts"] <= k["ts"]
            assert k["ts"] + k["dur"] <= p["ts"] + p["dur"]

    def test_nobody_listening_records_nothing(self):
        ring = telemetry.default_tracer()
        before = len(_spans(ring))
        assert telemetry.listening() is None
        with span("unheard", step=1) as s:
            s.set(x=1)
            assert s.ring is None
        assert len(_spans(ring)) == before

    def test_a_request_phase_hangs_under_the_open_span(self):
        tr = Tracer()
        with span("engine.step", tracer=tr, step=4):
            with span("engine.deliver", tracer=tr) as d:
                tr.span("decode", 9, 1.0, 2.0, dispatch=12)
        dec = _spans(tr, "decode")[0]
        assert (dec["parent"], dec["step"], dec["trace"]) == (d.id, 4, 9)
        assert dec["args"]["dispatch"] == 12
        # outside any span it is a root of its own
        tr.span("queued", 9, 0.0, 1.0)
        q = _spans(tr, "queued")[0]
        assert q["parent"] is None and q["step"] is None and q["id"]

    def test_threads_nest_independently(self):
        tr = Tracer()
        go = threading.Barrier(2, timeout=10)

        def work(i):
            with span(f"root{i}", tracer=tr, step=i):
                go.wait()
                with span(f"kid{i}", tracer=tr):
                    go.wait()

        ts = [threading.Thread(target=work, args=(i,)) for i in (1, 2)]
        with span("main", tracer=tr, step=0):
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=20)
        assert not any(t.is_alive() for t in ts)
        by = {r["name"]: r for r in _spans(tr)}
        for i in (1, 2):
            assert by[f"root{i}"]["parent"] is None      # not main's child
            assert by[f"kid{i}"]["parent"] == by[f"root{i}"]["id"]
            assert by[f"kid{i}"]["step"] == i
        assert by["main"]["parent"] is None

    def test_an_exception_closes_the_span_and_unwinds_the_stack(self):
        tr = Tracer()
        with pytest.raises(ValueError):
            with span("outer", tracer=tr):
                with span("inner", tracer=tr):
                    raise ValueError("boom")
        assert [r["name"] for r in _spans(tr)] == ["inner", "outer"]
        with span("after", tracer=tr):
            pass
        assert _spans(tr, "after")[0]["parent"] is None

    def test_export_carries_id_parent_and_step(self, tmp_path):
        tr = Tracer()
        with span("root", tracer=tr, step=2):
            with span("kid", tracer=tr):
                pass
        doc = json.load(open(tr.export(str(tmp_path / "t.json"))))
        x = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        assert x["kid"]["parent"] == x["root"]["id"]
        assert x["root"]["parent"] is None
        assert x["kid"]["step"] == x["root"]["step"] == 2

    def test_off_cost_is_microseconds(self):
        """Nobody listening: an is_enabled() and a TraceMe. Held to a
        bound two orders above the reading (0.6-0.9 us here) so that a
        loaded test host cannot fail it."""
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            with span("off"):
                pass
        assert (time.perf_counter() - t0) / n < 100e-6


class TestProfilerSession:
    """A live jax profiler session is what switches the spans on, and
    the two clocks agree."""

    def test_same_span_in_the_ring_and_on_the_xplane(self, tmp_path):
        from jax.profiler import ProfileData
        ring = telemetry.default_tracer()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        assert not jax.profiler.TraceAnnotation.is_enabled()
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            assert telemetry.listening() is ring
            with span("clock_anchor"):
                time.sleep(0.002)
            time.sleep(0.01)
            with span("clock_probe", step=5, rows=3):
                time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
        assert telemetry.listening() is None
        rec = {r["name"]: r for r in _spans(ring)
               if r["name"].startswith("clock_")}
        assert set(rec) == {"clock_anchor", "clock_probe"}
        path = [os.path.join(d, f) for d, _, fs in os.walk(str(tmp_path))
                for f in fs if f.endswith(".xplane.pb")][0]
        seen = {}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name in rec:
                        seen[e.name] = e
        assert set(seen) == set(rec), "a span is missing from the xplane"
        stats = dict(seen["clock_probe"].stats)
        assert stats["step"] == 5 and stats["rows"] == 3
        offset = seen["clock_anchor"].start_ns \
            - rec["clock_anchor"]["ts"] * 1e9
        probe = rec["clock_probe"]
        assert abs(seen["clock_probe"].start_ns
                   - (probe["ts"] * 1e9 + offset)) < 1e6      # 1 ms
        assert abs(seen["clock_probe"].duration_ns
                   - probe["dur"] * 1e9) < 1e6


# -- the compile path --------------------------------------------------------

class TestCompileEvents:
    def test_a_compile_fires_the_events_with_fun_name(self):
        ring = telemetry.default_tracer()
        c0 = dict(ring.metrics.snapshot()["counters"])
        mark = time.perf_counter()

        def tracing_span_probe(x):
            # slow enough to trace that the event is not folded into
            # the under-1-ms record
            for _ in range(60):
                x = jnp.sin(x) * 2.0 + 1.0
            return x

        t0 = time.perf_counter()
        jax.jit(tracing_span_probe)(jnp.ones((7, 3)))
        t1 = time.perf_counter()
        new = [r for r in ring.records()
               if r["kind"] == "event" and r["ts"] >= mark]
        by = {}
        for r in new:
            by.setdefault(r["name"], []).append(r["args"])
        for name in ("compile.trace", "compile.lower", "compile.backend"):
            funs = [a["fun_name"] for a in by.get(name, [])]
            assert any("tracing_span_probe" in f for f in funs), (name, funs)
        assert "compile.cache_request" in by
        c1 = ring.metrics.snapshot()["counters"]
        for key in ("compile.trace_s", "compile.lower_s",
                    "compile.backend_s"):
            assert c1[key] > c0.get(key, 0.0)
        assert c1["compile.cache_requests"] > c0.get(
            "compile.cache_requests", 0)
        # the account of the interval is wall time: no more than it
        got = telemetry.compile_seconds(t0, t1)
        assert set(got) >= {"trace_s", "lower_s", "backend_s"}
        assert 0 < sum(got.values()) <= (t1 - t0) * 1.001

    def test_nested_traces_are_counted_once(self):
        ring = telemetry.default_tracer()

        @jax.jit
        def tracing_inner(x):
            for _ in range(40):
                x = jnp.cos(x) + 1.0
            return x

        def tracing_outer(x):
            for _ in range(40):
                x = jnp.tanh(x) * 0.5
            return tracing_inner(x) + 1.0

        mark = time.perf_counter()
        jax.jit(tracing_outer)(jnp.ones(5))
        ev = {r["args"]["fun_name"]: r["args"] for r in ring.records()
              if r["kind"] == "event" and r["name"] == "compile.trace"
              and r["ts"] >= mark}
        outer, inner = ev["tracing_outer"], ev["tracing_inner"]
        assert inner["self_s"] <= inner["seconds"]
        assert outer["self_s"] <= outer["seconds"] - inner["seconds"] + 1e-9

    def test_small_events_share_a_record(self):
        ring = telemetry.default_tracer()

        def folded():
            recs = [r["args"] for r in ring.records()
                    if r["name"] == "compile.trace" and "n" in r["args"]]
            return (len(recs), sum(a["n"] for a in recs),
                    sum(a["self_s"] for a in recs))

        r0, n0, s0 = folded()
        c0 = ring.metrics.snapshot()["counters"].get(
            "events.compile.trace", 0)
        for i in range(50):
            telemetry._on_compile_duration(
                "/jax/core/compile/jaxpr_trace_duration", 1e-5,
                fun_name=f"tiny{i}")
        r1, n1, s1 = folded()
        assert r1 - r0 <= 2           # one record, two across a second
        assert n1 - n0 == 50
        assert s1 - s0 == pytest.approx(50e-5)
        assert ring.metrics.snapshot()["counters"][
            "events.compile.trace"] == c0 + 50

    def test_unknown_events_are_ignored(self):
        ring = telemetry.default_tracer()
        n0 = len(ring.records())
        telemetry._on_compile_duration("/jax/other", 1.0)
        telemetry._on_compile_event("/jax/other")
        assert len(ring.records()) == n0

    def test_compile_watch_takes_its_durations_from_the_events(self):
        tr = Tracer()
        watch = CompileWatch(tr)

        def watched_program(x):
            for _ in range(60):
                x = jnp.sin(x) + 0.5
            return x

        fn = jax.jit(watched_program)
        watch.register("probe", fn)
        x = jnp.ones((3, 5))
        t0 = time.perf_counter()
        fn(x)
        t1 = time.perf_counter()
        assert watch.observe(fn, t0, t1, (0, 0, 0, x)) == (1, 0)
        rec = watch.records[0]
        assert rec["family"] == "probe" and rec["signature"] == "f4[3x5]"
        for key in ("trace_s", "lower_s", "backend_s"):
            assert 0 < rec[key] <= rec["wall_s"]
        args = _spans(tr, "compile")[0]["args"]
        assert args["backend_s"] == rec["backend_s"]


# -- TrainStep ---------------------------------------------------------------

class TestTrainStepSpans:
    def test_five_spans_and_one_compile(self):
        step, ids = _trainer()
        tr = step.tracer = Tracer()
        l1 = float(step(ids, ids)._value)
        assert step.compile_watch.compiles == 1
        first = _spans(tr)
        assert sorted(r["name"] for r in first) == [
            "compile", "train_step", "train_step.args",
            "train_step.build", "train_step.dispatch",
            "train_step.rebind"]
        root = _spans(tr, "train_step")[0]
        assert root["step"] == 0 and root["args"]["step_num"] == 0
        kids = [r for r in first if r["parent"] == root["id"]]
        assert len(kids) == 4
        assert sum(r["dur"] for r in kids) <= root["dur"]
        # the compile is the dispatch's child and carries jax's account
        comp = _spans(tr, "compile")[0]
        assert comp["parent"] == _spans(tr, "train_step.dispatch")[0]["id"]
        assert comp["args"]["family"] == "step"
        assert comp["args"]["backend_s"] > 0
        l2 = float(step(ids, ids)._value)
        assert step.compile_watch.compiles == 1          # unchanged
        second = [r for r in _spans(tr) if r["step"] == 1]
        assert sorted(r["name"] for r in second) == [
            "train_step", "train_step.args", "train_step.dispatch",
            "train_step.rebind"]
        assert l2 < l1
        step.compile_watch.seal()
        step(ids, ids)
        assert step.compile_watch.unexpected_recompiles == 0

    def test_losses_are_bitwise_the_same_traced_or_not(self):
        plain, ids = _trainer()
        traced, _ = _trainer()
        traced.tracer = Tracer()
        for _ in range(3):
            a = np.asarray(plain(ids, ids)._value)
            b = np.asarray(traced(ids, ids)._value)
            assert a.tobytes() == b.tobytes()
        assert plain.tracer is None

    def test_gradient_merge_registers_both_programs(self):
        paddle.seed(3)
        m = LlamaForCausalLM(CFG)
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=m.parameters())
        step = paddle.jit.TrainStep(m, lambda o, l: m.loss(o, l), opt,
                                    gradient_merge=2)
        tr = step.tracer = Tracer()
        ids = paddle.to_tensor(_prompt(32).reshape(2, 16))
        for _ in range(4):
            step(ids, ids)
        assert step.compile_watch.families == ["accum_step", "apply_step"]
        assert step.compile_watch.compiles == 2
        assert opt._step_count == 2
        assert len(_spans(tr, "train_step.dispatch")) == 4
        assert len(_spans(tr, "train_step.rebind")) == 4


# -- ServingEngine -----------------------------------------------------------

STEP_KIDS = {"engine.deadlines", "engine.admit", "engine.plan",
             "engine.dispatch", "engine.collect", "engine.deliver",
             "engine.prefill_dispatch", "engine.prefill_collect"}


class TestEngineSpans:
    @pytest.mark.parametrize("ragged", [True, False],
                             ids=["ragged", "dense"])
    def test_tokens_are_bitwise_the_same_traced_or_not(self, model, ragged):
        ring = telemetry.default_tracer()
        before = len(_spans(ring))
        _, plain = _serve(model, ragged=ragged)
        # nobody listened: the process-wide ring got compile events only
        assert len(_spans(ring)) == before
        assert {r["name"].split(".")[0] for r in ring.records()
                if r["kind"] == "event"} <= {"compile"}
        _, traced = _serve(model, Tracer(), ragged=ragged)
        for a, b in zip(plain, traced):
            assert a.tobytes() == b.tobytes()

    @pytest.mark.parametrize("ragged", [True, False],
                             ids=["ragged", "dense"])
    def test_children_sum_to_no_more_than_the_step(self, model, ragged):
        tr = Tracer()
        eng, _ = _serve(model, tr, ragged=ragged)
        steps = _spans(tr, "engine.step")
        assert [r["step"] for r in steps] == \
            list(range(1, len(steps) + 1))
        names = set()
        for root in steps:
            kids = [r for r in _spans(tr) if r["parent"] == root["id"]]
            names |= {r["name"] for r in kids}
            assert {r["step"] for r in kids} == {root["step"]}
            assert sum(r["dur"] for r in kids) <= root["dur"]
            for r in kids:
                assert root["ts"] <= r["ts"]
                assert r["ts"] + r["dur"] <= root["ts"] + root["dur"]
        assert names <= STEP_KIDS | {"compile"}
        want = {"engine.deadlines", "engine.admit", "engine.dispatch",
                "engine.collect", "engine.deliver"}
        want |= {"engine.plan"} if ragged else {"engine.prefill_dispatch"}
        assert want <= {r["name"] for r in _spans(tr)}

    @pytest.mark.parametrize("tracer", [None, "ring"],
                             ids=["untraced", "traced"])
    def test_time_by_phase_sums_to_the_three_floats(self, model, tracer):
        eng, _ = _serve(model, Tracer() if tracer else None)
        st = eng.stats()
        by = st["time_by_phase_s"]
        assert set(by) <= set(STEP_KIDS)
        assert {"engine.plan", "engine.dispatch", "engine.collect"} <= \
            set(by)
        assert sum(by.values()) == pytest.approx(
            st["time_host_s"] + st["time_decode_stall_s"]
            + st["time_prefill_s"], rel=1e-9)
        assert by["engine.plan"] + by["engine.dispatch"] == \
            pytest.approx(st["time_host_s"], rel=1e-9)
        assert by["engine.collect"] == pytest.approx(
            st["time_decode_stall_s"], rel=1e-9)
        eng.clear_finished()
        assert eng.stats()["time_by_phase_s"] == {}

    def test_dispatch_collect_and_requests_are_one_chain(self, model):
        tr = Tracer()
        eng, _ = _serve(model, tr)
        disp = [r for r in _spans(tr, "engine.dispatch")
                if "dispatch" in r["args"]]
        seqs = [r["args"]["dispatch"] for r in disp]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        assert seqs[-1] == eng._dispatch_seq
        for r in disp:
            assert r["args"]["T"] >= 1 and r["args"]["W"] >= 1
            assert r["args"]["decode_cols"] + r["args"]["prefill_tokens"] > 0
        collected = [r["args"]["dispatch"]
                     for r in _spans(tr, "engine.collect")]
        assert collected == seqs            # every launch is waited for
        delivered = [r["args"]["dispatch"]
                     for r in _spans(tr, "engine.deliver")]
        assert delivered == seqs
        steps = {r["id"]: r for r in _spans(tr, "engine.step")}
        for r in _spans(tr, "decode") + _spans(tr, "prefill"):
            # the request's phase hangs, through the phase that closed
            # it, under the engine.step that served it, and names the
            # launch that carried its last token
            assert r["trace"] is not None and r["step"] is not None
            assert r["args"]["dispatch"] in seqs
            up = r
            while up["parent"] is not None and up["id"] not in steps:
                up = next(x for x in _spans(tr) if x["id"] == up["parent"])
            assert up["name"] == "engine.step"
            assert up["step"] == r["step"]

    def test_spec_decoding_keeps_its_prelude_out_of_the_phase(self, model):
        """A ragged engine without speculation opens no empty
        engine.dispatch for the spec probe."""
        tr = Tracer()
        _serve(model, tr)
        assert all("dispatch" in r["args"]
                   for r in _spans(tr, "engine.dispatch"))


# -- names on the device side ------------------------------------------------

class TestDeviceNames:
    def test_jit_step_holds_the_scopes(self):
        step, ids = _trainer()
        step(ids, ids)
        p = [t._value for t in step._p_tensors]
        b = [t._value for t in step._b_tensors]
        low = step._compiled.lower(
            p, b, step.optimizer._state, jnp.float32(1e-3),
            jax.random.PRNGKey(0), (ids._value,), (ids._value,))
        assert "jit_step" in low.as_text()[:200] \
            or "@jit_step" in low.as_text() or "jit_step" in str(
                low.compiler_ir().operation.attributes["sym_name"])
        text = low.as_text(debug_info=True)
        for scope in ("fwd_bwd", "optimizer", "embed", "layer0/attn",
                      "layer0/mlp", "layer1/mlp", "final_norm", "lm_head",
                      "loss"):
            assert scope in text, scope
        assert "transpose(jvp(layer0/mlp))" in text      # the backward

    def test_jit_ragged_chunk_holds_the_scopes(self, model):
        eng = ServingEngine(model, **dict(KW, ragged=True))
        eng.add_request(_prompt(9), SamplingParams(max_new_tokens=3))
        eng.run_to_completion()
        from benchmark import systems
        T, W = sorted(systems.ragged_program_set(eng))[0]
        cache = eng.dec.cache
        args = (eng.dec.weights, cache.k, cache.v) \
            + systems.ragged_operands(eng, T, W)
        low = eng._ragged_j.lower(*args)
        assert "jit_ragged_chunk" in str(
            low.compiler_ir().operation.attributes["sym_name"])
        text = low.as_text(debug_info=True)
        for scope in ("embed", "layer0/attn", "layer0/mlp", "layer1/attn",
                      "final_norm", "lm_head", "sample"):
            assert scope in text, scope

    @pytest.mark.parametrize("path", [
        "paddle_tpu/ops/pallas/flash_attention.py",
        "paddle_tpu/ops/pallas/ragged_paged_attention.py",
        "paddle_tpu/ops/pallas/decode_matmul.py",
        "paddle_tpu/ops/pallas/paged_attention.py"])
    def test_every_pallas_call_has_a_name(self, path):
        tree = ast.parse(open(os.path.join(REPO, path)).read())
        calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
                 and isinstance(n.func, ast.Attribute)
                 and n.func.attr == "pallas_call"]
        assert calls
        names = []
        for c in calls:
            kw = {k.arg: k.value for k in c.keywords}
            assert "name" in kw, f"{path}:{c.lineno} has no name="
            names.append(ast.unparse(kw["name"]))
        assert len(set(names)) == len(names)
        if path.endswith("flash_attention.py"):
            # the three calls take theirs from _names: the old ones
            # without a window, flash_win_* under one
            from paddle_tpu.ops.pallas.flash_attention import _names
            assert sorted(names) == ["_names(window)[0]", "names[1]",
                                     "names[2]"]
            assert _names(None) == ("flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv")
            assert _names(4096) == ("flash_win_fwd", "flash_win_bwd_dq",
                                    "flash_win_bwd_dkv")

    def test_the_kernel_name_reaches_the_lowered_program(self):
        from paddle_tpu.ops.pallas.flash_attention import \
            flash_attention_pallas
        q = jnp.ones((1, 2, 128, 64), jnp.float32)

        def f(q):
            with jax.named_scope("layer0/attn"):
                return flash_attention_pallas(q, q, q, causal=True).sum()

        text = jax.jit(jax.grad(f)).lower(q).as_text(debug_info=True)
        assert "jvp(layer0/attn)/flash_fwd" in text
        for name in ("flash_bwd_dq", "flash_bwd_dkv"):
            assert f"/{name}" in text, name


# -- reading a capture -------------------------------------------------------

CHIP_TRACE = os.path.join(REPO, "tests", "data", "xprof_train_step")


class TestDeviceTraceSummary:
    """``tests/data/xprof_train_step``: one training step of the
    benchmark's cell, cut from a chip run of this PR (operation names
    shortened, only the ``tf_op`` stat kept)."""

    @pytest.fixture(scope="class")
    def summary(self):
        return profiler.device_trace_summary(CHIP_TRACE)

    def test_lanes_and_events(self, summary):
        assert summary["device_lanes"] == ["/device:TPU:0"]
        assert summary["device_events"] == 772
        assert len(summary["top_kernels"]) == 5

    def test_time_by_scope_folds_the_layer_and_marks_the_backward(
            self, summary):
        scopes = {s: sec for s, sec, _ in summary["by_scope"]}
        assert "fwd_bwd/jvp(layer*/mlp)" in scopes
        assert "fwd_bwd/transpose(jvp(layer*/mlp))" in scopes
        assert not any("layer0" in s or "layer1" in s for s in scopes)
        # the backward of the MLPs is the costliest scope of a step,
        # about twice its forward
        assert summary["by_scope"][0][0] == \
            "fwd_bwd/transpose(jvp(layer*/mlp))"
        ratio = scopes["fwd_bwd/transpose(jvp(layer*/mlp))"] \
            / scopes["fwd_bwd/jvp(layer*/mlp)"]
        assert 1.5 < ratio < 2.5
        total = sum(sec for _, sec, _ in summary["by_scope"])
        assert 0.5 < total < 0.6             # one step of 0.594 s

    def test_time_by_kernel(self, summary):
        kernels = {k: (sec, n) for k, sec, n in summary["by_kernel"]}
        assert set(kernels) == {"flash_fwd", "flash_bwd_dq",
                                "flash_bwd_dkv"}
        assert kernels["flash_fwd"][1] == 2          # one a layer
        assert kernels["flash_bwd_dkv"][0] > kernels["flash_fwd"][0]

    def test_a_run_number_gets_a_layers_name(self, summary):
        ops = {op: scope for op, scope, _, _ in summary["top_ops"]}
        assert ops["fusion.318"] == "fwd_bwd/transpose(jvp(lm_head))"
        assert ops["fusion.334"] == "fwd_bwd/transpose(jvp(layer*/mlp))"
        assert ops["fusion.418"] == "fwd_bwd/jvp(lm_head)"

    def test_the_programs_host_spans(self, summary):
        spans = {n: (c, tot, p50) for n, c, tot, p50
                 in summary["host_spans"]}
        assert set(spans) == {"train_step", "train_step.args",
                              "train_step.dispatch", "train_step.rebind"}
        assert spans["train_step"][0] == 4
        kids = sum(spans[k][1] for k in spans if k != "train_step")
        assert kids <= spans["train_step"][1]

    @pytest.mark.parametrize("tf_op,scope", [
        ("jit(step)/fwd_bwd/transpose(jvp(layer1/mlp))/dot_general:",
         "fwd_bwd/transpose(jvp(layer*/mlp))"),
        ("jit(step)/fwd_bwd/jvp(layer0/attn)/flash_fwd/pallas_call:",
         "fwd_bwd/jvp(layer*/attn)/flash_fwd"),
        ("jit(step)/optimizer/mul", "optimizer"),
        ("jit(ragged_chunk)/while/body/layer12/mlp/dot_general",
         "while/body/layer*/mlp"),
        ("jit(step)/fwd_bwd/jvp(loss)/jit(log_softmax)/reduce_max:",
         "fwd_bwd/jvp(loss)/jit(log_softmax)"),
        ("jit(step)/copy", "(no scope)"),
    ])
    def test_scope_of(self, tf_op, scope):
        assert profiler.scope_of(tf_op) == scope

    def test_kernel_of(self):
        line = ('%flash_bwd_dq.11 = custom-call(...), '
                'custom_call_target="tpu_custom_call"')
        assert profiler.kernel_of(line) == "flash_bwd_dq"
        assert profiler.kernel_of("%fusion.3 = fusion(...)") is None
        assert profiler.kernel_of(
            '%x.1 = custom-call(...), custom_call_target="Sharding"') is None

    def test_a_host_only_capture_has_no_lanes(self, tmp_path):
        jax.profiler.start_trace(str(tmp_path))
        with span("train_step", step=0):
            np.asarray(jnp.ones(3) + 1)
        jax.profiler.stop_trace()
        s = profiler.device_trace_summary(str(tmp_path))
        assert s["device_lanes"] == [] and s["device_events"] == 0
        assert s["by_scope"] == [] and s["by_kernel"] == []
        assert [h[0] for h in s["host_spans"]] == ["train_step"]


# -- trace_report ------------------------------------------------------------

class TestTraceReportSelfTime:
    def _doc(self, tmp_path):
        tr = Tracer()
        t = time.perf_counter() + 1.0     # export rebases to the ring's birth
        # engine.step 0-10 ms: plan 1-2, dispatch 2-6 holding a flush's
        # collect 3-5, deliver 7-9; a request's decode 0-8 closed inside
        tr.span("engine.step", None, t, t + .010, id=1, parent=None, step=1)
        tr.span("engine.plan", None, t + .001, t + .002, id=2, parent=1,
                step=1)
        tr.span("engine.dispatch", None, t + .002, t + .006, id=3,
                parent=1, step=1)
        tr.span("engine.collect", None, t + .003, t + .005, id=4,
                parent=3, step=1)
        tr.span("engine.deliver", None, t + .007, t + .009, id=5,
                parent=1, step=1)
        tr.span("decode", 77, t - .020, t + .008, id=6, parent=5, step=1)
        return json.load(open(tr.export(str(tmp_path / "t.json"))))

    def test_self_time_is_duration_minus_what_children_cover(self, tmp_path):
        from tools.trace_report import analyze, format_report
        rep = analyze(self._doc(tmp_path))
        ph = rep["phases"]
        assert ph["engine.step"]["total_s"] == pytest.approx(0.010)
        assert ph["engine.step"]["self_s"] == pytest.approx(0.003)
        assert ph["engine.dispatch"]["self_s"] == pytest.approx(0.002)
        assert ph["engine.collect"]["self_s"] == pytest.approx(0.002)
        # the request phase began before the span it hangs under: it
        # takes nothing from it
        assert ph["engine.deliver"]["self_s"] == pytest.approx(0.002)
        assert ph["decode"]["self_s"] == pytest.approx(0.028)
        assert "self=" in format_report(rep)

    def test_program_phases_do_not_count_as_replica_occupancy(
            self, tmp_path):
        from tools.trace_report import analyze
        rep = analyze(self._doc(tmp_path))
        assert rep["replicas"]["replica0"]["busy_s"] == pytest.approx(0.028)

    def test_a_trace_without_ids_counts_spans_whole(self):
        from tools.trace_report import analyze
        doc = {"traceEvents": [
            {"ph": "X", "name": "decode", "pid": 0, "tid": 3, "ts": 0.0,
             "dur": 5e3, "args": {}}]}
        assert analyze(doc)["phases"]["decode"]["self_s"] == \
            pytest.approx(0.005)
