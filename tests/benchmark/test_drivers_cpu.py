"""The three drivers and the harness around them at a tiny size on the
CPU. The device check is let through in these tests only; a plain run on
the CPU refuses to measure."""
import json
import time

import numpy as np
import pytest

import tiny_tree
from benchmark import run, traffic
from benchmark.drivers import _serving, open_loop

BIG_SEED = 2 ** 31 + 11


# -- traffic from the seed ----------------------------------------------------

def test_same_seed_same_traffic_other_seed_same_work():
    mix = tiny_tree.MIXES["tiny_open"]
    a = traffic.poisson_due_times(6.0, 50, BIG_SEED)
    b = traffic.poisson_due_times(6.0, 50, BIG_SEED)
    c = traffic.poisson_due_times(6.0, 50, BIG_SEED + 1)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    gaps = lambda d: np.sort(np.concatenate([[2 * d[0]], np.diff(d)]))
    assert np.allclose(gaps(a), gaps(c))          # same gaps, other order
    assert 0 < a[0] and a[-1] < 50 / 6.0           # all inside the span
    pa, oa = traffic.request_sizes(mix, 50, BIG_SEED)
    pb, ob = traffic.request_sizes(mix, 50, BIG_SEED)
    pc, oc = traffic.request_sizes(mix, 50, 3)
    assert np.array_equal(pa, pb) and np.array_equal(oa, ob)
    assert not np.array_equal(pa, pc)
    assert np.array_equal(np.sort(pa), np.sort(pc))
    assert np.array_equal(np.sort(oa), np.sort(oc))
    assert pa.min() >= 8 and pa.max() <= 200
    x = traffic.train_batch(512, 2, 64, BIG_SEED, 4)
    assert np.array_equal(x, traffic.train_batch(512, 2, 64, BIG_SEED, 4))
    assert not np.array_equal(x, traffic.train_batch(512, 2, 64, BIG_SEED, 5))
    assert not np.array_equal(x[0], x[1])          # rows all differ
    assert np.array_equal(traffic.prompt_tokens(512, 9, 7, 3),
                          traffic.prompt_tokens(512, 9, 7, 3))


def test_schedule_is_the_same_for_the_same_seed():
    mix = tiny_tree.MIXES["tiny_open"]
    a = open_loop.schedule(mix, 512, 5, 2.0)
    b = open_loop.schedule(mix, 512, 5, 2.0)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.want for r in a] == [r.want for r in b]
    # another seed: the same requests after the same gaps, in the same
    # cyclic order from another starting place, with other token ids
    c = open_loop.schedule(mix, 512, 6, 2.0)
    n_ramp = int(round(mix["rate_rps"] * mix["ramp_s"]))
    wa, wc = a[n_ramp:], c[n_ramp:]
    sizes = lambda rs: [(len(r.prompt), r.want) for r in rs]
    assert sizes(wa) != sizes(wc)
    k = sizes(wc + wc).index(sizes(wa)[0])
    assert any(sizes(wc + wc)[j:j + len(wa)] == sizes(wa)
               for j in range(len(wa)))
    assert all(mix["ramp_s"] <= r.due < mix["ramp_s"] + 2.0 for r in wc)
    assert not np.array_equal(wa[0].prompt, wc[k % len(wc)].prompt)


# -- latency is taken from the due time ---------------------------------------

class FakeRequest:
    def __init__(self, want):
        self.out_tokens, self.state, self.want = [], "queued", want


class FakeEngine:
    """One token per live request per step; every step takes ``stall``
    seconds, so a slow engine makes later requests wait."""

    class _Cache:
        available_blocks, num_blocks = 1, 2

    class _Dec:
        pass

    def __init__(self, stall):
        self.stall, self.reqs = stall, {}
        self.dec = self._Dec()
        self.dec.cache = self._Cache()

    def add_request(self, prompt, sampling):
        rid = len(self.reqs)
        self.reqs[rid] = FakeRequest(sampling.max_new_tokens)
        return rid

    def _find_request(self, rid):
        return self.reqs[rid]

    @property
    def has_work(self):
        return any(r.state != "done" for r in self.reqs.values())

    def step(self):
        time.sleep(self.stall)
        for r in self.reqs.values():
            if r.state != "done":
                r.out_tokens.append(1)
                if len(r.out_tokens) >= r.want:
                    r.state = "done"

    def clear_finished(self):
        pass

    def stats(self):
        return {}


def _open_loop_on(stall):
    mix = dict(tiny_tree.MIXES["tiny_open"], rate_rps=20.0, ramp_s=0.2)
    hooks = run.Hooks(False, mix, 1.0)
    res = open_loop.run(FakeEngine(stall), mix, 512, 9, 1.0, hooks)
    assert res["attempted"] == 20 and res["failed"] == 0
    return res


def test_a_stalled_engine_raises_ttft_and_generator_lateness():
    fast, slow = _open_loop_on(0.001), _open_loop_on(0.15)
    assert fast["end_to_end"]["ttft_p95_ms"] < 60
    assert slow["end_to_end"]["ttft_p95_ms"] > 150
    assert slow["clock"]["gen_late_p95_ms"] > 100 > \
        fast["clock"]["gen_late_p95_ms"]
    # every request due in the window is judged, and from its due time:
    # a first token can never be seen before the request was due
    for r in slow["measured"]:
        assert r.t_tokens[0] >= r.due and r.sent >= r.due


def test_token_gaps_and_work_counts():
    rec = _serving.Rec(0, 0.0, np.zeros(10, np.int32), 4)
    rec.t_tokens = [1.0, 1.5, 1.5, 2.0]
    assert [g for _, g in _serving.token_gaps(rec)] == [0.5, 0.0, 0.5]
    w = _serving.work_counts([rec], 0.0, 3.0)
    # the prompt's 10 tokens and three fed-back tokens at contexts 11..13
    assert w["tokens"] == 13 and w["pairs"] == 55 + 11 + 12 + 13
    assert w["decode_tokens"] == 3 and w["decode_pairs"] == 36
    assert _serving.work_counts([rec], 1.2, 1.8)["tokens"] == 2


# -- whole runs ---------------------------------------------------------------

CELL_METRICS = {
    "t_open": {"ttft_p95_ms", "itl_p95_ms", "setup_s"},
    "t_closed": {"itl_p95_ms", "serve_tokens_per_s", "setup_s"},
    "t_train": {"train_tokens_per_s", "setup_s"},
}


def _run(capsys, cell, seed=BIG_SEED, seconds="1.5"):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   seconds, "--trace", "0"])
    return rc, tiny_tree.last_json_line(capsys)


@pytest.mark.parametrize("cell", sorted(CELL_METRICS))
def test_a_run_prints_the_contracts_line(tmp_path, monkeypatch, capsys,
                                         cell):
    tiny_tree.point_at(monkeypatch, str(tmp_path))
    tiny_tree.let_cpu_through(monkeypatch)
    rc, line = _run(capsys, cell)
    assert rc == 0
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == CELL_METRICS[cell]
    for m in line["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert line["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}
    judged = [c for c in line["checks"].values() if c["limit"] is not None]
    assert len(judged) >= 2
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}


def test_a_plain_cpu_run_refuses_to_measure(tmp_path, monkeypatch, capsys):
    tiny_tree.point_at(monkeypatch, str(tmp_path))
    rc = run.main(["--workload", "t_train", "--seed", "1", "--seconds",
                   "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""      # no result line


def test_an_unknown_device_kind_refuses_to_measure():
    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"
    import jax
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "devices", lambda: [Dev()])
        with pytest.raises(run.NoChip, match="peaks.json"):
            run.device_info(1, {"TPU v5 lite": {}})
        Dev.device_kind = "TPU v5 lite"
        with pytest.raises(run.NoChip, match="asks for 4"):
            run.device_info(4, {"TPU v5 lite": {}})
        assert run.device_info(1, {"TPU v5 lite": {}})["count"] == 1


# -- the timed path broken underneath: correct must come out false ------------

def test_fault_a_served_token_altered(tmp_path, monkeypatch, capsys):
    """The step program's sampled tokens are altered where they are
    produced: one column of every chunk comes back shifted by one id."""
    tiny_tree.point_at(monkeypatch, str(tmp_path))
    tiny_tree.let_cpu_through(monkeypatch)
    from benchmark import systems
    build = systems.build_engine

    def broken(cfg, seed):
        eng, t = build(cfg, seed)
        step = eng._ragged_j

        def altered(*args):
            toks, k, v = step(*args)
            return toks.at[:, 0].set((toks[:, 0] + 1) % 512), k, v
        altered.lower = step.lower
        altered._cache_size = step._cache_size
        eng._ragged_j = altered
        return eng, t
    monkeypatch.setattr(systems, "build_engine", broken)
    rc, line = _run(capsys, "t_open")
    assert rc == 0 and line["correct"] is False
    gap = line["checks"]["served_logit_gap_max"]
    assert gap["value"] > gap["limit"]


def _break_trainer(monkeypatch, breaker):
    from benchmark import systems
    call = systems.Trainer.__call__
    monkeypatch.setattr(systems.Trainer, "__call__",
                        lambda self, ids: breaker(self, ids, call))


def test_fault_a_step_that_returns_its_state_unchanged(tmp_path, monkeypatch,
                                                       capsys):
    tiny_tree.point_at(monkeypatch, str(tmp_path))
    tiny_tree.let_cpu_through(monkeypatch)

    def frozen(self, ids, call):
        # from the second step on the step hands back the state it was
        # given (copies: the real step donates its inputs)
        import jax
        import jax.numpy as jnp
        state = self.opt._state
        if state is None:
            return call(self, ids)
        params = [jnp.copy(p._value) for p in self.step._p_tensors]
        state = jax.tree.map(jnp.copy, state)
        loss = call(self, ids)
        for p, a in zip(self.step._p_tensors, params):
            p._replace(a)
        self.opt._state = state
        return loss
    _break_trainer(monkeypatch, frozen)
    rc, line = _run(capsys, "t_train")
    assert rc == 0 and line["correct"] is False
    change = line["checks"]["change_norm_gap_worst_leaf"]
    assert change["value"] > change["limit"]


def test_fault_half_of_the_batch_left_out(tmp_path, monkeypatch, capsys):
    tiny_tree.point_at(monkeypatch, str(tmp_path))
    tiny_tree.let_cpu_through(monkeypatch)
    _break_trainer(monkeypatch,
                   lambda self, ids, call: call(self, ids[:len(ids) // 2]))
    rc, line = _run(capsys, "t_train")
    assert rc == 0 and line["correct"] is False
    grad = line["checks"]["grad_norm_gap_worst_leaf"]
    assert grad["value"] > grad["limit"]


def test_fault_a_compile_inside_the_training_window(tmp_path, monkeypatch,
                                                    capsys):
    """The window's second step arrives at another length, so the step
    compiles again: the trainer's own watch counts it, ``correct`` comes
    out false, and the log says what the registry counted."""
    tiny_tree.point_at(monkeypatch, str(tmp_path))
    tiny_tree.let_cpu_through(monkeypatch)
    calls = []

    def reshaped(self, ids, call):
        calls.append(1)
        # three steps of set-up, then the window
        return call(self, ids[:, :32] if len(calls) == 5 else ids)
    _break_trainer(monkeypatch, reshaped)
    rc = run.main(["--workload", "t_train", "--seed", "7", "--seconds",
                   "1.5", "--trace", "0"])
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert rc == 0 and line["failed"] == 0 and line["correct"] is False
    assert line["checks"]["compiles_in_window"] == {"value": 1, "limit": 0}
    counters = json.loads(
        cap.err.split("program counters: ")[1].splitlines()[0])
    assert counters["loss.cross_entropy.grad_in_forward"] >= 1
    assert any(k.startswith("compile.") for k in counters)
    assert all(k.startswith(("compile.", "loss.")) for k in counters)
