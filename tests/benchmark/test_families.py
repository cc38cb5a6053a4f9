"""A model family is files. ``data/gpt_family/`` holds a second family
(the GPT-2 equations over the program's ``GPTForCausalLM``) with its
leaves, builder, reference, work counts, a metric file with a kernel
count of its own, a configuration, two mixes, limits and a driver. These
tests copy it beside an UNTOUCHED copy of ``benchmark/`` and run its cells
there: nothing under ``benchmark/`` knows the family or the driver."""
import filecmp
import importlib
import json
import os
import shutil
import sys

import pytest

import tiny_tree

REPO = tiny_tree.REPO
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "gpt_family")
MS = 1_000_000


def _ours():
    return [k for k in sys.modules
            if k == "benchmark" or k.startswith("benchmark.")]


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """The copy; while the test runs, ``benchmark`` is the copy's package
    and ``BENCHMARK.json`` the fixture's."""
    tmp = str(tmp_path)
    real = os.path.join(REPO, "benchmark")
    shutil.copytree(real, os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(FIXTURE, tmp, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    # the fixture added files and replaced none
    same = filecmp.dircmp(real, os.path.join(tmp, "benchmark"),
                          ignore=["__pycache__"])
    stack = [same]
    while stack:
        d = stack.pop()
        assert not d.diff_files and not d.left_only, (d.left, d.diff_files)
        stack += d.subdirs.values()
    saved = {k: sys.modules.pop(k) for k in _ours()}
    monkeypatch.syspath_prepend(tmp)
    importlib.invalidate_caches()
    try:
        assert importlib.import_module("benchmark.run").ROOT == tmp
        tiny_tree.let_cpu_through(monkeypatch)
        yield tmp
    finally:
        for k in _ours():
            del sys.modules[k]
        sys.modules.update(saved)


def _run(capsys, cell, seed=2 ** 31 + 29):
    run = importlib.import_module("benchmark.run")
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "1.0", "--trace", "0"])
    return rc, tiny_tree.last_json_line(capsys)


@pytest.mark.parametrize("cell", ["t_gpt_train", "t_gpt_unread"])
def test_a_family_and_a_driver_that_are_files_run_a_cell(tree, capsys, cell):
    rc, line = _run(capsys, cell)
    assert rc == 0 and line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert line["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}
    judged = {k for k, c in line["checks"].items() if c["limit"] is not None}
    assert judged >= {"loss_rel_gap_max", "grad_norm_gap_worst_leaf",
                      "change_norm_gap_worst_leaf"}
    # biases start at nought and gains at one, by the family's own kinds
    from benchmark import manifest, weights
    cfg = manifest.config_of(manifest.workload(cell))
    seeded = weights.Leaves(manifest.family_of(cfg), cfg, 5)
    assert float(abs(seeded.make("h.1.fc.b")).max()) == 0.0
    assert float(seeded.make("lnf.g").min()) == 1.0
    assert float(seeded.make("h.0.qkv.w").std()) == pytest.approx(
        0.02, rel=0.05)


def test_half_of_the_batch_left_out_of_the_new_family(tree, capsys,
                                                      monkeypatch):
    from benchmark import systems
    call = systems.Trainer.__call__
    monkeypatch.setattr(systems.Trainer, "__call__",
                        lambda self, ids: call(self, ids[:len(ids) // 2]))
    rc, line = _run(capsys, "t_gpt_train")
    assert rc == 0 and line["correct"] is False
    grad = line["checks"]["grad_norm_gap_worst_leaf"]
    assert grad["value"] > grad["limit"]


def test_the_new_familys_control_fails_the_comparison(tree):
    from benchmark import check, manifest, traffic
    from benchmark.drivers import train_steps
    cfg = manifest.config_of(manifest.workload("t_gpt_train"))
    mix = traffic.load_mix("t_gpt_steps")
    batches = [train_steps.feed(mix, 512, 7, s) for s in range(3)]
    ref = check.reference_train_readings(cfg, 7, batches)
    low = check.reference_train_readings(cfg, 7, batches, precision="lower")
    ok, _ = check.judge(check.train_numbers(low, ref),
                        check.load_limits("t_gpt_train"))
    assert not ok


def _traced(tree):
    """A hand-made trace of two steps, 40 ms of ``jit_step`` holding
    30 ms of fusions, as the fixture's cell would have traced it."""
    from benchmark import manifest, trace_reduce as tr, traffic
    cell = manifest.workload("t_gpt_train")
    cfg = manifest.config_of(cell)
    ops = [["fusion.1", 10 * MS, 15 * MS], ["fusion.1", 50 * MS, 15 * MS]]
    modules = [["jit_step(1)", 10 * MS, 20 * MS],
               ["jit_step(1)", 50 * MS, 20 * MS]]
    return {"model": cfg["model"], "cfg": cfg,
            "mix": traffic.load_mix(cell["traffic"]),
            "family": manifest.family_of(cfg),
            "peak": {"flops_per_s_bf16": 1e12, "bytes_per_s_hbm": 1e11},
            "res": {"window": (0.0, 0.1)}, "trace_clock": (0.0, 0.1),
            "trace": {"planes": {
                "/device:TPU:0": {tr.MODULES_LINE: modules,
                                  tr.OPS_LINE: ops},
                "host": {"spans": [["bench:window", 0, 100 * MS]]}}}}


def test_work_is_counted_by_the_family(tree):
    from benchmark import manifest, run
    ctx = _traced(tree)
    names = manifest.metrics_for("t_gpt_train", "per_layer")
    assert names == ["step_mfu.train", "gelu_mlp_roofline.train"]
    got = run.read_per_layer(names, ctx)
    # by hand: 2 layers of 4 h^2 + 2 h it, the tied head; 2 steps of 2 x 64
    tokens, pairs = 2 * 64, 2 * (64 * 65 // 2)
    params = 2 * (4 * 128 * 128 + 2 * 128 * 512) + 128 * 512
    step = 3 * (2 * params * tokens + 4 * 128 * 2 * pairs)
    assert got["step_mfu.train"]["value"] == pytest.approx(
        100.0 * 2 * step / (0.040 * 1e12))
    mlp = 3 * 2 * (2 * 128 * 512) * 2 * tokens
    assert got["gelu_mlp_roofline.train"]["value"] == pytest.approx(
        100.0 * (2 * mlp / 1e12) / 0.030)
    # the same metric file under a family whose table lacks the count
    ctx["family"] = manifest.module("families", "llama")
    with pytest.raises(ValueError, match="counts no 'mlp_flops'; it counts: "
                                         "decode_kv_bytes, flash_flops"):
        run.read_per_layer(["gelu_mlp_roofline.train"], ctx)


def test_a_driver_is_a_module_that_exists(tree):
    from benchmark import manifest, traffic
    assert traffic.load_mix("t_gpt_unread")["driver"] == "train_steps_unread"
    assert callable(manifest.module("drivers", "train_steps_unread").run)
    with open(os.path.join(tree, "benchmark", "traffic", "lost.json"),
              "w") as f:
        json.dump({"driver": "absent"}, f)
    with pytest.raises(ValueError, match="no driver 'absent'; there are: "
                       "closed_loop, open_loop, train_steps, "
                       "train_steps_unread"):
        traffic.load_mix("lost")
    # in the repo's own tree the fixture's driver is not there
    assert "train_steps_unread" not in os.listdir(
        os.path.join(REPO, "benchmark", "drivers"))
