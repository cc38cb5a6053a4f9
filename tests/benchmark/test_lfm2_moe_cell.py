"""The LFM2-8B-A1B cell's own files (family ``lm_lfm2_moe``, reference
``lfm2_moe_ref``, the three metric files that came with them) at tiny
size on the CPU, and its work counts by hand."""
import copy
import json
import os

import numpy as np
import pytest

import tiny_tree

from benchmark import check, manifest, run, traffic
from benchmark.drivers import train_steps
from benchmark.families import lm_lfm2_moe as family

REPO = tiny_tree.REPO
CONFIG = "benchmark/configs/lfm2_8b_a1b_ep4_l5_train.json"
CELL = "lfm2_ep4_l5_train_s8192"
MS = 1_000_000


def real_cfg():
    return manifest.load_json(REPO, CONFIG)


def tiny_cfg():
    """The real file cut to CPU size: the same five layers (a dense
    convolution layer, then attention and three convolutions with
    experts), 8 experts of which share 1 of 4 holds 2, top-2."""
    cfg = copy.deepcopy(real_cfg())
    cfg["model"].update(
        hidden_size=64, intermediate_size=96, moe_intermediate_size=48,
        num_attention_heads=4, num_key_value_heads=2, num_experts=2,
        expert_share=[1, 4], num_experts_per_tok=2, vocab_size=128)
    return cfg


@pytest.fixture
def tree(tmp_path, monkeypatch):
    bench = tiny_tree.point_at(monkeypatch, str(tmp_path))
    data = tmp_path / "benchmark"
    (data / "configs" / "t_lfm2.json").write_text(json.dumps(tiny_cfg()))
    (data / "traffic" / "tiny_lfm2.json").write_text(json.dumps(
        {"driver": "train_steps", "batch": 2, "seq": 64, "trace_steps": 2,
         "check_steps": 3}))
    # between what a sound run reads here (loss 3e-5, gradient 0.006-0.008,
    # change 0.001-0.003) and what the control (gradient 0.87, change 264)
    # and the planted fault (gradient 0.55-0.80) read
    (data / "limits" / "t_lfm2.json").write_text(json.dumps(
        {"numbers": {"loss_rel_gap_max": {"limit": 0.001},
                     "grad_norm_gap_worst_leaf": {"limit": 0.05},
                     "change_norm_gap_worst_leaf": {"limit": 0.05}}}))
    real = manifest.load_json(REPO, "BENCHMARK.json")
    bench["configs"].append({"name": "t_lfm2", "source": "test",
                             "file": "benchmark/configs/t_lfm2.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "t_lfm2", "config": "t_lfm2",
                               "traffic": "tiny_lfm2", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][3]["workloads"].append("t_lfm2")
    for m in real["per_layer"]:
        if CELL in m["workloads"]:
            bench["per_layer"].append(dict(m, workloads=["t_lfm2"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    tiny_tree.let_cpu_through(monkeypatch)
    return bench


def _run(capsys, seed=2 ** 31 + 7):
    rc = run.main(["--workload", "t_lfm2", "--seed", str(seed),
                   "--seconds", "1.0", "--trace", "0"])
    return rc, tiny_tree.last_json_line(capsys)


def test_the_cells_files_run_at_tiny_size(tree, capsys):
    rc, line = _run(capsys)
    assert rc == 0 and line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert line["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}
    judged = {k for k, c in line["checks"].items() if c["limit"] is not None}
    assert judged >= {"loss_rel_gap_max", "grad_norm_gap_worst_leaf",
                      "change_norm_gap_worst_leaf"}
    # the experts' counters were published when the window closed, and
    # the readers still find them once the trainer is freed
    from benchmark.readers import registry_ratio
    share = registry_ratio.read({}, "moe.rows_held", "moe.rows_routed")
    assert 0.1 < share < 0.4            # 2 of 8 experts: 0.25 when even
    busiest = registry_ratio.read({}, "moe.rows_max_expert", "moe.rows_held",
                                  scale=8)      # 4 layers x 2 held
    assert 1.0 <= busiest < 3.0


def test_half_of_the_batch_left_out_is_not_correct(tree, capsys,
                                                   monkeypatch):
    from benchmark import systems
    call = systems.Trainer.__call__
    monkeypatch.setattr(systems.Trainer, "__call__",
                        lambda self, ids: call(self, ids[:len(ids) // 2]))
    rc, line = _run(capsys)
    assert rc == 0 and line["correct"] is False
    grad = line["checks"]["grad_norm_gap_worst_leaf"]
    assert grad["value"] > grad["limit"]


def test_the_control_fails_the_comparison(tree):
    cfg = manifest.config_of(manifest.workload("t_lfm2"))
    mix = traffic.load_mix("tiny_lfm2")
    batches = [train_steps.feed(mix, 128, 7, s) for s in range(3)]
    ref = check.reference_train_readings(cfg, 7, batches)
    low = check.reference_train_readings(cfg, 7, batches, precision="lower")
    ok, _ = check.judge(check.train_numbers(low, ref),
                        check.load_limits("t_lfm2"))
    assert not ok


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(REPO, "benchmark", "reference",
                            "lfm2_moe_ref.py")).read()
    assert "paddle_tpu" not in src and "pallas" not in src


# -- the configuration file ---------------------------------------------------

def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    for line in open(path):
        row = json.loads(line)
        if row["name"] == "LFM2-8B-A1B":
            return row
    pytest.skip("the catalog has no such row")


ATTN = "full_attention"
REDUCED = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 16384,
           "layer_types": ["conv", ATTN, "conv", "conv", "conv"]}


def test_the_two_copies_of_the_models_keys_are_equal():
    cfg = real_cfg()
    extra = {"expert_share": [0, 4], "torch_dtype": "bfloat16"}
    assert {k: v for k, v in cfg["model"].items() if k not in extra} \
        == {k: cfg[k] for k in cfg["model"] if k not in extra}
    assert {k: cfg["model"][k] for k in extra} == extra
    assert family.router_width(cfg["model"]) == 32
    assert cfg["published"] == {
        "num_hidden_layers": 24, "num_dense_layers": 2, "num_experts": 32,
        "vocab_size": 65536, "layer_types": [
            ATTN if i in (2, 6, 10, 14, 18, 21) else "conv"
            for i in range(24)]}
    # published layers 1-5: the second dense layer and the period after it
    assert cfg["published"]["layer_types"][1:6] == REDUCED["layer_types"]
    entry = [c for c in manifest.load_json(REPO, "BENCHMARK.json")["configs"]
             if c["file"] == CONFIG][0]
    assert entry["reduced"] == cfg["reduced"]
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    assert entry["source"] == cfg["source"]


def test_the_file_keeps_the_catalogs_keys_but_the_reduced_ones():
    cfg, row = real_cfg(), catalog_row()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        want = REDUCED.get(key, value)
        assert cfg[key] == want and cfg["model"][key] == want, key
        if key in REDUCED:
            assert value == cfg["published"][key]


def test_the_leaves_are_the_memory_arithmetic():
    m = real_cfg()["model"]
    sizes = {n: int(np.prod(s)) for n, s, *_ in family.leaf_shapes(m)}

    def layer(i):
        return {k.split(".")[2]: v for k, v in sizes.items()
                if k.startswith(f"layers.{i}.")}
    conv = 3 * 2048 * 2048 + 3 * 2048 + 2048 * 2048          # 16.78 M
    norms, experts = 2 * 2048, 3 * 8 * 2048 * 1792           # 88.08 M
    assert sum(layer(0).values()) == conv + 3 * 2048 * 7168 + norms
    assert set(layer(0)) == {"ln1", "ln2", "ci", "cw", "co", "w1", "w3",
                             "w2"}
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64         # 10.49 M
    assert sum(layer(1).values()) == attn + experts + 2048 * 32 + norms
    for i in (2, 3, 4):
        assert sum(layer(i).values()) == conv + experts + 2048 * 32 + norms
        assert set(layer(i)) == {"ln1", "ln2", "ci", "cw", "co", "wr", "eg",
                                 "eu", "ed"}
    assert sizes["embed"] == 16384 * 2048 and "head" not in sizes   # tied
    total = sum(sizes.values())
    assert total == 60_827_648 + 98_635_904 + 3 * 104_933_376 \
        + 33_554_432 + 2048 == 507_820_160      # x 16 B = 8.1 GB
    # every leaf has a parameter name and no two share one
    names = [family.train_param_name(n) for n in sizes]
    assert len(set(names)) == len(names) == 49


# -- work counts, by hand -----------------------------------------------------

def test_work_counts_by_hand():
    m = real_cfg()["model"]
    batch, seq = 2, 8192
    tokens = batch * seq
    causal = 8192 * 8193 // 2
    # a token's matmul parameters: four convolutions' in and out 4 x
    # 16.78 M, attention's q, k, v, o 10.49 M, the dense MLP 44.04 M, four
    # routers over 32 and 4 x 8 / 32 = 1 expected expert row of 3 x 2048 x
    # 1792 in each of four layers, the tied head 2048 x 16384
    params = 4 * (4 * 2048 * 2048) + (2 * 2048 * 2048 + 2 * 2048 * 512) \
        + 3 * 2048 * 7168 + 4 * (2048 * 32 + 3 * 2048 * 1792) \
        + 2048 * 16384
    assert family.token_matmul_params(m) == params
    assert 397e6 < 2 * params < 399e6           # MFLOP a token, forward
    attn = 4 * 64 * 32 * 1 * batch * causal     # one attention layer
    fwd = 2 * params * tokens + attn
    assert family.train_flops(m, batch, seq) == 3 * fwd
    assert 21.1e12 < 3 * fwd < 21.3e12
    work = {"steps": 4, "batch": batch, "seq": seq}
    assert family.KERNEL_WORK["flash_flops"](m, work) == 4 * 3 * attn
    rows = tokens * 4 * 8 // 32                 # 16,384 held rows a layer
    assert family.KERNEL_WORK["expert_mm_flops"](m, work) \
        == 4 * 4 * rows * 9 * 2 * 2048 * 1792
    assert family.KERNEL_WORK["flash_flops"](m, {"tokens": 1}) == 0
    assert family.KERNEL_WORK["expert_mm_flops"](m, {"tokens": 1}) == 0


def _traced(names):
    """A recorded tiny trace: two steps of 100 ms, each holding the named
    operations."""
    from benchmark import trace_reduce as tr
    cfg = real_cfg()
    ops, modules = [], []
    for step in range(2):
        t = (10 + 200 * step) * MS
        modules.append(["jit_step(7)", t, 100 * MS])
        for name, ms in names:
            ops.append([name, t, ms * MS])
            t += ms * MS
    return {"model": cfg["model"], "cfg": cfg, "family": family,
            "mix": {"batch": 2, "seq": 8192, "trace_steps": 2},
            "peak": {"flops_per_s_bf16": 197e12, "bytes_per_s_hbm": 819e9},
            "res": {"window": (0.0, 1.0)}, "trace_clock": (0.0, 1.0),
            "trace": {"planes": {
                "/device:TPU:0": {tr.MODULES_LINE: modules,
                                  tr.OPS_LINE: ops},
                "host": {"spans": [["bench:window", 0, 500 * MS]]}}}}


def test_the_new_metrics_read_the_kernels_by_name(monkeypatch):
    monkeypatch.setattr(manifest, "ROOT", REPO)
    ctx = _traced([("flash_fwd.2 tpu_custom_call", 4),
                   ("flash_bwd_dq.7 tpu_custom_call", 5),
                   ("flash_bwd_dkv.7 tpu_custom_call", 7),
                   ("ragged-dot-none.3 tpu_custom_call", 6),
                   ("ragged-dot-none.4 tpu_custom_call", 6),
                   ("fusion.12", 20)])
    names = manifest.metrics_for(CELL, "per_layer")
    assert set(names) >= {"flash_d64_roofline.train",
                          "expert_mm_roofline.train",
                          "expert_rows_busiest.train",
                          "expert_rows_share.train", "step_mfu.train"}
    traced = [n for n in names
              if manifest.metric_file(n)["source"] == "device_trace"]
    got = {k: v["value"] for k, v in run.read_per_layer(traced, ctx).items()}
    m = ctx["model"]
    attn = 3 * 4 * 64 * 32 * 2 * (8192 * 8193 // 2)           # a step
    assert got["flash_d64_roofline.train"] == pytest.approx(
        100.0 * (2 * attn / 197e12) / 0.032)          # not the experts'
    experts = 4 * 16384 * 9 * 2 * 2048 * 1792                 # a step
    assert got["expert_mm_roofline.train"] == pytest.approx(
        100.0 * (2 * experts / 197e12) / 0.024)       # not the kernels'
    assert got["step_mfu.train"] == pytest.approx(
        100.0 * 2 * family.train_flops(m, 2, 8192) / (0.2 * 197e12))
    # Mistral's pattern would take ragged-dot-none for a flash kernel
    assert "flash_attn_roofline.train" not in names
    # on a program without these kernels the readers find nothing
    bare = _traced([("fusion.12", 20)])
    assert set(run.read_per_layer(traced, bare)) <= {
        "step_mfu.train", "device_idle_share.train"}


def test_the_busiest_expert_is_read_against_the_mean_held_expert(
        monkeypatch):
    from benchmark.readers import _program
    spec = manifest.load_json(REPO, "benchmark", "metrics",
                              "expert_rows_busiest.train.json")
    reader = manifest.module("readers", spec["reader"])
    monkeypatch.setattr(_program, "counters", lambda prefixes: {})
    assert reader.read({}, **spec["args"]) is None
    monkeypatch.setattr(_program, "counters", lambda prefixes: {
        "moe.rows_max_expert": 2560, "moe.rows_held": 65536})
    assert reader.read({}, **spec["args"]) == pytest.approx(1.25)
