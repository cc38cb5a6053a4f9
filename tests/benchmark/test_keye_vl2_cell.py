"""The Keye-VL-2.0 cell's own files (family ``lm_keye_vl2``, reference
``keye_vl2_ref``, the metric files and the two readers that came with
them) at tiny size on the CPU, and its work counts by hand."""
import copy
import json
import os

import numpy as np
import pytest

import tiny_tree

from benchmark import check, manifest, run, traffic
from benchmark.drivers import train_steps
from benchmark.families import lm_keye_vl2 as family

REPO = tiny_tree.REPO
CONFIG = "benchmark/configs/keye_vl2_30b_a3b_ep8_l4_train.json"
CELL = "keye_vl2_ep8_l4_train_s8192"
MS = 1_000_000


def real_cfg():
    return manifest.load_json(REPO, CONFIG)


def tiny_cfg():
    """The real file cut to CPU size: 8 experts of which share 1 of 4
    holds 2, top-2, 16 of 64 keys selected. The scale of the weights and
    the lr are this size's own: the real file's are set for 8,192 tokens
    at hidden 2048, where they keep the routing even."""
    cfg = copy.deepcopy(real_cfg())
    cfg["init_scale"] = 0.02
    cfg["optimizer"]["learning_rate"] = 1e-6
    m = cfg["model"]
    m.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, moe_intermediate_size=48,
             num_experts=2, num_local_experts=2, expert_share=[1, 4],
             num_experts_per_tok=2, vocab_size=128)
    m["sa_config"].update(indexer_num_heads=4, indexer_head_dim=16, topk=16)
    return cfg


@pytest.fixture
def tree(tmp_path, monkeypatch):
    bench = tiny_tree.point_at(monkeypatch, str(tmp_path))
    data = tmp_path / "benchmark"
    (data / "configs" / "t_keye.json").write_text(json.dumps(tiny_cfg()))
    (data / "traffic" / "tiny_keye.json").write_text(json.dumps(
        {"driver": "train_steps", "batch": 2, "seq": 64, "trace_steps": 2,
         "check_steps": 3}))
    # between what a sound run reads here (loss 2e-4, gradient 0.02,
    # change 0.004) and what the control and the planted fault read
    (data / "limits" / "t_keye.json").write_text(json.dumps(
        {"numbers": {"loss_rel_gap_max": {"limit": 0.002},
                     "grad_norm_gap_worst_leaf": {"limit": 0.1},
                     "change_norm_gap_worst_leaf": {"limit": 0.1}}}))
    real = manifest.load_json(REPO, "BENCHMARK.json")
    bench["configs"].append({"name": "t_keye", "source": "test",
                             "file": "benchmark/configs/t_keye.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "t_keye", "config": "t_keye",
                               "traffic": "tiny_keye", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][3]["workloads"].append("t_keye")
    for m in real["per_layer"]:
        if CELL in m["workloads"]:
            bench["per_layer"].append(dict(m, workloads=["t_keye"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    tiny_tree.let_cpu_through(monkeypatch)
    return bench


def _run(capsys, seed=2 ** 31 + 7):
    rc = run.main(["--workload", "t_keye", "--seed", str(seed),
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
    # the reader still finds them once the trainer is freed
    from benchmark.readers import registry_ratio
    share = registry_ratio.read({}, "moe.rows_held", "moe.rows_routed")
    assert 0.1 < share < 0.4            # 2 of 8 experts: 0.25 when even


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
    cfg = manifest.config_of(manifest.workload("t_keye"))
    mix = traffic.load_mix("tiny_keye")
    batches = [train_steps.feed(mix, 128, 7, s) for s in range(3)]
    ref = check.reference_train_readings(cfg, 7, batches)
    low = check.reference_train_readings(cfg, 7, batches, precision="lower")
    ok, _ = check.judge(check.train_numbers(low, ref),
                        check.load_limits("t_keye"))
    assert not ok


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(REPO, "benchmark", "reference",
                            "keye_vl2_ref.py")).read()
    assert "paddle_tpu" not in src and "pallas" not in src


# -- the configuration file ---------------------------------------------------

def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    for line in open(path):
        row = json.loads(line)
        if row["name"] == "Keye-VL-2.0-30B-A3B":
            return row
    pytest.skip("the catalog has no such row")


REDUCED = {"num_hidden_layers": (48, 4), "num_experts": (128, 16),
           "num_local_experts": (128, 16), "vocab_size": (151936, 18992)}


def test_the_file_keeps_the_catalogs_keys_but_the_reduced_ones():
    cfg, row = real_cfg(), catalog_row()
    assert cfg["source"] == row["source_url"]
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    for key, value in row["config"].items():
        want = REDUCED[key][1] if key in REDUCED else value
        # at the top level, where the catalog's keys stand, and in the
        # group the harness reads
        assert cfg[key] == want and cfg["model"][key] == want, key
        if key in REDUCED:
            assert value == REDUCED[key][0] == cfg["published"][key]
    assert cfg["model"]["expert_share"] == [0, 8]
    assert family.router_width(cfg["model"]) == 128
    entry = [c for c in manifest.load_json(REPO, "BENCHMARK.json")["configs"]
             if c["file"] == CONFIG][0]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_the_leaves_are_the_memory_arithmetic():
    m = real_cfg()["model"]
    sizes = {n: int(np.prod(s)) for n, s, *_ in family.leaf_shapes(m)}
    layer = {k.split(".")[2]: v for k, v in sizes.items()
             if k.startswith("layers.0.")}
    assert layer["wq"] + layer["wk"] + layer["wv"] + layer["wo"] == 18_874_368
    assert layer["iwq"] + layer["iwk"] + layer["iww"] == 2_260_992
    assert layer["wr"] == 2048 * 128
    assert layer["eg"] == 16 * 2048 * 768 == layer["eu"] == layer["ed"]
    assert sizes["embed"] == sizes["head"] == 18992 * 2048
    total = sum(sizes.values())
    assert total == 465_390_848                 # x 16 B = 7.45 GB
    # every leaf has a parameter name and no two share one
    names = [family.train_param_name(n) for n in sizes]
    assert len(set(names)) == len(names)


# -- work counts, by hand -----------------------------------------------------

def test_the_weights_scale_keeps_the_routing_even():
    """Random attention over thousands of keys passes on what a
    sequence's tokens share in full and averages the rest away, so a
    layer multiplies the shared part of the stream (relative to the
    stream, whose scale is the embedding's) by about
    scale**2 * sqrt(hidden * q_width) * (scale / sqrt(scale**2 + eps))
    / scale. Over 1 the stream collapses onto one vector in a layer or
    two and a sequence's tokens all pick the same experts: the held rows,
    and with them a step's time, are then the seed's luck (PERF.md,
    section 6). The file's scale keeps the factor well under 1; the
    usual 0.02 reads 58."""
    cfg = real_cfg()
    m, s = cfg["model"], cfg["init_scale"]

    def growth(s):
        q_width = m["num_attention_heads"] * m["head_dim"]
        normed = s / np.sqrt(s * s + m["rms_norm_eps"])
        return s * np.sqrt(m["hidden_size"] * q_width) * normed
    assert growth(s) < 0.5
    assert 57 < growth(0.02) < 59
    # the lr keeps its ratio to the scale: 5e-5 of a weight's scale a step
    assert cfg["optimizer"]["learning_rate"] / s == pytest.approx(5e-5)


def test_work_counts_by_hand():
    m = real_cfg()["model"]
    batch, seq, layers = 2, 8192, 4
    tokens = batch * seq
    sel = 2048 * 2049 // 2 + (8192 - 2048) * 2048        # one sequence
    assert family.selected_pairs(m, seq) == sel == 14_681_088
    causal = 8192 * 8193 // 2
    # a token's matmul parameters: attention 18.87 M, indexer 2.26 M,
    # router 0.26 M, and 8 x 16 / 128 = 1 expected expert row of
    # 3 x 2048 x 768; the head 2048 x 18992
    per_layer = 18_874_368 + 2_260_992 + 262_144 + 1 * 3 * 2048 * 768
    params = layers * per_layer + 2048 * 18992
    assert family.token_matmul_params(m) == params
    attn = 4 * 128 * 32 * layers * batch * sel
    idx = 2 * 64 * 16 * layers * batch * causal
    fwd = 2 * params * tokens + attn + idx
    assert family.train_flops(m, batch, seq) == 3 * fwd
    assert 21.4e12 < 3 * fwd < 21.6e12
    work = {"steps": 4, "batch": batch, "seq": seq}
    assert family.KERNEL_WORK["sparse_attn_flops"](m, work) == 4 * 3 * attn
    assert family.KERNEL_WORK["indexer_scores_flops"](m, work) == 4 * idx
    assert family.KERNEL_WORK["sparse_attn_flops"](m, {"tokens": 1}) == 0


def _traced(names):
    """Two steps of 100 ms, each holding the named operations."""
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
    ctx = _traced([("sparse_attn_fwd.5 tpu_custom_call", 10),
                   ("sparse_attn_bwd_dq.2 tpu_custom_call", 12),
                   ("sparse_attn_bwd_dkv.2 tpu_custom_call", 18),
                   ("indexer_loss_rows.3 tpu_custom_call", 7),
                   ("indexer_loss_grad.3 tpu_custom_call", 6),
                   ("indexer_scores.8 tpu_custom_call", 2),
                   ("indexer_scores.9 tpu_custom_call", 2),
                   ("topk_select.4 tpu_custom_call", 9),
                   ("ragged-dot-none.3 tpu_custom_call", 5),
                   ("fusion.12", 20)])
    names = [n for n in manifest.metrics_for(CELL, "per_layer")
             if manifest.metric_file(n)["source"] == "device_trace"]
    got = {k: v["value"] for k, v in run.read_per_layer(names, ctx).items()}
    m = ctx["model"]
    attn = 3 * 4 * 128 * 32 * 4 * 2 * 14_681_088          # a step
    assert got["sparse_attn_roofline.train"] == pytest.approx(
        100.0 * (2 * attn / 197e12) / 0.080)              # not the loss's
    idx = 2 * 64 * 16 * 4 * 2 * (8192 * 8193 // 2)
    assert got["indexer_roofline.train"] == pytest.approx(
        100.0 * (2 * idx / 197e12) / 0.008)
    assert got["select_ms_per_step.train"] == pytest.approx(9.0)
    assert got["indexer_loss_ms_per_step.train"] == pytest.approx(13.0)
    assert got["step_mfu.train"] == pytest.approx(
        100.0 * 2 * family.train_flops(m, 2, 8192) / (0.2 * 197e12))
    assert "flash_attn_roofline.train" not in manifest.metrics_for(
        CELL, "per_layer")
    # on a program without these kernels the readers find nothing
    bare = _traced([("fusion.12", 20)])
    assert set(run.read_per_layer(names, bare)) <= {
        "step_mfu.train", "device_idle_share.train"}


def test_the_registry_reader_returns_nothing_without_the_counters(
        monkeypatch):
    from benchmark.readers import _program, registry_ratio
    monkeypatch.setattr(_program, "counters", lambda prefixes: {})
    assert registry_ratio.read({}, "moe.rows_held", "moe.rows_routed") is None
    monkeypatch.setattr(_program, "counters", lambda prefixes: {
        "moe.rows_held": 16000, "moe.rows_routed": 131072})
    assert registry_ratio.read({}, "moe.rows_held", "moe.rows_routed") \
        == pytest.approx(16000 / 131072)
