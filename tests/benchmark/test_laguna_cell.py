"""The Laguna-S-2.1 cell's own files (family ``lm_laguna``, reference
``laguna_ref``, configuration, traffic and limits) at tiny size on the
CPU, and its work counts by hand."""
import copy
import json
import os

import numpy as np
import pytest

import tiny_tree

from benchmark import check, manifest, run
from benchmark.families import lm_laguna as family

REPO = tiny_tree.REPO
CONFIG = "benchmark/configs/laguna_s21_ep32_l5_train.json"
CELL = "laguna_ep32_l5_train_s8192"
MS = 1_000_000


def real_cfg():
    return manifest.load_json(REPO, CONFIG)


def tiny_cfg():
    """The real file cut to CPU size: the same five layers (the dense
    one, a full layer, three window layers here under 16 keys, a full
    layer), groups of two query heads a key head on full layers and of
    three on window layers, the published rotary parameters, 16 experts
    of which share 1 of 4 holds 4, top-3 scaled 2.5, a shared expert."""
    cfg = copy.deepcopy(real_cfg())
    cfg["model"].update(
        hidden_size=64, head_dim=16, num_key_value_heads=2,
        num_attention_heads_per_layer=[4, 6, 6, 6, 4],
        intermediate_size=96, moe_intermediate_size=32,
        shared_expert_intermediate_size=24, num_experts=4,
        expert_share=[1, 4], num_experts_per_tok=3, vocab_size=128,
        sliding_window=16)
    cfg["init_scale"] = 0.02
    cfg["optimizer"]["learning_rate"] = 1e-6
    return cfg


@pytest.fixture
def tree(tmp_path, monkeypatch):
    bench = tiny_tree.point_at(monkeypatch, str(tmp_path))
    data = tmp_path / "benchmark"
    (data / "configs" / "t_lg.json").write_text(json.dumps(tiny_cfg()))
    # the cell's own shape, one row a batch, at 64 positions
    (data / "traffic" / "tiny_lg.json").write_text(json.dumps(
        {"driver": "train_steps", "batch": 1, "seq": 64, "trace_steps": 2,
         "check_steps": 3}))
    # between what a sound run reads here (seeds 7, 8, 9 and 2**31 + 7:
    # gradient 0.0013-0.017, change 0.0025-0.0052: bfloat16 at widths of
    # 24 to 96) and what the control (seeds 7, 8: gradient 0.93-0.98,
    # change 262-269) and the first half of the sequence (gradient
    # 0.86-1.08, change 0.22-0.24) read
    (data / "limits" / "t_lg.json").write_text(json.dumps(
        {"numbers": {"grad_norm_gap_worst_leaf": {"limit": 0.1},
                     "change_norm_gap_worst_leaf": {"limit": 0.03}}}))
    real = manifest.load_json(REPO, "BENCHMARK.json")
    bench["configs"].append({"name": "t_lg", "source": "test",
                             "file": "benchmark/configs/t_lg.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "t_lg", "config": "t_lg",
                               "traffic": "tiny_lg", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][3]["workloads"].append("t_lg")
    for m in real["per_layer"]:
        if CELL in m["workloads"]:
            bench["per_layer"].append(dict(m, workloads=["t_lg"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    tiny_tree.let_cpu_through(monkeypatch)
    return bench


def _run(capsys, seed=2 ** 31 + 7):
    rc = run.main(["--workload", "t_lg", "--seed", str(seed),
                   "--seconds", "1.0", "--trace", "0"])
    return rc, tiny_tree.last_json_line(capsys)


def test_the_cells_files_run_at_tiny_size(tree, capsys):
    rc, line = _run(capsys)
    assert rc == 0 and line["correct"] is True, json.dumps(line["checks"])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert line["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}
    judged = {k for k, c in line["checks"].items() if c["limit"] is not None}
    assert judged >= {"grad_norm_gap_worst_leaf",
                      "change_norm_gap_worst_leaf"}
    # the experts' counters were published when the window closed, and
    # the readers still find them once the trainer is freed
    from benchmark.readers import registry_ratio
    share = registry_ratio.read({}, "moe.rows_held", "moe.rows_routed")
    assert 0.1 < share < 0.45           # 4 of 16 experts: 0.25 when even
    walked = registry_ratio.read({}, "moe.rows_walked", "moe.rows_held")
    assert walked >= 1.0


def test_one_row_a_batch_is_read_with_half_of_the_sequence_left_out(
        tree, capsys):
    """The cell's batch is one row: ``readings_one_row.py`` plants the
    first half of the sequence and holds program, control and fault to
    the cell's limits; the fault fails each limit by itself."""
    from benchmark import readings_one_row
    assert readings_one_row.read("t_lg", [7], 1) == 0
    rows = {r["who"]: r for r in map(
        json.loads, capsys.readouterr().out.strip().splitlines())
        if "who" in r}
    assert rows["program"]["correct"] is True
    assert rows["control"]["correct"] is False
    fault = rows["fault_half_sequence"]
    assert fault["correct"] is False
    assert fault["grad_norm_gap_worst_leaf"] > 0.1 \
        and fault["change_norm_gap_worst_leaf"] > 0.03


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(REPO, "benchmark", "reference",
                            "laguna_ref.py")).read()
    assert "paddle_tpu" not in src and "pallas" not in src


# -- the configuration file ---------------------------------------------------

PER_LAYER = ("layer_types", "mlp_layer_types", "gating_types",
             "num_attention_heads_per_layer")
REDUCED = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 12544,
           "layer_types": ["full_attention"] + ["sliding_attention"] * 3
           + ["full_attention"],
           "mlp_layer_types": ["dense"] + ["sparse"] * 4,
           "gating_types": ["per_head"] * 5,
           "num_attention_heads_per_layer": [48, 72, 72, 72, 48]}


def test_the_two_copies_of_the_models_keys_are_equal():
    cfg = real_cfg()
    extra = {"expert_share": [0, 32], "torch_dtype": "bfloat16"}
    assert {k: v for k, v in cfg["model"].items() if k not in extra} \
        == {k: cfg[k] for k in cfg["model"] if k not in extra}
    assert {k: cfg["model"][k] for k in extra} == extra
    assert family.router_width(cfg["model"]) == 256
    # published layers 0-4: the leading dense layer and one whole period
    for key in PER_LAYER:
        assert cfg["published"][key][:5] == REDUCED[key] == cfg[key], key
        assert len(cfg["published"][key]) == 48
    assert cfg["published"]["num_experts"] == 256
    assert 8 * cfg["vocab_size"] == cfg["published"]["vocab_size"] == 100352
    entry = [c for c in manifest.load_json(REPO, "BENCHMARK.json")["configs"]
             if c["file"] == CONFIG][0]
    assert entry["reduced"] == cfg["reduced"]
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    assert entry["source"] == cfg["source"]
    for key in ("assumed", "departures", "deployment", "memory",
                "trainer_note"):
        assert cfg[key], key
    assert "32 chips" in cfg["deployment"]


def test_the_file_cuts_depth_experts_and_vocabulary_and_no_width():
    """Every key the file states twice holds the published value but the
    reduced ones; of a per-layer list the first five entries are run,
    which are published layers 0-4."""
    cfg = real_cfg()
    for key in set(cfg["model"]) - {"expert_share", "torch_dtype"}:
        want = REDUCED.get(key, cfg[key])
        assert cfg[key] == want and cfg["model"][key] == want, key
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "intermediate_size",
                "moe_intermediate_size", "shared_expert_intermediate_size",
                "num_experts_per_tok", "sliding_window", "rope_parameters"):
        assert key not in cfg["reduced"]
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["sliding_window"]) == (3072, 128, 12288, 1024, 10, 512)
    full = cfg["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["partial_rotary_factor"],
            full["factor"]) == ("yarn", 0.5, 128)


def test_the_program_refuses_what_its_decoder_has_not():
    cfg = real_cfg()
    for key, other in (("attention_bias", True), ("gating", "none"),
                       ("tie_word_embeddings", True),
                       ("moe_router_logit_softcapping", 30),
                       ("moe_apply_router_weight_on_input", True)):
        bad = copy.deepcopy(cfg)
        bad["model"][key] = other
        with pytest.raises(ValueError, match=key):
            family.laguna_config(bad)
    bad = copy.deepcopy(cfg)
    bad["model"]["mlp_only_layers"] = [0, 1]
    with pytest.raises(ValueError, match="disagree"):
        family.laguna_config(bad)


def test_the_leaves_are_the_memory_arithmetic():
    m = real_cfg()["model"]
    sizes = {n: int(np.prod(s)) for n, s, *_ in family.leaf_shapes(m)}

    def layer(i):
        return sum(v for k, v in sizes.items()
                   if k.startswith(f"layers.{i}."))
    h = 3072
    full = 2 * h * 6144 + 2 * h * 1024 + h * 48 + 2 * h        # 44.19 M
    window = 2 * h * 9216 + 2 * h * 1024 + h * 72 + 2 * h      # 63.14 M
    shared, router = 3 * h * 1024, h * 256                    # 9.44, 0.79 M
    held = 8 * 3 * h * 1024                                   # 75.50 M
    assert layer(0) == full + 3 * h * 12288 == 157_440_000
    for i in (1, 2, 3):
        assert layer(i) == window + shared + router + held == 148_862_976
    assert layer(4) == full + shared + router + held == 129_914_880
    assert sizes["embed"] == sizes["head"] == 12544 * 3072
    total = sum(sizes.values())
    assert total == 157_440_000 + 3 * 148_862_976 + 129_914_880 \
        + 2 * 12544 * 3072 + h == 811_017_216       # x 16 B = 12.98 GB
    # every leaf has a parameter name and no two share one
    names = [family.train_param_name(n) for n in sizes]
    assert len(set(names)) == len(names) == 69


# -- work counts, by hand -----------------------------------------------------

def test_work_counts_by_hand():
    m = real_cfg()["model"]
    seq, window, h = 8192, 512, 3072
    causal = seq * (seq + 1) // 2
    band = window * (window + 1) // 2 + (seq - window) * window
    assert family.band_pairs(seq, window) == band == 4_063_488
    # a token's matmul parameters: two full layers' and three window
    # layers' projections and gates, the dense MLP, four routers and
    # shared experts, 10 x 8 / 256 = 0.3125 expected rows of a held
    # expert in each of four layers, the head
    params = 2 * (2 * h * 6144 + 2 * h * 1024 + h * 48) \
        + 3 * (2 * h * 9216 + 2 * h * 1024 + h * 72) + 3 * h * 12288 \
        + 4 * (h * 256 + 3 * h * 1024 + 0.3125 * 3 * h * 1024) + h * 12544
    assert family.token_matmul_params(m) == pytest.approx(params)
    full_pair, window_pair = 4 * 128 * 48, 4 * 128 * 72
    fwd = 2 * params * seq + 2 * full_pair * causal + 3 * window_pair * band
    assert family.train_flops(m, 1, seq) == pytest.approx(3 * fwd)
    assert 1.21e9 < fwd / seq < 1.23e9              # 1,221 MFLOP a token
    assert 29.9e12 < 3 * fwd < 30.1e12              # 30.0 TFLOP a step
    work = {"steps": 4, "batch": 1, "seq": seq}
    assert family.KERNEL_WORK["global_flash_flops"](m, work) \
        == 4 * 3 * 2 * full_pair * causal
    assert family.KERNEL_WORK["window_flash_flops"](m, work) \
        == 4 * 3 * 3 * window_pair * band
    rows = seq * 10 * 8 // 256                  # 2,560 held rows a layer
    assert family.KERNEL_WORK["expert_mm_flops"](m, work) \
        == 4 * 4 * rows * 9 * 2 * h * 1024
    for count in family.KERNEL_WORK.values():
        assert count(m, {"tokens": 1}) == 0


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
            "mix": {"batch": 1, "seq": 8192, "trace_steps": 2},
            "peak": {"flops_per_s_bf16": 197e12, "bytes_per_s_hbm": 819e9},
            "res": {"window": (0.0, 1.0)}, "trace_clock": (0.0, 1.0),
            "trace": {"planes": {
                "/device:TPU:0": {tr.MODULES_LINE: modules,
                                  tr.OPS_LINE: ops},
                "host": {"spans": [["bench:window", 0, 500 * MS]]}}}}


def test_the_cells_metrics_read_the_familys_counts(monkeypatch):
    monkeypatch.setattr(manifest, "ROOT", REPO)
    ctx = _traced([("flash_fwd.2 tpu_custom_call", 4),
                   ("flash_bwd_dq.7 tpu_custom_call", 5),
                   ("flash_bwd_dkv.7 tpu_custom_call", 7),
                   ("flash_win_fwd.3 tpu_custom_call", 2),
                   ("flash_win_bwd_dq.9 tpu_custom_call", 3),
                   ("flash_win_bwd_dkv.9 tpu_custom_call", 4),
                   ("ragged-dot-none.3 tpu_custom_call", 6),
                   ("fusion.12", 20)])
    names = manifest.metrics_for(CELL, "per_layer")
    assert set(names) == {
        "step_mfu.train", "device_idle_share.train",
        "host_ms_per_step.train", "dispatch_gap_ms_per_step.train",
        "trace_lower_s.setup", "backend_compile_s.setup",
        "step_stall_share.train", "window_attn_roofline.train",
        "global_attn_roofline.train", "expert_mm_roofline.train",
        "expert_rows_share.train", "expert_rows_walked.train",
        "expert_rows_busiest.train"}
    traced = [n for n in names
              if manifest.metric_file(n)["source"] == "device_trace"]
    got = {k: v["value"] for k, v in run.read_per_layer(traced, ctx).items()}
    causal, band = 8192 * 8193 // 2, 4_063_488
    assert got["global_attn_roofline.train"] == pytest.approx(
        100.0 * (2 * 3 * 2 * 4 * 128 * 48 * causal / 197e12) / 0.032)
    assert got["window_attn_roofline.train"] == pytest.approx(
        100.0 * (2 * 3 * 3 * 4 * 128 * 72 * band / 197e12) / 0.018)
    assert got["expert_mm_roofline.train"] == pytest.approx(
        100.0 * (2 * 4 * 2560 * 9 * 2 * 3072 * 1024 / 197e12) / 0.012)
    assert got["step_mfu.train"] == pytest.approx(
        100.0 * 2 * family.train_flops(ctx["model"], 1, 8192)
        / (0.2 * 197e12))
    # on a program without these kernels (the parent's) the readers find
    # nothing and do not raise
    bare = _traced([("fusion.12", 20)])
    assert set(run.read_per_layer(traced, bare)) <= {
        "step_mfu.train", "device_idle_share.train"}


def test_the_limits_file_judges_what_tells_sound_from_unsound():
    limits = check.load_limits(CELL)
    assert set(limits) == {"grad_norm_gap_worst_leaf",
                           "change_norm_gap_worst_leaf", "loss_rel_gap_max",
                           "first_loss_rel_gap"}
    raw = manifest.load_json(REPO, "benchmark", "limits", f"{CELL}.json")
    for name, entry in raw["numbers"].items():
        # each limit lies between the program's largest reading and the
        # smaller of the control's and the fault's (the fault's alone for
        # the loss, which the control moves by a rounding), with three
        # times of room on both sides
        assert 3 * entry["lower"] < entry["limit"] < entry["upper"] / 3, name
        assert entry["readings"], name
    assert "not_judged" not in raw
    # the program's largest readings pass, the fault's smallest fail each
    # limit
    sound = {"grad_norm_gap_worst_leaf": 2.289e-3,
             "change_norm_gap_worst_leaf": 1.204e-3,
             "loss_rel_gap_max": 2.0211e-7, "first_loss_rel_gap": 2.0211e-7}
    assert check.judge(sound, limits)[0]
    fault = {"grad_norm_gap_worst_leaf": 0.9022,
             "change_norm_gap_worst_leaf": 0.3079,
             "loss_rel_gap_max": 3.638e-6, "first_loss_rel_gap": 2.5264e-6}
    for name in fault:
        assert not check.judge(dict(sound, **{name: fault[name]}),
                               limits)[0], name
