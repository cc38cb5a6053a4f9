"""The SmallThinker-21BA3B cell's own files (family ``lm_smallthinker``,
reference ``smallthinker_ref``, the two metric files that came with
them, ``readings_one_row.py``) at tiny size on the CPU, and its work
counts by hand."""
import copy
import json
import os

import numpy as np
import pytest

import tiny_tree

from benchmark import check, manifest, run, traffic
from benchmark.drivers import train_steps
from benchmark.families import lm_smallthinker as family

REPO = tiny_tree.REPO
CONFIG = "benchmark/configs/smallthinker_21b_a3b_ep4_l4_train.json"
CELL = "smallthinker_ep4_l4_train_s16384"
MS = 1_000_000
NEW_METRICS = ("window_attn_roofline.train", "global_attn_roofline.train")


def real_cfg():
    return manifest.load_json(REPO, CONFIG)


def tiny_cfg():
    """The real file cut to CPU size: the same period (a global layer
    without rotary embedding, three rotary layers under a window, here of
    16 keys), groups of three query heads, 8 experts of which share 1 of
    4 holds 2, top-2."""
    cfg = copy.deepcopy(real_cfg())
    cfg["model"].update(
        hidden_size=64, head_dim=16, num_attention_heads=6,
        num_key_value_heads=2, moe_ffn_hidden_size=48,
        moe_num_primary_experts=2, expert_share=[1, 4],
        moe_num_active_primary_experts=2, vocab_size=128,
        sliding_window_size=16)
    cfg["init_scale"] = 0.02
    cfg["optimizer"]["learning_rate"] = 1e-6
    return cfg


@pytest.fixture
def tree(tmp_path, monkeypatch):
    bench = tiny_tree.point_at(monkeypatch, str(tmp_path))
    data = tmp_path / "benchmark"
    (data / "configs" / "t_st.json").write_text(json.dumps(tiny_cfg()))
    (data / "traffic" / "tiny_st.json").write_text(json.dumps(
        {"driver": "train_steps", "batch": 2, "seq": 64, "trace_steps": 2,
         "check_steps": 3}))
    # between what a sound run reads here (loss 2e-5, gradient 0.068: in
    # bfloat16 at a width of 48 a ReLU gate's sign flips on a rounding and
    # the worst leaf is an expert's gate matrix; change 0.004) and what the
    # control (gradient 0.97, change 269) and the planted fault (gradient
    # 1.1, change 0.16, loss 2.3e-3) read
    (data / "limits" / "t_st.json").write_text(json.dumps(
        {"numbers": {"loss_rel_gap_max": {"limit": 0.001},
                     "grad_norm_gap_worst_leaf": {"limit": 0.2},
                     "change_norm_gap_worst_leaf": {"limit": 0.05}}}))
    # the cell's own shape, one row a batch (``readings_one_row.py``), on
    # seeds 7, 8, 9: a sound run reads gradient 0.005-0.024 and change
    # 0.003-0.006, the control 0.96-0.98 and 266-273, the first half of the
    # sequence 0.43-0.66 and 0.22-0.23
    (data / "traffic" / "tiny_st1.json").write_text(json.dumps(
        {"driver": "train_steps", "batch": 1, "seq": 64, "trace_steps": 2,
         "check_steps": 3}))
    (data / "limits" / "t_st1.json").write_text(json.dumps(
        {"numbers": {"grad_norm_gap_worst_leaf": {"limit": 0.1},
                     "change_norm_gap_worst_leaf": {"limit": 0.03}}}))
    real = manifest.load_json(REPO, "BENCHMARK.json")
    bench["configs"].append({"name": "t_st", "source": "test",
                             "file": "benchmark/configs/t_st.json",
                             "reduced": [], "why": "test"})
    for name, mix in (("t_st", "tiny_st"), ("t_st1", "tiny_st1")):
        bench["workloads"].append({"name": name, "config": "t_st",
                                   "traffic": mix, "chips": 1,
                                   "why": "test"})
        bench["end_to_end"][3]["workloads"].append(name)
    for m in real["per_layer"]:
        if CELL in m["workloads"]:
            bench["per_layer"].append(dict(m, workloads=["t_st", "t_st1"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    tiny_tree.let_cpu_through(monkeypatch)
    return bench


def _run(capsys, seed=2 ** 31 + 7):
    rc = run.main(["--workload", "t_st", "--seed", str(seed),
                   "--seconds", "1.0", "--trace", "0"])
    return rc, tiny_tree.last_json_line(capsys)


def test_the_cells_files_run_at_tiny_size(tree, capsys):
    rc, line = _run(capsys)
    assert rc == 0 and line["correct"] is True, json.dumps(line["checks"])
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
    walked = registry_ratio.read({}, "moe.rows_walked", "moe.rows_held")
    assert walked >= 1.0


def test_half_of_the_batch_left_out_is_not_correct(tree, capsys,
                                                   monkeypatch):
    from benchmark import systems
    call = systems.Trainer.__call__
    monkeypatch.setattr(systems.Trainer, "__call__",
                        lambda self, ids: call(self, ids[:len(ids) // 2]))
    rc, line = _run(capsys)
    assert rc == 0 and line["correct"] is False, json.dumps(line["checks"])
    grad = line["checks"]["grad_norm_gap_worst_leaf"]
    assert grad["value"] > grad["limit"]


def test_the_control_fails_the_comparison(tree):
    cfg = manifest.config_of(manifest.workload("t_st"))
    mix = traffic.load_mix("tiny_st")
    batches = [train_steps.feed(mix, 128, 7, s) for s in range(3)]
    ref = check.reference_train_readings(cfg, 7, batches)
    low = check.reference_train_readings(cfg, 7, batches, precision="lower")
    ok, _ = check.judge(check.train_numbers(low, ref),
                        check.load_limits("t_st"))
    assert not ok


def test_one_row_a_batch_is_read_with_half_of_the_sequence_left_out(
        tree, capsys):
    """The cell's batch is one row, which ``readings.py train`` cannot
    cut: ``readings_one_row.py`` plants the first half of the sequence
    and holds program, control and fault to the cell's limits."""
    from benchmark import readings_one_row
    assert readings_one_row.read("t_st1", [7], 1) == 0
    rows = {r["who"]: r for r in map(
        json.loads, capsys.readouterr().out.strip().splitlines())
        if "who" in r}
    assert rows["program"]["correct"] is True
    assert rows["control"]["correct"] is False
    fault = rows["fault_half_sequence"]
    assert fault["correct"] is False
    assert fault["grad_norm_gap_worst_leaf"] > 0.1 \
        and fault["change_norm_gap_worst_leaf"] > 0.03     # by each limit
    with pytest.raises(SystemExit, match="2 rows a batch"):
        readings_one_row.read("t_st", [7], 1)


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(REPO, "benchmark", "reference",
                            "smallthinker_ref.py")).read()
    assert "paddle_tpu" not in src and "pallas" not in src


# -- the configuration file ---------------------------------------------------

def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    for line in open(path):
        row = json.loads(line)
        if row["name"] == "SmallThinker-21BA3B-Instruct":
            return row
    pytest.skip("the catalog has no such row")


REDUCED = {"num_hidden_layers": 4, "rope_layout": [0, 1, 1, 1],
           "sliding_window_layout": [0, 1, 1, 1],
           "moe_num_primary_experts": 16, "vocab_size": 37984}


def test_the_two_copies_of_the_models_keys_are_equal():
    cfg = real_cfg()
    extra = {"expert_share": [0, 4], "torch_dtype": "bfloat16"}
    assert {k: v for k, v in cfg["model"].items() if k not in extra} \
        == {k: cfg[k] for k in cfg["model"] if k not in extra}
    assert {k: cfg["model"][k] for k in extra} == extra
    assert family.router_width(cfg["model"]) == 64
    assert cfg["published"] == {
        "num_hidden_layers": 52, "rope_layout": [0, 1, 1, 1] * 13,
        "sliding_window_layout": [0, 1, 1, 1] * 13,
        "moe_num_primary_experts": 64, "vocab_size": 151936}
    # published layers 0-3: the first whole period
    for key in ("rope_layout", "sliding_window_layout"):
        assert cfg["published"][key][:4] == REDUCED[key] == cfg[key]
    assert 4 * cfg["vocab_size"] == cfg["published"]["vocab_size"]
    entry = [c for c in manifest.load_json(REPO, "BENCHMARK.json")["configs"]
             if c["file"] == CONFIG][0]
    assert entry["reduced"] == cfg["reduced"]
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    assert entry["source"] == cfg["source"]
    for key in ("assumed", "departures", "deployment", "memory",
                "trainer_note"):
        assert cfg[key], key
    assert "52 chips" in cfg["deployment"] and "13" in cfg["deployment"]


def test_the_file_keeps_the_catalogs_keys_but_the_reduced_ones():
    cfg, row = real_cfg(), catalog_row()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        want = REDUCED.get(key, value)
        assert cfg[key] == want and cfg["model"][key] == want, key
        if key in REDUCED:
            assert value == cfg["published"][key]
    # every width is the source's
    for key in ("hidden_size", "head_dim", "num_attention_heads",
                "num_key_value_heads", "moe_ffn_hidden_size",
                "moe_num_active_primary_experts", "sliding_window_size"):
        assert key not in cfg["reduced"]


def test_the_leaves_are_the_memory_arithmetic():
    m = real_cfg()["model"]
    sizes = {n: int(np.prod(s)) for n, s, *_ in family.leaf_shapes(m)}

    def layer(i):
        return {k.split(".")[2]: v for k, v in sizes.items()
                if k.startswith(f"layers.{i}.")}
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512               # 20.97 M
    outside = attn + 2560 * 64 + 2 * 2560                 # 21.14 M
    experts = 16 * 3 * 2560 * 768                         # 94.37 M
    for i in range(4):
        assert sum(layer(i).values()) == outside + experts == 115_512_320
        assert set(layer(i)) == {"ln1", "ln2", "wq", "wk", "wv", "wo", "wr",
                                 "eg", "eu", "ed"}
    assert sizes["embed"] == sizes["head"] == 37984 * 2560
    total = sum(sizes.values())
    assert total == 4 * 115_512_320 + 2 * 97_239_040 + 2560 \
        == 656_529_920                          # x 16 B = 10.5 GB
    # every leaf has a parameter name and no two share one
    names = [family.train_param_name(n) for n in sizes]
    assert len(set(names)) == len(names) == 43


# -- work counts, by hand -----------------------------------------------------

def test_work_counts_by_hand():
    m = real_cfg()["model"]
    batch, seq, window = 1, 16384, 4096
    causal = seq * (seq + 1) // 2                           # 134.2 M
    band = window * (window + 1) // 2 + (seq - window) * window   # 58.7 M
    assert family.band_pairs(seq, window) == band == 58_722_304
    assert family.band_pairs(4096, window) == family.band_pairs(
        4096, 8192) == 4096 * 4097 // 2         # no longer than the window
    assert sum(min(t + 1, 5) for t in range(12)) == family.band_pairs(12, 5)
    # a token's matmul parameters: in each of four layers q, k, v, o
    # 20.97 M, the router over 64 and 6 x 16 / 64 = 1.5 expected expert
    # rows of 3 x 2560 x 768; the head 2560 x 37,984
    params = 4 * (2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64
                  + 1.5 * 3 * 2560 * 768) + 2560 * 37984
    assert family.token_matmul_params(m) == params
    pair = 4 * 128 * 28                         # QK^T and PV, 28 heads
    fwd = 2 * params * seq + pair * (causal + 3 * band)
    assert family.train_flops(m, batch, seq) == 3 * fwd
    per_token = fwd / seq
    assert 704e6 < per_token < 708e6            # 705 MFLOP a token
    assert 34.6e12 < 3 * fwd < 34.8e12
    # causal pairs in every layer would count 28% more
    high = 2 * params * seq + pair * 4 * causal
    assert 1.27 < high / fwd < 1.29
    assert 0.37 < pair * (causal + 3 * band) / fwd < 0.39   # attention 38%
    work = {"steps": 4, "batch": batch, "seq": seq}
    assert family.KERNEL_WORK["global_flash_flops"](m, work) \
        == 4 * 3 * pair * causal
    assert family.KERNEL_WORK["window_flash_flops"](m, work) \
        == 4 * 3 * pair * 3 * band
    rows = seq * 6 * 16 // 64                   # 24,576 held rows a layer
    assert family.KERNEL_WORK["expert_mm_flops"](m, work) \
        == 4 * 4 * rows * 9 * 2 * 2560 * 768
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
            "mix": {"batch": 1, "seq": 16384, "trace_steps": 2},
            "peak": {"flops_per_s_bf16": 197e12, "bytes_per_s_hbm": 819e9},
            "res": {"window": (0.0, 1.0)}, "trace_clock": (0.0, 1.0),
            "trace": {"planes": {
                "/device:TPU:0": {tr.MODULES_LINE: modules,
                                  tr.OPS_LINE: ops},
                "host": {"spans": [["bench:window", 0, 500 * MS]]}}}}


def test_the_new_metrics_tell_a_window_layer_from_the_global_one(
        monkeypatch):
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
    assert set(names) >= set(NEW_METRICS) | {
        "step_mfu.train", "expert_mm_roofline.train",
        "expert_rows_share.train", "expert_rows_walked.train",
        "device_idle_share.train", "host_ms_per_step.train",
        "dispatch_gap_ms_per_step.train", "trace_lower_s.setup",
        "backend_compile_s.setup"}
    # its file scales by another cell's 32 held experts (4 layers x 8);
    # this cell holds 64 and would read half of what it means
    assert manifest.metric_file("expert_rows_busiest.train")["args"][
        "scale"] == 32 and "expert_rows_busiest.train" not in names
    traced = [n for n in names
              if manifest.metric_file(n)["source"] == "device_trace"]
    got = {k: v["value"] for k, v in run.read_per_layer(traced, ctx).items()}
    m = ctx["model"]
    pair, causal, band = 4 * 128 * 28, 16384 * 16385 // 2, 58_722_304
    assert got["global_attn_roofline.train"] == pytest.approx(
        100.0 * (2 * 3 * pair * causal / 197e12) / 0.032)
    assert got["window_attn_roofline.train"] == pytest.approx(
        100.0 * (2 * 3 * pair * 3 * band / 197e12) / 0.018)
    assert got["step_mfu.train"] == pytest.approx(
        100.0 * 2 * family.train_flops(m, 1, 16384) / (0.2 * 197e12))
    # the other cells' attention metrics are not this cell's: Mistral's
    # pattern would take every custom call, LFM2's work is another
    # family's
    assert "flash_attn_roofline.train" not in names
    assert "flash_d64_roofline.train" not in names
    # on a program without these kernels (the parent's) the readers find
    # nothing and do not raise
    bare = _traced([("fusion.12", 20)])
    assert set(run.read_per_layer(traced, bare)) <= {
        "step_mfu.train", "device_idle_share.train"}


def test_the_limits_file_judges_what_tells_sound_from_unsound():
    limits = check.load_limits(CELL)
    assert set(limits) == {"grad_norm_gap_worst_leaf",
                           "change_norm_gap_worst_leaf"}
    raw = manifest.load_json(REPO, "benchmark", "limits", f"{CELL}.json")
    for name, entry in raw["numbers"].items():
        # each limit lies between the program's largest reading and the
        # smaller of the control's and the fault's, with three times of
        # room on both sides
        assert 3 * entry["lower"] < entry["limit"] < entry["upper"] / 3, name
        assert entry["readings"], name
    # the loss's gap reads the same from a sound step and an unsound one
    # at this scale of weights: reported, not judged
    assert set(raw["not_judged"]) == {"loss_rel_gap_max"}
    ok, out = check.judge({"loss_rel_gap_max": 1.0,
                           "grad_norm_gap_worst_leaf": 0.0,
                           "change_norm_gap_worst_leaf": 0.0}, limits)
    assert ok and out["loss_rel_gap_max"]["limit"] is None
