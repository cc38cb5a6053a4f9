"""A benchmark of its own in a temporary directory: tiny configurations
and mixes as data files, the real readers and metric files. Tests point
``manifest.ROOT``/``manifest.DATA`` at it, which is all it takes for the
harness to find a cell: no code knows a cell's name."""
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

MODEL = {"hidden_act": "silu", "hidden_size": 256, "intermediate_size": 512,
         "num_hidden_layers": 2, "num_attention_heads": 2,
         "num_key_value_heads": 1, "head_dim": 128, "vocab_size": 512,
         "rope_theta": 1000000.0, "rms_norm_eps": 1e-05,
         "max_position_embeddings": 2048, "sliding_window": None,
         "tie_word_embeddings": False, "torch_dtype": "bfloat16"}

SERVE = {"source": "test", "kind": "serve", "family": "llama",
         "model": MODEL, "reduced": [], "assumed": [],
         "deployment": "test", "init_scale": 0.02,
         "precision": {"weights": "int8", "activations": "bfloat16",
                       "kv_pool": "bfloat16"},
         "decoder": {"weight_dtype": "int8", "block_size": 32,
                     "num_blocks": 128},
         # a small prefill budget and idle cap keep the reachable
         # program set, and so the test's set-up, small
         "engine": {"max_batch_size": 4, "ragged": True,
                    "prompt_buckets": [512], "prefill_chunk": 32,
                    "ragged_idle_cap": 32}}

TRAIN = {"source": "test", "kind": "train", "family": "llama",
         "model": MODEL, "reduced": [], "assumed": [],
         "deployment": "test", "init_scale": 0.02,
         "precision": {"weights": "bfloat16", "activations": "bfloat16"},
         "trainer": {"use_recompute": False,
                     "recompute_granularity": "full",
                     "chunked_ce_tokens": 0},
         "optimizer": {"learning_rate": 0.0001, "beta1": 0.9,
                       "beta2": 0.999, "epsilon": 1e-08,
                       "weight_decay": 0.01}}

SIZES = {"prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.9,
                        "min": 8, "max": 200},
         "output_len": {"dist": "lognormal", "median": 10, "sigma": 0.7,
                        "min": 4, "max": 24}}

MIXES = {
    "tiny_open": dict(SIZES, driver="open_loop", rate_rps=6.0, ramp_s=0.5,
                      trace_s=1.0, check_requests=3),
    "tiny_closed": dict(SIZES, driver="closed_loop", clients=3,
                        population=32, ramp_s=0.5, trace_s=1.0,
                        check_requests=3),
    "tiny_train": {"driver": "train_steps", "batch": 2, "seq": 64,
                   "trace_steps": 2, "check_steps": 3},
}

# limits for the tiny sizes on the CPU: between what sound runs read there
# (serving gap 0.004-0.007; training loss 2e-5, gradient 0.002, change
# 0.0004) and what the control and the planted faults read (serving 0.25;
# training gradient 0.4 and more)
LIMITS = {
    "t_open": {"served_logit_gap_max": 0.05},
    "t_closed": {"served_logit_gap_max": 0.05},
    "t_train": {"loss_rel_gap_max": 0.001,
                "grad_norm_gap_worst_leaf": 0.05,
                "change_norm_gap_worst_leaf": 0.05},
}


def build(tmp: str) -> dict:
    """Write the tree under ``tmp``; returns its BENCHMARK.json."""
    data = os.path.join(tmp, "benchmark")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(data, sub), exist_ok=True)
    real = os.path.join(REPO, "benchmark")
    shutil.copytree(os.path.join(real, "metrics"),
                    os.path.join(data, "metrics"), dirs_exist_ok=True)
    shutil.copy(os.path.join(real, "peaks.json"), data)
    for name, cfg in (("t_serve", SERVE), ("t_train", TRAIN)):
        with open(os.path.join(data, "configs", f"{name}.json"), "w") as f:
            json.dump(cfg, f)
    for name, mix in MIXES.items():
        with open(os.path.join(data, "traffic", f"{name}.json"), "w") as f:
            json.dump(mix, f)
    for name, lim in LIMITS.items():
        with open(os.path.join(data, "limits", f"{name}.json"), "w") as f:
            json.dump({"numbers": {k: {"limit": v}
                                   for k, v in lim.items()}}, f)
    cells = {"t_open": ("t_serve", "tiny_open"),
             "t_closed": ("t_serve", "tiny_closed"),
             "t_train": ("t_train", "tiny_train")}
    bench = {
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 2,
        "configs": [{"name": n, "source": "test",
                     "file": f"benchmark/configs/{n}.json", "reduced": [],
                     "why": "test"} for n in ("t_serve", "t_train")],
        "workloads": [{"name": n, "config": c, "traffic": t, "chips": 1,
                       "why": "test"} for n, (c, t) in cells.items()],
        "end_to_end": [
            {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock", "workloads": ["t_open"]},
            {"name": "itl_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["t_open", "t_closed"]},
            {"name": "serve_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.1, "source": "host_clock",
             "workloads": ["t_closed"]},
            {"name": "train_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.1, "source": "host_clock",
             "workloads": ["t_train"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"}],
        "per_layer": [
            {"name": "host_ms_per_step.itl", "unit": "ms",
             "better": "lower", "source": "program_counter",
             "layer": "scheduler", "moves": "itl_p95_ms",
             "workloads": ["t_open", "t_closed"]}],
    }
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return bench


def point_at(monkeypatch, tmp: str) -> dict:
    from benchmark import manifest
    bench = build(tmp)
    monkeypatch.setattr(manifest, "ROOT", tmp)
    monkeypatch.setattr(manifest, "DATA", os.path.join(tmp, "benchmark"))
    return bench


def let_cpu_through(monkeypatch):
    """The one place a test lets the CPU stand in for the chip."""
    from benchmark import run
    monkeypatch.setattr(
        run, "device_info",
        lambda chips, peaks: {"platform": "cpu", "kind": "TPU v5 lite",
                              "count": 1})


def last_json_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
