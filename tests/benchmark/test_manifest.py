"""BENCHMARK.json and the data files it names keep to the contract's
names and shapes, and a cell is nothing but files."""
import json
import os
import re

import pytest

import tiny_tree
from benchmark import manifest

REPO = tiny_tree.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _metrics(bench):
    return bench["end_to_end"] + bench["per_layer"]


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["paths"]) <= 16
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
    assert any(word.startswith(tuple(p + "/" for p in bench["paths"]))
               for word in bench["command"])


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_are_unique_and_of_allowed_characters(bench, kind):
    names = [e["name"] for e in bench[kind]]
    assert len(set(names)) == len(names)
    for n in names:
        assert NAME.match(n), n


def test_metric_entries(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in _metrics(bench):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


def test_every_file_a_cell_names_exists(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        used.add(w["config"])
        cfg = manifest.config_of(w)
        assert cfg["source"] == configs[w["config"]]["source"]
        assert sorted(cfg["reduced"]) == \
            sorted(configs[w["config"]]["reduced"])
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "limits", w["name"] + ".json"))
    assert used == set(configs), "a configuration no cell uses"
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert len(c["reduced"]) <= 16
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_no_width_is_reduced(bench):
    widths = re.compile(r"(hidden|intermediate|latent|state|proj).*size"
                        r"|_dim$|_rank$|head_dim|experts_per_tok")
    for c in bench["configs"]:
        for key in c["reduced"]:
            assert not widths.search(key), key
    serve = manifest.load_json(
        REPO, "benchmark/configs/mistral_7b_v03_int8_serve.json")["model"]
    train = manifest.load_json(
        REPO, "benchmark/configs/mistral_7b_v03_l2_train.json")["model"]
    differ = {k for k in serve if serve[k] != train[k]}
    assert differ == {"num_hidden_layers"}


def test_four_chip_cells_are_at_most_a_quarter(bench):
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_per_layer_metrics_match_their_files(bench):
    for m in bench["per_layer"]:
        spec = manifest.metric_file(m["name"])
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert spec["reader"] in manifest.module_names("readers")


def test_benchmark_json_alone_lists_a_metrics_cells(bench):
    """No metric file repeats the list, so adding a cell to a metric is
    an edit of BENCHMARK.json and of nothing else."""
    metrics = os.path.join(REPO, "benchmark", "metrics")
    for f in sorted(os.listdir(metrics)):
        assert "workloads" not in manifest.load_json(metrics, f), f
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m["workloads"]) <= cells
        for cell in m["workloads"]:
            assert m["moves"] in manifest.metrics_for(cell, "end_to_end"), \
                f"{cell} does not report {m['moves']}"


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = manifest.metrics_for(w["name"], "end_to_end")
        assert "setup_s" in e2e and len(e2e) >= 2
        assert manifest.metrics_for(w["name"], "per_layer")


def test_rooflines_have_a_step_mfu_beside_them(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for m in bench["per_layer"]:
        if "_roofline" in m["name"]:
            assert m["unit"] == "%"
            assert any("mfu" in re.split(r"[._]", o["name"])
                       and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in by_name.values()), m["name"]


def test_a_cell_dropped_in_as_files_is_found(tmp_path, monkeypatch):
    bench = tiny_tree.point_at(monkeypatch, str(tmp_path))
    assert manifest.workload("t_open")["traffic"] == "tiny_open"
    assert manifest.config_of(manifest.workload("t_train"))["kind"] == \
        "train"
    assert manifest.metrics_for("t_closed", "end_to_end") == \
        ["itl_p95_ms", "serve_tokens_per_s", "setup_s"]
    assert manifest.metrics_for("t_open", "per_layer") == \
        ["host_ms_per_step.itl"]
    # a later PR's metric: one more file and one more entry, no code
    spec = dict(manifest.metric_file("host_ms_per_step.itl"),
                moves="serve_tokens_per_s")
    with open(tmp_path / "benchmark" / "metrics" / "new.tps.json",
              "w") as f:
        json.dump(spec, f)
    bench["per_layer"].append(
        {k: spec[k] for k in ("unit", "better", "source", "layer", "moves")}
        | {"name": "new.tps", "workloads": ["t_closed"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    assert manifest.metrics_for("t_closed", "per_layer") == \
        ["host_ms_per_step.itl", "new.tps"]
    # a later PR's cell joins a metric that is there: BENCHMARK.json's
    # entry gets one more name, the metric's file stays as it is
    bench["per_layer"][0]["workloads"].append("t_train")
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    assert manifest.metrics_for("t_train", "per_layer") == \
        ["host_ms_per_step.itl"]


def test_a_configuration_names_its_family(bench):
    for c in bench["configs"]:
        cfg = manifest.load_json(REPO, c["file"])
        assert manifest.family_of(cfg).__name__ == \
            "benchmark.families." + cfg["family"]
    with pytest.raises(ValueError, match="there are: llama"):
        manifest.family_of({"family": "mamba"})
    with pytest.raises(KeyError, match="there are: llama"):
        manifest.family_of({"model": {}})
