"""``expert_rows_walked.train``: the rows the expert layers' gathers and
scatter-adds went over for every row a held expert computed. The metric
came as one data file and one BENCHMARK.json entry read by the reader
that was there (``registry_ratio``); a program that publishes no
``moe.rows_walked`` reads nothing."""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.models import (KeyeVL2ForCausalLM, Lfm2MoeForCausalLM,
                               keye_vl2_tiny, lfm2_moe_tiny)
from paddle_tpu.utils import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest                      # noqa: E402
from benchmark.readers import _program              # noqa: E402

NAME = "expert_rows_walked.train"


@pytest.fixture
def read():
    spec = manifest.load_json(REPO, "benchmark", "metrics", NAME + ".json")
    reader = manifest.module("readers", spec["reader"])
    return lambda: reader.read({}, **spec["args"])


@pytest.mark.parametrize("published", [
    {},
    # the program before the walk: its rows are counted, not its movement
    {"moe.rows_held": 66000, "moe.rows_max_expert": 2300,
     "moe.rows_routed": 262144},
    {"moe.rows_walked": 0, "moe.rows_held": 0},
])
def test_nothing_is_read_where_nothing_was_walked(read, monkeypatch,
                                                  published):
    monkeypatch.setattr(_program, "counters", lambda prefixes: published)
    assert read() is None


def test_the_rows_walked_are_read_against_the_rows_held(read, monkeypatch):
    # four layers' nine blocks of 2,048 for 16,500 held rows each
    monkeypatch.setattr(_program, "counters", lambda prefixes: {
        "moe.rows_walked": 4 * 9 * 2048, "moe.rows_held": 4 * 16500})
    assert read() == pytest.approx(1.1171, abs=1e-4)


def test_the_entry_is_the_files_and_lists_the_expert_cells():
    spec = manifest.load_json(REPO, "benchmark", "metrics", NAME + ".json")
    [entry] = [m for m in manifest.load_json(REPO, "BENCHMARK.json")[
        "per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, **{k: spec[k] for k in (
            "unit", "better", "source", "layer", "moves")},
        "workloads": ["keye_vl2_ep8_l4_train_s8192",
                      "lfm2_ep4_l5_train_s8192",
                      "smallthinker_ep4_l4_train_s16384",
                      "laguna_ep32_l5_train_s8192"]}
    assert (spec["better"], spec["source"]) == ("lower", "program_counter")


@pytest.mark.parametrize("build", [
    lambda: KeyeVL2ForCausalLM(keye_vl2_tiny()),
    lambda: Lfm2MoeForCausalLM(lfm2_moe_tiny())], ids=["keye", "lfm2"])
def test_a_trained_model_publishes_what_the_metric_reads(read, build):
    paddle.seed(0)
    model = build()
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, lambda o, l: model.loss(o, l), opt)
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, 128, (2, 64), dtype=np.int32))
    for _ in range(2):
        step(ids, ids)
    counts = model.routing_counts()
    # every layer's held rows lie inside the whole blocks it walked, and
    # no layer walks past the routed rows' chunks
    assert counts["rows_held"] <= counts["rows_walked"] \
        <= counts["rows_routed"]
    telemetry.default_tracer().metrics.snapshot()   # asks the sources
    del step, opt, model                            # and it outlives them
    assert read() == counts["rows_walked"] / counts["rows_held"] >= 1.0
