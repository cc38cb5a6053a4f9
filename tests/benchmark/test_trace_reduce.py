"""The reduction from a profiler trace to numbers, on a hand-made trace
whose answers can be worked out on paper and on a small recorded one
(``data/``, cut from a chip run of this benchmark); and the Llama
family's work counts against hand-worked ones for the Mistral-7B shapes."""
import json
import os

import pytest

import tiny_tree
from benchmark import costs, manifest, trace_reduce as tr
from benchmark.families import llama

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def hand_made():
    """A window of 100 ms on one device: two runs of a step program with
    three operations each, 34 ms busy in all; the host steps, polls and
    waits for a request in between."""
    ops = [["fusion.1", 10 * MS, 6 * MS], ["attn_kernel", 16 * MS, 4 * MS],
           ["fusion.2", 19 * MS, 5 * MS],             # overlaps the kernel
           ["fusion.1", 50 * MS, 6 * MS], ["attn_kernel", 56 * MS, 4 * MS],
           ["fusion.2", 60 * MS, 10 * MS],
           ["fusion.9", 150 * MS, 5 * MS]]            # after the window
    modules = [["jit_step(1)", 10 * MS, 14 * MS],
               ["jit_step(1)", 50 * MS, 20 * MS],
               ["jit_other(2)", 150 * MS, 5 * MS]]
    spans = [["bench:window", 0, 100 * MS],
             ["bench:step", 2 * MS, 7 * MS], ["bench:poll", 9 * MS, 1 * MS],
             ["bench:no_request_due", 24 * MS, 25 * MS],
             ["bench:step", 49 * MS, 2 * MS],
             ["bench:no_request_due", 72 * MS, 28 * MS]]
    return {"planes": {
        "/device:TPU:0": {tr.MODULES_LINE: modules, tr.OPS_LINE: ops},
        "host": {"spans": spans}}}


def test_busy_union_and_idle_share():
    trace = hand_made()
    assert tr.window_of(trace) == (0, 100 * MS)
    busy, window = tr.busy_and_window_s(trace)
    assert window == pytest.approx(0.1)
    assert busy == pytest.approx(0.034)     # 14 ms + 20 ms, overlap once
    assert tr.idle_share(trace) == pytest.approx(0.66)


def test_union_merges_touching_and_nested_intervals():
    ev = [["a", 0, 5], ["b", 5, 5], ["c", 2, 1], ["d", 20, 1]]
    assert tr.union(ev) == [[0, 10], [20, 21]]
    assert tr.busy_ns(ev) == 11
    assert tr.clip(ev, 3, 20) == [["a", 3, 2], ["b", 5, 5]]


def test_module_and_kernel_time():
    trace = hand_made()
    assert tr.module_durations_s(trace, "jit_step") == \
        pytest.approx([0.014, 0.020])
    assert tr.module_durations_s(trace, "jit_other") == []   # outside
    seconds, n = tr.op_time_s(trace, "attn_kernel")
    assert (seconds, n) == (pytest.approx(0.008), 2)
    assert tr.op_time_s(trace, "fusion", within_modules="jit_step")[1] == 4
    assert tr.op_time_s(trace, "no_such_kernel") == (0.0, 0)


def test_top_ops_and_gap_labels():
    trace = hand_made()
    top = tr.top_ops(trace, 2)
    assert top[0] == ["fusion.2", pytest.approx(0.015)]
    assert top[1] == ["fusion.1", pytest.approx(0.012)]
    gaps = dict(tr.idle_gaps(trace))
    # 0-10 ms: mostly the step span; 24-50 and 70-100: waiting for a request
    assert gaps["no_request_due"] == pytest.approx(0.056)
    assert gaps["step"] == pytest.approx(0.010)
    assert sum(gaps.values()) == pytest.approx(0.066)


def test_loops_are_not_counted_beside_their_bodies():
    ev = [["while.5", 0, 100], ["fusion.1", 0, 40], ["closed_call.2", 40, 60],
          ["fusion.7", 120, 10]]
    assert [e[0] for e in tr.leaf_ops(ev)] == \
        ["fusion.1", "closed_call.2", "fusion.7"]


def test_an_event_name_is_cut_to_the_operation_and_its_target():
    line = ('%closed_call.354 = bf16[8,6,32,128]{3,2,1,0} custom-call(s32[48]'
            '{0} %fusion.2008), custom_call_target="tpu_custom_call", '
            'frontend_attributes={kernel_metadata={}}')
    assert tr.short_name(line) == "closed_call.354 tpu_custom_call"
    assert tr.short_name("%fusion.318 = (bf16[4096,32768]) fusion(...)") == \
        "fusion.318"
    assert tr.short_name("jit_step(9128751667246542526)") == \
        "jit_step(9128751667246542526)"


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError, match="no device"):
        tr.busy_and_window_s({"planes": {"host": {"spans": []}}})


def test_readers_return_nothing_where_there_is_nothing_to_read():
    from benchmark.readers import (step_mfu, trace_kernel_roofline,
                                   trace_module_time)
    ctx = {"trace": hand_made(), "res": {}, "mix": {}, "model": {},
           "peak": {"flops_per_s_bf16": 1.0, "bytes_per_s_hbm": 1.0},
           "trace_clock": (0.0, 0.1)}
    assert trace_module_time.read(ctx, pattern="nothing") is None
    assert step_mfu.read(ctx, pattern="nothing") is None
    assert trace_kernel_roofline.read(
        ctx, pattern="nothing", work="flash_flops", bound="flops") is None
    assert trace_module_time.read(ctx, pattern="jit_step", stat="p50",
                                  scale=1000.0) == pytest.approx(17.0)


# -- the recorded trace -------------------------------------------------------

RECORDED = sorted(f for f in os.listdir(os.path.join(HERE, "data"))
                  if f.endswith(".json.gz")) \
    if os.path.isdir(os.path.join(HERE, "data")) else []


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_trace_reduces(name):
    trace = tr.load(os.path.join(HERE, "data", name))
    with open(os.path.join(HERE, "data",
                           name.replace(".json.gz", ".expect.json"))) as f:
        expect = json.load(f)
    busy, window = tr.busy_and_window_s(trace)
    assert 0 < busy <= window
    assert busy == pytest.approx(expect["busy_s"], rel=1e-9)
    assert window == pytest.approx(expect["window_s"], rel=1e-9)
    runs = tr.module_durations_s(trace, expect["module"])
    assert len(runs) == expect["module_runs"]
    assert sum(runs) == pytest.approx(expect["module_s"], rel=1e-9)
    assert sum(runs) <= busy * 1.0001
    seconds, n = tr.op_time_s(trace, expect["kernel"], expect["module"])
    assert n == expect["kernel_events"]
    assert seconds == pytest.approx(expect["kernel_s"], rel=1e-9)
    assert tr.top_ops(trace, 3)[0][0] == expect["top_op"]
    gaps = tr.idle_gaps(trace)
    assert sum(s for _, s in gaps) <= (window - busy) * 1.0001
    assert gaps[0][0] == expect["top_gap"]


# -- costs --------------------------------------------------------------------

@pytest.fixture(scope="module")
def mistral():
    """The published shapes: the serving file's, at its published depth."""
    cfg = manifest.load_json(
        tiny_tree.REPO, "benchmark/configs/mistral_7b_v03_int8_serve.json")
    return dict(cfg["model"], **cfg["published"])


def test_mistral_parameter_counts(mistral):
    per_layer = 4096 * (4096 + 2 * 1024) + 4096 * 4096 + 3 * 4096 * 14336
    assert per_layer == 218_103_808
    head = 4096 * 32768
    assert llama.matmul_params(mistral) == 32 * per_layer + head
    assert llama.total_params(mistral) == \
        32 * per_layer + 2 * head + 65 * 4096 == 7_248_023_552


def test_mistral_kv_and_flops(mistral):
    assert llama.kv_bytes_per_token(mistral) == 128 * 1024
    assert costs.causal_pairs(4096) == 4096 * 4097 // 2
    # one decode token at context 1000: 2 FLOPs per matmul parameter and
    # 4 * 128 per head, layer and key
    assert llama.forward_flops(mistral, 1, 1000) == \
        2 * llama.matmul_params(mistral) + 4 * 128 * 32 * 32 * 1000
    assert llama.kv_read_bytes(mistral, 1000) == 1000 * 131072
    assert llama.weight_stream_bytes(mistral) == llama.matmul_params(mistral)


def test_train_flops_of_the_two_layer_stage(mistral):
    two = dict(mistral, num_hidden_layers=2)
    params = 2 * 218_103_808 + 4096 * 32768
    assert llama.matmul_params(two) == params == 570_425_344
    assert llama.total_params(two) == 704_663_552
    pairs = 4 * (4096 * 4097 // 2)
    attn = 4 * 128 * 32 * 2 * pairs
    assert llama.train_flops(two, 4, 4096) == \
        3 * (2 * params * 4 * 4096 + attn)
    assert llama.flash_train_flops(two, 4, 4096) == 3 * attn
