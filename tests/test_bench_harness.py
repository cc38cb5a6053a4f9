"""bench.py self-defense harness tests (VERDICT r4 #1).

The r4 capture recorded a poisoned environment (external HBM pressure:
headline 24x slow, then seven RESOURCE_EXHAUSTED rows) as if it were
the code's number. These tests drive the auto-mode orchestrator with an
injected child runner to prove the defenses: calibration gating with
backoff, per-mode isolation + retry, the env_suspect flag, and per-row
suspect marking. Mirrors the reference's stance that perf capture is
gated CI infrastructure (tools/ci_op_benchmark.sh,
tools/check_op_benchmark_result.py).
"""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import bench  # noqa: E402

GOOD_CAL = {"metric": "calibration_tflops", "value": 120.0,
            "unit": "TFLOP/s", "vs_baseline": 0.61,
            "extra": {"calibration_tflops": 120.0,
                      "calibration_frac_peak": 0.61,
                      "calibration_ok": True}}
BAD_CAL = {"metric": "calibration_tflops", "value": 5.0,
           "unit": "TFLOP/s", "vs_baseline": 0.025,
           "extra": {"calibration_tflops": 5.0,
                     "calibration_frac_peak": 0.025,
                     "calibration_ok": False}}


def _mid(value=32859.0, mfu=0.743):
    # real children stamp extra["lkg_ratio"] via main(); the fakes must
    # too, or the merge-clobber bug class goes untested
    return {"metric": "llama_mid_train_tokens_per_sec_chip",
            "value": value, "unit": "tokens/s/chip",
            "vs_baseline": round(mfu / 0.40, 4),
            "extra": {"mfu": mfu, "params": 650164224, "batch": 4,
                      "seq": 2048, "final_loss": 5.5, "step_ms": 255.0,
                      "lkg_ratio": round(value / 32859.0, 4)}}


def _simple(metric, value, extra=None):
    extra = dict(extra or {})
    extra.setdefault("lkg_ratio", 1.0)
    return {"metric": metric, "value": value, "unit": "u",
            "vs_baseline": 1.0, "extra": extra}


class Runner:
    """Scripted child runner: mode -> list of responses (popped in
    order; the last response repeats)."""

    def __init__(self, script):
        self.script = {k: list(v) for k, v in script.items()}
        self.calls = []

    def __call__(self, mode, timeout):
        self.calls.append(mode)
        seq = self.script.get(mode, [(None, "no script")])
        resp = seq.pop(0) if len(seq) > 1 else seq[0]
        if isinstance(resp, tuple):
            return resp
        return resp, ""


def _full_script(**overrides):
    script = {
        "calibrate": [(GOOD_CAL, "")],
        "mid": [(_mid(), "")],
        "mid4k": [(_mid(29990.0, 0.740), "")],
        "mid8k": [(_mid(15000.0, 0.760), "")],
        "1b": [(_mid(20400.0, 0.703), "")],
        "resnet": [(_simple("resnet50_train_imgs_per_sec_chip", 2170.0,
                            {"resnet50_imgs_per_sec": 2170.0}), "")],
        "decode": [(_simple("paged_decode_tokens_per_sec", 4434.0,
                            {"paged_decode_tok_per_sec": 4434.0}), "")],
        "serving": [(_simple(
            "serving_bf16_c8_tok_per_sec", 289.0,
            {"serving_bf16_c8_tok_per_sec": 289.0,
             "serving_capacity_decode_tok_per_sec": 3398.0,
             # ISSUE 14: the serving_trace suite row re-pins its <5%
             # bar with the program observatory riding the traced leg
             # and asserts a sealed steady state — scripted same-PR
             # (the PR-9 lesson, four times applied)
             "serving_trace_overhead_frac": 0.012,
             "serving_trace_unexpected_recompiles": 0,
             "serving_trace_counter_samples": 2048,
             "serving_trace_tokens_identical": True}), "")],
        # serving_tp joined AUTO_MODES in the ISSUE-8 PR but was never
        # scripted here, so every auto run "failed" the mode, burned a
        # recalibration, and broke the two call-count assertions below
        "serving_tp": [(_simple(
            "serving_tp2_tok_per_sec", 119.0,
            {"serving_tp2_tok_per_sec": 119.0}), "")],
        # serving_lora joined AUTO_MODES in the ISSUE-10 PR — scripted
        # from day one (the PR-9 lesson)
        "serving_lora": [(_simple(
            "serving_lora_lora_tok_per_sec", 95.0,
            {"serving_lora_lora_tok_per_sec": 95.0,
             "serving_lora_adapter_hit_rate": 0.6}), "")],
        # serving_dp joined AUTO_MODES in the ISSUE-11 PR — scripted
        # same-PR (the PR-9 lesson, twice applied)
        "serving_dp": [(_simple(
            "serving_dp2_tok_per_sec", 88.0,
            {"serving_dp2_tok_per_sec": 88.0,
             "serving_dp_affinity_hit_gain": 0.3,
             "serving_dp_tokens_identical": True}), "")],
        # serving_proc joined AUTO_MODES in the ISSUE-19 PR — scripted
        # same-PR (the PR-9 lesson, five times applied)
        "serving_proc": [(_simple(
            "serving_proc_process_tok_per_sec", 83.0,
            {"serving_proc_process_tok_per_sec": 83.0,
             "serving_proc_overhead_pct": 4.1,
             "serving_proc_respawn_wall_s": 9.5,
             "serving_proc_worker_exits": 1}), "")],
        # serving_kv8 joined AUTO_MODES in the ISSUE-13 PR — scripted
        # same-PR (the PR-9 lesson, three times applied)
        "serving_kv8": [(_simple(
            "serving_kv8_bytes_per_token_reduction_x", 3.56,
            {"serving_kv8_bytes_per_token_reduction_x": 3.56,
             "serving_kv8_tokens_identical": True,
             "serving_kv8_cap_fp32_oom_preemptions": 6,
             "serving_kv8_cap_int8_oom_preemptions": 1}), "")],
        # serving_msteps joined AUTO_MODES in the ISSUE-16 PR — scripted
        # same-PR (the PR-9 lesson, four times applied)
        "serving_msteps": [(_simple(
            "serving_msteps_dispatch_reduction_x", 3.4,
            {"serving_msteps_dispatch_reduction_x": 3.4,
             "serving_msteps_tokens_identical": True,
             "serving_msteps_tok_per_sec_ratio": 1.2,
             "serving_msteps_host_overhead_shrink_x": 1.9,
             "serving_msteps_k4_fused_windows": 8}), "")],
        "pp": [(_simple("pp_remat_overhead_x", 0.991,
                        {"pp_remat_overhead_x": 0.991,
                         "pp_tick_fwd_ms": 0.086,
                         "pp_bubble_measured_p4m16v1": 0.158}), "")],
        "moe": [(_simple("moe_ragged_tok_per_sec", 66282.0,
                         {"moe_ragged_tok_per_sec": 66282.0}), "")],
        "8b": [(_simple("paged_decode_8b_int4_tok_per_sec", 580.0,
                        {"paged_decode_8b_int4_tok_per_sec": 580.0}),
                "")],
        "profile": [(_simple("profile_device_events", 8211,
                             {"profile_device_events": 8211}), "")],
        "dit": [(_simple("dit_xl2_imgs_per_sec", 2500.0,
                         {"dit_xl2_mfu": 0.779}), "")],
    }
    script.update(overrides)
    return script


def test_lkg_ratio_paths():
    assert bench._lkg_ratio("mid", _mid()) == pytest.approx(1.0)
    assert bench._lkg_ratio("mid", _mid(value=32859.0 / 2)) == \
        pytest.approx(0.5)
    # extra-path metric (mfu-keyed rows)
    assert bench._lkg_ratio("1b", _mid(123.0, mfu=0.703)) == \
        pytest.approx(1.0)
    # lower-is-better: pp tick time doubling -> ratio 0.5
    pp = _simple("pp_remat_overhead_x", 0.99,
                 {"pp_tick_fwd_ms": 0.172})
    assert bench._lkg_ratio("pp", pp) == pytest.approx(0.5)
    # unknown mode / missing path -> None
    assert bench._lkg_ratio("nope", _mid()) is None
    assert bench._lkg_ratio("pp", _simple("x", 1.0)) is None
    # multi-entry gate: min over entries, so a collapsed open-loop row
    # flags serving even when the capacity metric is at parity
    sv = _simple("serving_bf16_c8_tok_per_sec", 28.9,
                 {"serving_bf16_c8_tok_per_sec": 28.9,
                  "serving_capacity_decode_tok_per_sec": 3398.0})
    assert bench._lkg_ratio("serving", sv) == pytest.approx(0.1)


def test_auto_happy_path_merges_all_modes():
    r = Runner(_full_script())
    out = bench.run_auto(child_runner=r, backoff=(0,))
    assert out["env_suspect"] is False
    assert out["metric"] == "llama_mid_train_tokens_per_sec_chip"
    assert out["value"] == 32859.0
    ex = out["extra"]
    # merged rows from every mode
    assert ex["llama_mid4k_tok_per_sec"] == 29990.0
    assert ex["llama_1b_mfu"] == 0.703
    assert ex["resnet50_imgs_per_sec"] == 2170.0
    assert ex["paged_decode_tok_per_sec"] == 4434.0
    assert ex["serving_capacity_decode_tok_per_sec"] == 3398.0
    assert ex["pp_bubble_measured_p4m16v1"] == 0.158
    assert ex["moe_ragged_tok_per_sec"] == 66282.0
    assert ex["dit_xl2_mfu"] == 0.779
    # per-mode trend ratios (VERDICT r4 #8) and the calibration record;
    # the headline's ratio must survive the merge of children that all
    # carry their own extra["lkg_ratio"]
    assert ex["lkg_ratio"] == pytest.approx(1.0)
    assert ex["decode_lkg_ratio"] == pytest.approx(1.0)
    assert ex["calibration_frac_peak"] == 0.61
    # exactly one calibration, one child per mode
    assert r.calls.count("calibrate") == 1
    assert r.calls.count("mid") == 1
    assert r.calls.count("dit") == 1


def test_auto_poisoned_env_withholds_perf_rows():
    """r4 scenario: calibration never reaches the band -> env_suspect
    JSON with the calibration number, and NO mode is ever run."""
    r = Runner({"calibrate": [(BAD_CAL, "")]})
    out = bench.run_auto(child_runner=r, backoff=(0, 0, 0))
    assert out["env_suspect"] is True
    assert out["value"] == 0.0
    assert out["extra"]["calibration"]["calibration_frac_peak"] == 0.025
    assert "mid" not in r.calls
    assert r.calls.count("calibrate") == 3          # backoff attempts
    assert any("outside band" in n for n in out["extra"]["notes"])


def test_auto_mode_crash_is_isolated_and_retried():
    """One OOMing mode must not cascade (r4: seven rows died after one
    OOM): decode crashes twice -> recorded as an error; later modes
    still run and merge."""
    script = _full_script(decode=[(None, "RESOURCE_EXHAUSTED"),
                                  (None, "RESOURCE_EXHAUSTED")])
    r = Runner(script)
    out = bench.run_auto(child_runner=r, backoff=(0,))
    assert out["env_suspect"] is False
    assert "decode_error" in out["extra"]
    assert "paged_decode_tok_per_sec" not in out["extra"]
    # the crash triggered one re-calibration + one retry
    assert r.calls.count("decode") == 2
    assert r.calls.count("calibrate") >= 2
    # the suite continued past the dead mode
    assert out["extra"]["moe_ragged_tok_per_sec"] == 66282.0
    assert out["extra"]["dit_xl2_mfu"] == 0.779


def test_auto_slow_row_marked_suspect():
    """A row persistently <30% of last-known-good (the r4 24x-slow
    headline shape) is recorded but flagged, not silently trusted."""
    slow = _mid(value=1293.0, mfu=0.029)
    script = _full_script(mid=[(slow, "")])
    r = Runner(script)
    out = bench.run_auto(child_runner=r, backoff=(0,))
    assert out["value"] == 1293.0
    assert out["extra"]["headline_suspect"] is True
    assert out["extra"]["lkg_ratio"] < 0.3
    assert r.calls.count("mid") == 2                # retried once


def test_auto_headline_fallback_uses_small_lkg():
    """mid dead twice -> small headline; its trend ratio must be
    computed against the SMALL entry (mfu-keyed), not mid's tok/s."""
    small = {"metric": "llama_small_train_tokens_per_sec_chip",
             "value": 43768.0, "unit": "tokens/s/chip",
             "vs_baseline": 1.81,
             "extra": {"mfu": 0.7227, "params": 508594176, "batch": 8,
                       "seq": 1024, "step_ms": 187.0,
                       "lkg_ratio": 1.0038}}
    script = _full_script(mid=[(None, "boom"), (None, "boom")],
                          small=[(small, "")])
    r = Runner(script)
    out = bench.run_auto(child_runner=r, backoff=(0,))
    assert out["metric"] == "llama_small_train_tokens_per_sec_chip"
    assert out["extra"]["lkg_ratio"] == pytest.approx(0.7227 / 0.72,
                                                      abs=1e-3)
    # the headline regression signal also survives a slow headline
    slow = Runner(_full_script(mid=[(_mid(value=1293.0, mfu=0.029),
                                     "")]))
    out2 = bench.run_auto(child_runner=slow, backoff=(0,))
    assert out2["extra"]["lkg_ratio"] < 0.3


def test_auto_env_dies_mid_suite_stops_cascade():
    """decode goes slow AND re-calibration now fails: the orchestrator
    flags env_suspect, keeps what it captured, and skips the remaining
    modes instead of recording seven rows of garbage."""
    slow_decode = _simple("paged_decode_tokens_per_sec", 100.0,
                          {"paged_decode_tok_per_sec": 100.0})
    script = _full_script(
        calibrate=[(GOOD_CAL, ""), (BAD_CAL, "")],
        decode=[(slow_decode, "")])
    r = Runner(script)
    out = bench.run_auto(child_runner=r, backoff=(0, 0))
    assert out["env_suspect"] is True
    assert out["value"] == 32859.0                  # headline kept
    assert out["extra"]["decode_suspect"] is True
    # modes after decode were skipped, not recorded
    assert "moe_ragged_tok_per_sec" not in out["extra"]
    assert any("skipped" in n for n in out["extra"]["notes"])


def test_calibrate_child_real_subprocess():
    """End-to-end: `python bench.py calibrate` in a fresh CPU process
    prints one parseable JSON line with the probe fields (band check is
    documented n/a on CPU)."""
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(bench.__file__), "bench.py"),
         "calibrate"],
        capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stderr[-1000:]
    row = json.loads(p.stdout.strip().splitlines()[-1])
    assert row["metric"] == "calibration_tflops"
    assert row["extra"]["calibration_ok"] is True
    assert row["extra"]["calibration_platform"] == "cpu"
    assert row["value"] > 0


def test_auto_crashed_child_retries_once_after_recalibration():
    """A crashed child is not re-run blindly: one re-calibration, then
    ONE retry, whose row is captured."""
    boom = (None, "jax.errors.JaxRuntimeError: INTERNAL: device lost")
    script = _full_script(mid4k=[boom, (_mid(29990.0, 0.740), "")])
    r = Runner(script)
    out = bench.run_auto(child_runner=r, backoff=(0,))
    assert out["extra"]["llama_mid4k_tok_per_sec"] == 29990.0
    assert "mid4k_error" not in out["extra"]
    assert r.calls.count("mid4k") == 2
    assert r.calls.count("calibrate") == 2


@pytest.mark.parametrize("kind,peak", [("TPU v5 lite", 197e12),
                                       ("TPU v5e", 197e12),
                                       ("TPU v5p", 459e12),
                                       ("TPU v4", 275e12)])
def test_detect_peak_flops_known_kinds(monkeypatch, kind, peak):
    import jax
    dev = type("D", (), {"device_kind": kind})()
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    assert bench.detect_peak_flops() == peak


def test_detect_peak_flops_unknown_kind_raises(monkeypatch):
    """A device that is not in the table is an error, never a v5e."""
    import jax
    dev = type("D", (), {"device_kind": "TPU v7x"})()
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    with pytest.raises(ValueError, match="v7x"):
        bench.detect_peak_flops()


def test_child_compile_cache_defaults_to_the_checkout(monkeypatch):
    """One cache: the caller's JAX_COMPILATION_CACHE_DIR where set,
    else <checkout>/.jax_cache — never a fixed path elsewhere."""
    seen = {}

    def fake_run(cmd, env=None, **kw):
        seen["cache"] = env["JAX_COMPILATION_CACHE_DIR"]
        return subprocess.CompletedProcess(cmd, 1, "", "")

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    bench._default_child_runner("calibrate", 5)
    assert seen["cache"] == os.path.join(
        os.path.dirname(os.path.abspath(bench.__file__)), ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    bench._default_child_runner("calibrate", 5)
    assert seen["cache"] == "/somewhere/else"
