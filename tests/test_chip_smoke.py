"""chip_smoke.py off the chip: it must refuse to pass on the CPU, and its
phases must run to the end at the tiny size once the device check — here,
in the test only — lets the CPU through."""
import json
import os
import subprocess
import sys

import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.abspath(chip_smoke.__file__))


def _last_json(text):
    return json.loads(text.strip().splitlines()[-1])


def test_cpu_run_exits_nonzero_and_says_not_ok():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py"),
                        "--tiny"], capture_output=True, text=True,
                       timeout=300, env=env, cwd=ROOT)
    assert p.returncode != 0, p.stdout[-500:]
    last = _last_json(p.stdout)
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    # no phase ran: the device check is the first thing that happens
    assert '"phase"' not in p.stdout


def _run_with_cpu_let_through(monkeypatch, capsys, argv):
    monkeypatch.setattr(chip_smoke, "check_device", lambda dev, chips: None)
    rc = chip_smoke.main(argv)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    return rc, lines


def test_phases_run_to_the_end_at_tiny_size(monkeypatch, capsys):
    rc, lines = _run_with_cpu_let_through(monkeypatch, capsys, ["--tiny"])
    assert rc == 0, lines[-1]
    phases = {l["phase"]: l for l in lines if "phase" in l}
    assert list(phases) == ["start", "serve", "serve_checks", "between",
                            "train"]
    serve = phases["serve"]
    assert serve["requests_done"] == chip_smoke.N_REQUESTS
    assert serve["tokens_generated"] == \
        chip_smoke.N_REQUESTS * chip_smoke.MAX_NEW
    # warmup compiled every program the requests dispatched
    assert serve["compiles"]["run"] == 0
    # on the CPU the engine says it serves through the reference
    assert serve["attention"]["ragged"].startswith("reference")
    train = phases["train"]
    assert len(train["losses"]) == 5
    assert train["losses"][-1] < train["losses"][0]
    assert lines[-1] == {"ok": True, "device": lines[0]["device"]}


def test_tp4_phase_runs_alone_on_four_virtual_devices(monkeypatch, capsys):
    rc, lines = _run_with_cpu_let_through(monkeypatch, capsys,
                                          ["--tiny", "--chips", "4"])
    assert rc == 0, lines[-1]
    assert [l["phase"] for l in lines if "phase" in l] == ["start", "tp4"]
    tp4 = lines[1]
    assert tp4["logits_rel_err"] < 0.05 and tp4["logits_cosine"] > 0.999
    assert len(tp4["identical_prefix_tokens"]) == chip_smoke.N_REQUESTS
    assert tp4["tp4"]["compiles"] > 0
    assert lines[-1]["ok"] is True


def test_a_failing_phase_fails_the_script(monkeypatch, capsys):
    def boom(args, dev):
        raise RuntimeError("phase broke")

    monkeypatch.setattr(chip_smoke, "phase_serve", boom)
    rc, lines = _run_with_cpu_let_through(monkeypatch, capsys, ["--tiny"])
    assert rc == 1
    assert lines[-1]["ok"] is False
    assert "phase broke" in lines[-1]["error"]


def test_kernel_check_fails_on_reference_path(monkeypatch):
    """On the chip a step program without its kernels is a failure, not
    a slower pass."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1)
    x = jax.ShapeDtypeStruct((4,), jnp.float32)
    assert chip_smoke.count_kernels(f, (x,), on_tpu=False, want=1) == \
        {"lowered": 0}
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.count_kernels(f, (x,), on_tpu=True, want=1,
                                 compiled=False)
