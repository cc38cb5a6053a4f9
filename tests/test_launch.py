"""Launcher / spawn / elastic / auto-tuner tests.

Follows the reference's "multi-node without a cluster" pattern
(/root/reference/test/collective/test_communication_api_base.py:58-71):
N launcher copies on localhost rendezvousing through the master KV."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.core import native
from paddle_tpu.distributed.auto_tuner import (
    AutoTuner, Candidate, ClusterSpec, ModelSpec, TunableSpace)
from paddle_tpu.distributed.elastic import (
    ElasticLevel, ElasticManager, ElasticStatus)
from paddle_tpu.distributed.launch.context import Context, free_port

needs_native = pytest.mark.skipif(
    not native.available(),
    reason=f"native lib unavailable: {native.load_error()}")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_launcher(args, cwd, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch", *args],
        cwd=cwd, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT)


@pytest.fixture
def worker_script(tmp_path):
    """A tiny 'training' script that records its injected env."""
    p = tmp_path / "worker.py"
    p.write_text(
        "import json, os\n"
        "out = {k: v for k, v in os.environ.items()"
        " if k.startswith(('PADDLE_', 'MASTER_'))}\n"
        "path = f\"result_{out['PADDLE_TRAINER_ID']}.json\"\n"
        "json.dump(out, open(path, 'w'))\n"
        "print('worker done', out['PADDLE_TRAINER_ID'])\n")
    return str(p)


class TestContext:
    def test_nnodes_parsing(self):
        assert Context._parse_nnodes("3") == (3, 0)
        assert Context._parse_nnodes("2:6") == (2, 6)

    def test_from_args(self):
        ctx = Context.from_args(
            ["--nnodes", "2", "--nproc_per_node", "2", "--master",
             "127.0.0.1:1234", "train.py", "--lr", "0.1"])
        assert ctx.nnodes == 2 and ctx.nproc_per_node == 2
        assert ctx.training_script == "train.py"
        assert ctx.training_script_args == ["--lr", "0.1"]


class TestLaunchSingleNode:
    def test_single_node_env_injection(self, worker_script, tmp_path):
        proc = _run_launcher(
            ["--nproc_per_node", "2", "--log_dir", "lg", worker_script],
            cwd=str(tmp_path))
        out, _ = proc.communicate(timeout=120)
        assert proc.returncode == 0, out.decode()
        for rank in range(2):
            res = json.load(open(tmp_path / f"result_{rank}.json"))
            assert res["PADDLE_TRAINER_ID"] == str(rank)
            assert res["PADDLE_TRAINERS_NUM"] == "2"
        # per-rank logs exist and contain the worker's stdout
        logs = os.listdir(tmp_path / "lg")
        assert len(logs) == 2
        assert "worker done" in open(tmp_path / "lg" / logs[0]).read()

    def test_failing_worker_restarts_then_fails(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import sys; sys.exit(3)\n")
        proc = _run_launcher(
            ["--max_restart", "1", str(bad)], cwd=str(tmp_path))
        out, _ = proc.communicate(timeout=120)
        assert proc.returncode == 1
        assert out.decode().count("restarting") == 1


@needs_native
class TestLaunchMultiNode:
    def test_two_node_rendezvous(self, worker_script, tmp_path):
        port = free_port()
        master = f"127.0.0.1:{port}"
        procs = [
            _run_launcher(["--master", master, "--nnodes", "2",
                           "--job_id", "t2n", worker_script],
                          cwd=str(tmp_path))
            for _ in range(2)
        ]
        outs = [p.communicate(timeout=180)[0] for p in procs]
        for p, o in zip(procs, outs):
            assert p.returncode == 0, o.decode()
        ids = set()
        for rank in range(2):
            res = json.load(open(tmp_path / f"result_{rank}.json"))
            assert res["PADDLE_TRAINERS_NUM"] == "2"
            assert res["PADDLE_MASTER"] == master
            assert len(res["PADDLE_TRAINER_ENDPOINTS"].split(",")) == 2
            ids.add(res["PADDLE_TRAINER_ID"])
        assert ids == {"0", "1"}


class TestSpawn:
    def test_spawn_runs_and_injects_rank(self, tmp_path):
        from paddle_tpu.distributed import spawn
        marker = str(tmp_path / "m")
        spawn(_spawn_worker, args=(marker,), nprocs=2)
        got = sorted(open(marker + str(r)).read() for r in range(2))
        assert got == ["0/2", "1/2"]

    def test_spawn_propagates_failure(self):
        from paddle_tpu.distributed import spawn
        with pytest.raises(RuntimeError, match="rank"):
            spawn(_spawn_failer, nprocs=2)


def _spawn_worker(marker):
    rank = os.environ["PADDLE_TRAINER_ID"]
    n = os.environ["PADDLE_TRAINERS_NUM"]
    with open(marker + rank, "w") as f:
        f.write(f"{rank}/{n}")


def _spawn_failer():
    if os.environ["PADDLE_TRAINER_ID"] == "1":
        raise ValueError("intentional")


@needs_native
class TestElastic:
    def test_membership_and_plan(self):
        store = native.TCPStore(is_master=True, world_size=1)
        m0 = ElasticManager(store, "job", rank=0, min_nodes=2, max_nodes=3,
                            level=ElasticLevel.FAULT_TOLERANCE,
                            heartbeat_interval=0.2)
        m1 = ElasticManager(store, "job", rank=1, min_nodes=2, max_nodes=3,
                            level=ElasticLevel.FAULT_TOLERANCE,
                            heartbeat_interval=0.2)
        m0.register(); m1.register()
        alive = m0.alive_nodes()
        assert alive == [0, 1]
        assert m0.healthy(alive)
        m0._last_alive = alive
        assert m0.plan(alive) == ElasticStatus.RUNNING
        # rank 1 dies: its beat goes stale
        time.sleep(0.5)
        m0.heartbeat()
        alive = m0.alive_nodes()
        assert alive == [0]
        assert m0.plan(alive) == ElasticStatus.ERROR
        m0._last_alive = alive  # what the watch loop would do
        # rank 1 comes back
        m1.heartbeat()
        alive = m0.alive_nodes()
        assert set(alive) == {0, 1}
        assert m0.plan(alive) == ElasticStatus.RESTART  # membership changed
        store.close()

    def test_watch_thread_fires_on_change(self):
        store = native.TCPStore(is_master=True, world_size=1)
        changes = []
        m0 = ElasticManager(store, "watch", rank=0, min_nodes=1,
                            max_nodes=2, heartbeat_interval=0.2)
        m0.start(on_change=lambda alive: changes.append(list(alive)))
        m1 = ElasticManager(store, "watch", rank=1, min_nodes=1,
                            max_nodes=2, heartbeat_interval=0.2)
        m1.register()
        deadline = time.time() + 5
        while not changes and time.time() < deadline:
            time.sleep(0.05)
        m0.stop()
        store.close()
        assert changes and set(changes[-1]) == {0, 1}


class TestAutoTuner:
    def _tuner(self, chips=8):
        model = ModelSpec(num_layers=32, hidden=4096, ffn_hidden=14336,
                          heads=32, vocab=128256, seq_len=8192,
                          global_batch=64)
        return AutoTuner(model, ClusterSpec(num_chips=chips))

    def test_candidates_valid(self):
        t = self._tuner()
        cands = t.candidates()
        assert cands
        for c in cands:
            assert c.degrees() == 8
            assert 32 % c.pp == 0 and 32 % c.tp == 0
            assert c.est_memory <= t.cluster.hbm_bytes

    def test_pruning_respects_memory(self):
        # tiny HBM: pure-DP candidates (full replica per chip) must vanish
        t = self._tuner()
        t.cluster.hbm_bytes = 30e9
        for c in t.candidates():
            assert not (c.fsdp == 1 and c.tp == 1 and c.pp == 1
                        and not c.use_recompute)

    def test_tune_prefers_measured(self):
        t = self._tuner()
        top = t.tune(top_k=3)
        assert len(top) == 3
        # record a fake great measurement on the worst of the three
        worst = top[-1]
        t.recorder.record(worst, 1e-6)
        assert t.tune(top_k=1)[0].key() == worst.key()

    def test_space_restriction(self):
        t = self._tuner()
        t.space = TunableSpace(mp_degree=[4], pp_degree=[1],
                               use_recompute=[False])
        for c in t.candidates():
            assert c.tp == 4 and c.pp == 1

    def test_recorder_roundtrip(self, tmp_path):
        t = self._tuner()
        c = t.candidates()[0]
        t.recorder.record(c, 0.123)
        p = str(tmp_path / "rec.json")
        t.recorder.save(p)
        t2 = self._tuner()
        t2.recorder.load(p)
        assert t2.recorder.get(c) == 0.123


class TestFailureInjectionResume:
    """Kill a worker mid-train; the launcher must relaunch with a bumped
    generation and the worker must RESUME from its last checkpoint with
    loss continuity (reference pattern: the subprocess-kill tests of
    /root/reference/test/collective/ + elastic manager restart loop,
    fleet/elastic/manager.py:126,254-296)."""

    TRAIN = r'''
import json, os, signal, sys
import numpy as np
# CPU backend for the trainer subprocess
os.environ["JAX_PLATFORMS"] = "cpu"
import paddle_tpu as paddle
from paddle_tpu import nn, optimizer

gen = int(os.environ.get("PADDLE_RESTART_GENERATION", "0"))
workdir = os.environ["TEST_WORKDIR"]
ckpt = os.path.join(workdir, "ckpt.pdparams")
log = open(os.path.join(workdir, f"train_gen{gen}.jsonl"), "a")

paddle.seed(0)
model = nn.Linear(8, 8)
opt = optimizer.SGD(learning_rate=0.05, parameters=model.parameters())
start_step = 0
if os.path.exists(ckpt):
    state = paddle.load(ckpt)
    model.set_state_dict(state["model"])
    start_step = int(state["step"])

rng = np.random.RandomState(7)
X = paddle.to_tensor(rng.randn(16, 8).astype(np.float32))
Y = paddle.to_tensor((rng.randn(16, 8) * 0.1).astype(np.float32))
step_fn = paddle.jit.TrainStep(model, lambda o, l: ((o - l) ** 2).mean(),
                               opt)
TOTAL, KILL_AT = 12, 6
for step in range(start_step, TOTAL):
    loss = float(step_fn(X, Y))
    log.write(json.dumps({"gen": gen, "step": step, "loss": loss}) + "\n")
    log.flush()
    paddle.save({"model": model.state_dict(), "step": step + 1}, ckpt)
    if gen == 0 and step + 1 == KILL_AT:
        os.kill(os.getpid(), signal.SIGKILL)   # die mid-train
print("training complete at", TOTAL)
'''

    def test_kill_relaunch_resume(self, tmp_path):
        script = tmp_path / "train.py"
        script.write_text(self.TRAIN)
        proc = _run_launcher(
            ["--nproc_per_node=1", "--max_restart=2", str(script)],
            cwd=str(tmp_path),
            extra_env={"TEST_WORKDIR": str(tmp_path),
                       "JAX_PLATFORMS": "cpu"})
        out, _ = proc.communicate(timeout=240)
        text = out.decode()
        assert proc.returncode == 0, text
        assert "restarting (attempt 1" in text, text

        def read(gen):
            p = tmp_path / f"train_gen{gen}.jsonl"
            return [json.loads(l) for l in p.read_text().splitlines()]

        g0, g1 = read(0), read(1)
        # generation 0 died after step 5 (KILL_AT=6)
        assert [r["step"] for r in g0] == list(range(6))
        # generation 1 RESUMED at step 6 — not from scratch
        assert [r["step"] for r in g1] == list(range(6, 12))
        # loss continuity: the resumed first loss continues the descent —
        # strictly below generation 0's last recorded loss
        assert g1[0]["loss"] < g0[-1]["loss"], (g0, g1)
        # and total descent across the failure
        assert g1[-1]["loss"] < g0[0]["loss"]
