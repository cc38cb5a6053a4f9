"""The plain float32 reference against the program at a tiny size on the
CPU: the trainer's model, loss and gradients, and prefill then decode
through the paged cache; and the control, the reference computed one
precision down, failing the same comparison."""
import copy

import numpy as np
import pytest

import tiny_tree
from benchmark import check, systems, traffic
from benchmark.drivers import train_steps
from benchmark.reference import llama_ref

SEED = 2 ** 31 + 5


def _cfg(base, dtype, weights=None):
    cfg = copy.deepcopy(base)
    cfg["model"]["torch_dtype"] = dtype
    if weights is not None:
        cfg["precision"]["weights"] = weights
        cfg["decoder"]["weight_dtype"] = None if weights == dtype \
            else weights
    return cfg


def test_forward_matches_llama_for_causal_lm_in_float32():
    import paddle_tpu as paddle
    cfg = _cfg(tiny_tree.TRAIN, "float32")
    cfg["precision"]["weights"] = "float32"
    trainer = systems.Trainer(cfg, SEED)
    ids = traffic.train_batch(512, 2, 48, SEED, 0)
    got = np.asarray(trainer.model(paddle.to_tensor(ids))._value)
    want = llama_ref.sequence_logits(
        cfg, SEED, list(ids), [np.arange(48)] * 2)
    for g, w in zip(got, want):
        assert np.abs(g - np.asarray(w)).max() < 2e-4


@pytest.mark.parametrize("dtype,loss,grad,change", [
    ("float32", 1e-5, 1e-3, 1e-3), ("bfloat16", 1e-3, 0.02, 0.02)])
def test_loss_gradients_and_update_match_the_trainer(dtype, loss, grad,
                                                     change):
    cfg = _cfg(tiny_tree.TRAIN, dtype)
    cfg["precision"]["weights"] = dtype
    mix = tiny_tree.MIXES["tiny_train"]
    trainer, setup = train_steps.setup(cfg, mix, SEED, lambda m: None)
    batches = [train_steps.feed(mix, 512, SEED, s) for s in range(3)]
    ref = check.reference_train_readings(cfg, SEED, batches)
    n = check.train_numbers(setup["readings"], ref)
    assert n["loss_rel_gap_max"] < loss
    assert n["grad_norm_gap_worst_leaf"] < grad
    assert n["change_norm_gap_worst_leaf"] < change
    # every leaf moved, on both sides
    assert min(ref["change_norm"].values()) > 0
    assert min(setup["readings"]["change_norm"].values()) > 0


def _served(cfg, prompts, new):
    """Greedy tokens of prefill then decode through the paged cache."""
    eng, _ = systems.build_engine(cfg, SEED)
    rids = [eng.add_request(p, systems.greedy(new)) for p in prompts]
    eng.run_to_completion()
    toks = [np.asarray(eng.result(r)) for r in rids]
    eng.close()
    return toks


@pytest.mark.parametrize("dtype,weights,limit", [
    ("float32", "float32", 1e-4), ("float32", "int8", 1e-4),
    ("bfloat16", "bfloat16", 0.05), ("bfloat16", "int8", 0.05)])
def test_prefill_then_decode_through_the_cache(dtype, weights, limit):
    cfg = _cfg(tiny_tree.SERVE, dtype, weights)
    prompts = [traffic.prompt_tokens(512, n, SEED, i)
               for i, n in enumerate((70, 33, 9))]   # 70 spans 3 chunks
    toks = _served(cfg, prompts, 12)
    seqs = [np.concatenate([p, t]) for p, t in zip(prompts, toks)]
    pos = [np.arange(len(p) - 1, len(p) + 11) for p in prompts]
    ref = llama_ref.sequence_logits(cfg, SEED, seqs, pos)
    gaps = np.concatenate([check.gaps_below_best(l, t)
                           for l, t in zip(ref, toks)])
    assert gaps.max() <= limit, gaps
    if weights != "int8":
        return
    # the control of the cells' own format: int4 weights fail the same
    # comparison
    low = llama_ref.sequence_logits(cfg, SEED, seqs, pos, precision="lower")
    control = np.concatenate([
        check.gaps_below_best(r, np.asarray(l).argmax(-1))
        for r, l in zip(ref, low)])
    assert control.max() > limit
    assert control.max() >= 3 * max(gaps.max(), 1e-6)


def test_the_training_control_fails_the_comparison():
    cfg = _cfg(tiny_tree.TRAIN, "bfloat16")
    mix = tiny_tree.MIXES["tiny_train"]
    batches = [train_steps.feed(mix, 512, SEED, s) for s in range(3)]
    ref = check.reference_train_readings(cfg, SEED, batches)
    low = check.reference_train_readings(cfg, SEED, batches,
                                         precision="lower")
    n = check.train_numbers(low, ref)
    limits = tiny_tree.LIMITS["t_train"]
    assert n["grad_norm_gap_worst_leaf"] > limits["grad_norm_gap_worst_leaf"]
    ok, _ = check.judge(n, limits)
    assert not ok


def test_reference_quantiser_is_the_published_scheme():
    import jax.numpy as jnp
    w = jnp.asarray(np.random.RandomState(0).randn(64, 8), jnp.float32)
    q, s = llama_ref.quantise_absmax(w, 127)
    assert np.allclose(np.abs(np.asarray(q)).max(0), 127)
    assert np.abs(np.asarray(q * s[None] - w)).max() <= \
        float(s.max()) / 2 + 1e-7
    q4, _ = llama_ref.quantise_absmax(w, 7)
    assert np.asarray(q4).min() >= -8 and np.asarray(q4).max() <= 7


def test_judge_needs_every_limited_number():
    ok, out = check.judge({"a": 1.0, "extra": 5.0}, {"a": 2.0, "b": 1.0})
    assert not ok and out["b"] == {"value": None, "limit": 1.0}
    assert out["extra"]["limit"] is None
    assert check.judge({"a": 1.0}, {"a": 1.0})[0]
    assert not check.judge({"a": float("nan")}, {"a": 1.0})[0]
