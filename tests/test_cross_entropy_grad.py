"""``F.cross_entropy`` on integer labels hands back its gradient from the
forward pass, and the causal models shift the labels, not the logits.

The reference throughout is the body ``cross_entropy`` had before
(``log_softmax`` of a float32 cast, differentiated by jax), kept here as
``autodiff_ce``, and for the models the sliced-logits form over it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import optimizer
from paddle_tpu.framework.core import apply
from paddle_tpu.models import (GPTForCausalLM, LlamaForCausalLM,
                               MoEForCausalLM, gpt_tiny, llama_tiny,
                               moe_tiny)
from paddle_tpu.utils import telemetry

N, V, IGNORE = 12, 37, -100


def autodiff_ce(logits, idx, reduction="mean", ignore_index=IGNORE):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    if idx.ndim == logp.ndim:
        idx = jnp.squeeze(idx, -1)
    valid = idx != ignore_index
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, idx, 0)[..., None], axis=-1)[..., 0]
    loss = jnp.where(valid, -picked, 0.0)
    if reduction == "mean":
        return jnp.sum(loss) / jnp.maximum(
            jnp.sum(valid.astype(jnp.float32)), 1.0)
    return jnp.sum(loss) if reduction == "sum" else loss


def ce(logits, label, **kw):
    """``F.cross_entropy`` on jax values, as ``jit.TrainStep`` calls it:
    wrapped, under ``no_grad``, differentiated by the caller."""
    with paddle.no_grad():
        return F.cross_entropy(paddle.Tensor(logits), paddle.Tensor(label),
                               **kw)._value


def counters():
    c = telemetry.default_tracer().metrics.counters
    return (c.get("loss.cross_entropy.grad_in_forward", 0),
            c.get("loss.cross_entropy.autodiff", 0))


def _labels(form, rng):
    y = rng.integers(0, V, size=(N,)).astype(np.int32)
    if form == "some_ignored":
        y[[1, 4, 5]] = IGNORE
    elif form == "all_ignored":
        y[:] = IGNORE
    y = jnp.asarray(y)
    return y[:, None] if form == "trailing_1" else y


# bfloat16 keeps 8 significant bits. Both sides round a float32 product
# to it, the rule twice where the cotangent is not 1 (d, then g * d):
# they agree to one unit in the last place, 2**-7 relative.
GRAD_RTOL = {jnp.float32: 2e-6, jnp.bfloat16: 2.0 ** -7}


@pytest.mark.parametrize("form", ["1d", "trailing_1", "some_ignored",
                                  "all_ignored"])
@pytest.mark.parametrize("reduction", ["mean", "sum", "none"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_value_and_gradient_match_autodiff(dtype, reduction, form):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(N, V)) * 3, dtype)
    y = _labels(form, rng)
    # a cotangent that is not 1, per row for reduction="none"
    g = jnp.asarray(rng.normal(size=(N,) if reduction == "none" else ()),
                    jnp.float32)
    new, d_new = jax.value_and_grad(
        lambda x: jnp.sum(ce(x, y, reduction=reduction) * g))(x)
    old, d_old = jax.value_and_grad(
        lambda x: jnp.sum(autodiff_ce(x, y, reduction) * g))(x)
    np.testing.assert_allclose(new, old, rtol=1e-6, atol=1e-6)
    assert d_new.dtype == dtype
    d_new, d_old = (np.asarray(a, np.float32) for a in (d_new, d_old))
    assert np.all(np.abs(d_new - d_old)
                  <= GRAD_RTOL[dtype] * np.abs(d_old) + 1e-9)
    if form == "all_ignored":
        assert float(jnp.sum(jnp.abs(new))) == 0.0 and not d_new.any()


@pytest.mark.parametrize("reduction", ["mean", "sum", "none"])
def test_jvp_and_forward_over_reverse(reduction):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(N, V)), jnp.float32)
    t = jnp.asarray(rng.normal(size=(N, V)), jnp.float32)
    y = _labels("some_ignored", rng)
    new = lambda x: jnp.sum(ce(x, y, reduction=reduction))     # noqa: E731
    old = lambda x: jnp.sum(autodiff_ce(x, y, reduction))      # noqa: E731
    for a, b in zip(jax.jvp(new, (x,), (t,)), jax.jvp(old, (x,), (t,))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    hvp_new = jax.jvp(jax.grad(new), (x,), (t,))[1]
    hvp_old = jax.jvp(jax.grad(old), (x,), (t,))[1]
    np.testing.assert_allclose(hvp_new, hvp_old, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("reduction", ["mean", "none"])
def test_the_one_large_residual_is_the_gradient_in_the_logits_dtype(
        reduction):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(N, V)), jnp.bfloat16)
    y = _labels("some_ignored", rng)
    _, pullback = jax.vjp(lambda x: ce(x, y, reduction=reduction), x)
    kept = [a for a in jax.tree_util.tree_leaves(pullback)
            if getattr(a, "shape", None) == x.shape]
    assert [a.dtype for a in kept] == [jnp.bfloat16]
    # and it IS the gradient: the pullback only scales it
    g = jnp.ones((N,) if reduction == "none" else (), jnp.float32)
    np.testing.assert_array_equal(np.asarray(pullback(g)[0], np.float32),
                                  np.asarray(kept[0], np.float32))
    # what autodiff keeps, for the contrast the rule exists for
    _, pullback = jax.vjp(lambda x: autodiff_ce(x, y, reduction), x)
    assert jnp.float32 in [a.dtype
                           for a in jax.tree_util.tree_leaves(pullback)
                           if getattr(a, "shape", None) == x.shape]


def _np_logp(x):
    x = x - x.max(-1, keepdims=True)
    return x - np.log(np.exp(x).sum(-1, keepdims=True))


def _soft(x, y, w, p):
    return {"label": p, "soft_label": True}, -(p * _np_logp(x)).sum(-1).mean()


def _smoothing(x, y, w, p):
    logp = _np_logp(x)
    nll = -logp[np.arange(N), y]
    return ({"label": y, "label_smoothing": 0.1},
            (0.9 * nll + 0.1 * -logp.mean(-1)).mean())


def _weight(x, y, w, p):
    nll = -_np_logp(x)[np.arange(N), y]
    return {"label": y, "weight": w}, (nll * w[y]).sum() / w[y].sum()


def _no_softmax(x, y, w, p):
    return ({"input": p, "label": y, "use_softmax": False},
            -np.log(p[np.arange(N), y]).mean())


@pytest.mark.parametrize("case", [_soft, _smoothing, _weight, _no_softmax])
def test_every_other_case_keeps_autodiff_and_its_numbers(case):
    rng = np.random.default_rng(11)
    x = rng.normal(size=(N, V)).astype(np.float32)
    y = rng.integers(0, V, size=(N,)).astype(np.int32)
    w = rng.uniform(0.5, 2.0, size=(V,)).astype(np.float32)
    p = np.exp(_np_logp(rng.normal(size=(N, V)))).astype(np.float32)
    kw, want = case(x, y, w, p)
    kw = {"input": x, **kw}
    kw = {k: paddle.to_tensor(v) if isinstance(v, np.ndarray) else v
          for k, v in kw.items()}
    kw["input"].stop_gradient = False
    before = counters()
    loss = F.cross_entropy(**kw)
    after = counters()
    assert (after[0] - before[0], after[1] - before[1]) == (0, 1)
    np.testing.assert_allclose(float(loss), want, rtol=2e-5)
    loss.backward()
    assert np.isfinite(np.asarray(kw["input"].grad._value)).all()


def test_hard_labels_count_as_grad_in_forward_through_the_tape():
    rng = np.random.default_rng(13)
    x = paddle.to_tensor(rng.normal(size=(N, V)).astype(np.float32),
                         stop_gradient=False)
    y = _labels("some_ignored", rng)
    before = counters()
    loss = F.cross_entropy(x, paddle.Tensor(y))
    after = counters()
    assert (after[0] - before[0], after[1] - before[1]) == (1, 0)
    loss.backward()
    want = jax.grad(lambda a: autodiff_ce(a, y))(x._value)
    np.testing.assert_allclose(x.grad._value, want, rtol=1e-5, atol=1e-8)


# -- the models: labels shifted, logits whole --------------------------------

def sliced_logits_loss(logits, labels):
    """The dense causal loss as the three models had it."""
    v = logits.shape[-1]
    return apply("ce_reference", autodiff_ce,
                 logits[:, :-1, :].reshape([-1, v]),
                 labels[:, 1:].reshape([-1]))


MODELS = {
    "llama": (LlamaForCausalLM, llama_tiny),
    "gpt": (GPTForCausalLM, gpt_tiny),
    "moe": (MoEForCausalLM, moe_tiny),
}


def _model_and_batch(name):
    cls, tiny = MODELS[name]
    paddle.seed(1234)
    model = cls(tiny())
    rng = np.random.RandomState(0)
    ids = rng.randint(0, model.cfg.vocab_size, (2, 16)).astype(np.int32)
    ids[1, 5:8] = IGNORE            # labels a data pipeline masked out
    labels = paddle.to_tensor(ids)
    return model, paddle.to_tensor(np.maximum(ids, 0)), labels


def _eager(name):
    model, ids, labels = _model_and_batch(name)
    loss = model.loss(model(ids), labels)
    loss.backward()
    return float(loss), {k: np.asarray(p.grad._value)
                         for k, p in model.named_parameters()
                         if p.grad is not None}


def _train_step(name):
    """One SGD step at learning rate 1: every parameter moves by its
    gradient."""
    model, ids, labels = _model_and_batch(name)
    start = {k: np.asarray(p._value) for k, p in model.named_parameters()}
    step = paddle.jit.TrainStep(
        model, lambda out, lab: model.loss(out, lab),
        optimizer.SGD(learning_rate=1.0, parameters=model.parameters()))
    loss = float(step(ids, labels))
    return loss, {k: start[k] - np.asarray(p._value)
                  for k, p in model.named_parameters()}


@pytest.mark.parametrize("run", [_eager, _train_step])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_model_loss_equals_the_sliced_logits_form(name, run, monkeypatch):
    loss, grads = run(name)
    # the one place the three models' dense loss is called from
    monkeypatch.setattr("paddle_tpu.models.lm_head.causal_lm_loss",
                        sliced_logits_loss)
    want_loss, want = run(name)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    assert grads.keys() == want.keys() and grads
    for k in want:
        np.testing.assert_allclose(grads[k], want[k], rtol=1e-4, atol=2e-6,
                                   err_msg=k)
