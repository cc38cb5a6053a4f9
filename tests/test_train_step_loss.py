"""jit.TrainStep runs the loss inside the forward pass's parameter swap.

A loss that reads a weight of the model (the chunked head-and-loss of
``models/lm_head.py`` reads the head; a regulariser reads whatever it
likes) reads the traced array, so the weight gets its gradient. Before,
the swap was over when the loss ran: the loss read the live array, a
constant of the trace, and with ``chunked_ce_tokens > 0`` a step moved
every weight but the head. Eager training never had the fault (autograd
sees the weight), which is why tests/test_models.py could not find it.

Every case takes one SGD step at lr 1 in float32, so a weight's change
is its gradient.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaForCausalLM,
                               MoEForCausalLM, llama_tiny, moe_tiny)

CHUNK = 16
GPT = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
           num_hidden_layers=1, num_attention_heads=2,
           max_position_embeddings=64)

# name -> (builder taking the config's extra fields, vocabulary, the
# parameter that is the head)
MODELS = {
    "llama_untied": (lambda **kw: LlamaForCausalLM(
        llama_tiny(num_hidden_layers=1, **kw)), 512, "lm_head.weight"),
    "llama_tied": (lambda **kw: LlamaForCausalLM(
        llama_tiny(num_hidden_layers=1, tie_word_embeddings=True, **kw)),
        512, "model.embed_tokens.weight"),
    "gpt_tied": (lambda **kw: GPTForCausalLM(GPTConfig(**GPT, **kw)),
                 128, "gpt.embed_tokens.weight"),
    "moe": (lambda **kw: MoEForCausalLM(moe_tiny(**kw)), 512,
            "lm_head.weight"),
}


def _build(name, **kw):
    paddle.seed(11)
    return MODELS[name][0](**kw)


def _ids(vocab, seq=21, seed=0):
    # 2 x 20 shifted positions: not a multiple of the chunk, so the last
    # chunk is padded
    ids = np.random.default_rng(seed).integers(0, vocab, size=(2, seq))
    return ids.astype(np.int32)


def _values(model):
    return {n: np.array(p.numpy()) for n, p in model.named_parameters()}


def _train(model, ids, labels=None, steps=1, loss_fn=None, **kw):
    """``steps`` calls of a TrainStep over SGD at lr 1; returns (the last
    loss, every parameter's change)."""
    opt = optimizer.SGD(learning_rate=1.0, parameters=model.parameters())
    step = paddle.jit.TrainStep(
        model, loss_fn or (lambda out, lab: model.loss(out, lab)), opt, **kw)
    before = _values(model)
    x = paddle.to_tensor(ids)
    y = x if labels is None else paddle.to_tensor(labels)
    for _ in range(steps):
        loss = step(x, y)
    after = _values(model)
    return float(loss.numpy()), {n: after[n] - before[n] for n in before}


def _assert_same_change(dense, chunked, head, atol=2e-5):
    assert np.abs(dense[head]).max() > 1e-3      # the dense head trains
    for name in dense:
        np.testing.assert_allclose(chunked[name], dense[name], atol=atol,
                                   rtol=1e-3, err_msg=name)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_chunked_loss_trains_the_head_as_the_dense_loss_does(name):
    _, vocab, head = MODELS[name]
    ids = _ids(vocab)
    l_d, d_d = _train(_build(name), ids)
    l_c, d_c = _train(_build(name, chunked_ce_tokens=CHUNK), ids)
    assert l_c == pytest.approx(l_d, rel=1e-5)
    _assert_same_change(d_d, d_c, head)


def test_chunked_loss_through_gradient_merge():
    """Both gradient-merge programs share the closure: two micro-steps,
    the second applies the averaged gradient."""
    ids = _ids(512)
    _, d_d = _train(_build("llama_untied"), ids, steps=2, gradient_merge=2)
    _, d_c = _train(_build("llama_untied", chunked_ce_tokens=CHUNK), ids,
                    steps=2, gradient_merge=2)
    _assert_same_change(d_d, d_c, "lm_head.weight")


def test_loss_fn_reading_a_parameter_gets_its_gradient():
    """An L2 term on one weight, written in ``loss_fn`` against the live
    model: its gradient is the weight, so at lr 1 the step with the term
    differs from the step without it by minus the weight."""
    ids = _ids(512)
    plain = _build("llama_untied")
    w0 = np.array(plain.model.norm.weight.numpy())
    _, d_plain = _train(plain, ids)

    model = _build("llama_untied")

    def with_l2(out, lab):
        w = model.model.norm.weight
        return model.loss(out, lab) + 0.5 * (w * w).sum()

    _, d_l2 = _train(model, ids, loss_fn=with_l2)
    np.testing.assert_allclose(
        d_l2["model.norm.weight"] - d_plain["model.norm.weight"], -w0,
        atol=1e-5)
    np.testing.assert_allclose(d_l2["lm_head.weight"],
                               d_plain["lm_head.weight"], atol=1e-6)


def test_frozen_head_under_the_chunked_loss_stays():
    model = _build("llama_untied", chunked_ce_tokens=CHUNK)
    model.lm_head.weight.stop_gradient = True
    _, delta = _train(model, _ids(512))
    assert not delta["lm_head.weight"].any()
    assert np.abs(delta["model.embed_tokens.weight"]).max() > 1e-4


def test_ignore_index_through_the_chunked_loss():
    ids = _ids(512)
    labels = ids.copy()
    labels[0, -7:] = -100
    labels[1, 3] = -100
    l_d, d_d = _train(_build("llama_untied"), ids, labels)
    l_c, d_c = _train(_build("llama_untied", chunked_ce_tokens=CHUNK), ids,
                      labels)
    assert l_c == pytest.approx(l_d, rel=1e-5)
    _assert_same_change(d_d, d_c, "lm_head.weight")
    # and the ignored positions do count for nothing: all labels gives
    # another loss
    l_all, _ = _train(_build("llama_untied", chunked_ce_tokens=CHUNK), ids)
    assert abs(l_all - l_c) > 1e-4
