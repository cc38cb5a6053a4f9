"""The decoder models' shared head and next-token loss
(``paddle_tpu/models/lm_head.py``) against a plain reference, and the
package's exports."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.models import (GPTForCausalLM, KeyeVL2ForCausalLM,
                               LlamaForCausalLM, MoEForCausalLM, gpt_tiny,
                               keye_vl2_tiny, llama_tiny, moe_tiny)


def _own_term(model, out):
    """What a model's ``loss`` adds to the next-token loss."""
    if isinstance(model, MoEForCausalLM):
        return model.cfg.aux_loss_weight * sum(
            float(a.numpy()) for a in model.model.aux_losses())
    if isinstance(model, KeyeVL2ForCausalLM):
        return float(out[1].numpy())
    return 0.0


@pytest.mark.parametrize("build,vocab", [
    (lambda: GPTForCausalLM(gpt_tiny()), 512),
    (lambda: GPTForCausalLM(gpt_tiny(tie_word_embeddings=False)), 512),
    (lambda: LlamaForCausalLM(llama_tiny()), 512),
    (lambda: LlamaForCausalLM(llama_tiny(tie_word_embeddings=True)), 512),
    (lambda: MoEForCausalLM(moe_tiny()), 512),
    (lambda: KeyeVL2ForCausalLM(keye_vl2_tiny()), 128),
], ids=["gpt_tied", "gpt_untied", "llama_untied", "llama_tied", "moe",
        "keye_vl2"])
def test_loss_is_the_plain_next_token_loss_plus_the_models_own_term(
        build, vocab):
    paddle.seed(3)
    model = build()
    ids = np.random.default_rng(3).integers(0, vocab, size=(2, 24)) \
        .astype(np.int32)
    x = paddle.to_tensor(ids)
    out = model(x)
    logits = out[0] if isinstance(out, tuple) else out
    assert tuple(logits.shape) == (2, 24, vocab)
    logp = jax.nn.log_softmax(
        jnp.asarray(logits.numpy(), jnp.float32)[:, :-1], axis=-1)
    plain = -float(jnp.take_along_axis(
        logp, jnp.asarray(ids)[:, 1:, None], axis=-1).mean())
    got = float(model.loss(out, x).numpy())
    assert got == pytest.approx(plain + _own_term(model, out), rel=1e-5)


def test_every_exported_name_resolves():
    """Each model module's ``__all__`` is there, and the package hands
    every one of those names on."""
    for name in ("llama", "gpt", "moe_lm", "keye_vl2", "dit", "bert"):
        module = importlib.import_module(f"paddle_tpu.models.{name}")
        for public in module.__all__:
            assert getattr(module, public) is getattr(models, public)
