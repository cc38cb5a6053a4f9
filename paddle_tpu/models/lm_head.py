"""The head and the next-token loss of the decoder models
(``GPTForCausalLM``, ``LlamaForCausalLM``, ``MoEForCausalLM``,
``KeyeVL2ForCausalLM``).

A model builds its head with ``make_lm_head`` and keeps it as
``self.lm_head`` (``None``: the head is the token embedding, transposed);
its ``forward`` ends in ``head_output`` and its ``loss`` starts from
``next_token_loss``, to which it adds its own terms. With
``chunk_tokens > 0`` the logits are never whole: ``head_output`` hands the
hidden states on and ``next_token_loss`` makes the head's matmul a chunk
of tokens at a time. The weight is read from the layer when the loss
runs, so under ``jit.TrainStep``, which runs the loss inside the forward
pass's parameter swap, it is the traced array.
"""
from __future__ import annotations

import jax

from .. import nn
from ..nn.functional.loss import (causal_lm_loss,
                                  chunked_softmax_cross_entropy)
from ..tensor.linalg import matmul

__all__ = ["make_lm_head", "head_output", "next_token_loss"]


def make_lm_head(hidden_size: int, vocab_size: int, tied: bool = False,
                 tensor_parallel: bool = False):
    """The output projection [hidden, vocab] without bias; ``None`` when
    ``tied``; split over the vocabulary and gathered when
    ``tensor_parallel``."""
    if tied:
        return None
    if tensor_parallel:
        from ..distributed.fleet import ColumnParallelLinear
        return ColumnParallelLinear(hidden_size, vocab_size, has_bias=False,
                                    gather_output=True)
    return nn.Linear(hidden_size, vocab_size, bias_attr=False)


def head_output(h, lm_head, embed_tokens, chunk_tokens: int = 0):
    """What ``forward`` returns for hidden states ``h``: the logits, or
    ``h`` itself where the loss is chunked."""
    if chunk_tokens:
        return h
    with jax.named_scope("lm_head"):
        if lm_head is None:
            return matmul(h, embed_tokens.weight, transpose_y=True)
        return lm_head(h)


def next_token_loss(out, labels, lm_head, embed_tokens,
                    chunk_tokens: int = 0):
    """Mean cross entropy of position t's prediction against
    ``labels[:, t + 1]`` over ``out = head_output(...)``. Dense: the labels
    are shifted and the logits taken whole (``causal_lm_loss``). Chunked:
    head matmul and cross entropy ``chunk_tokens`` tokens at a time under
    ``jax.checkpoint``; the backward pass makes one chunk's logits again
    at a time."""
    with jax.named_scope("loss"):
        if not chunk_tokens:
            return causal_lm_loss(out, labels)
        tied = lm_head is None      # then the weight is [vocab, hidden]
        return chunked_softmax_cross_entropy(
            out, labels, (embed_tokens if tied else lm_head).weight,
            int(chunk_tokens), transpose_weight=tied)
