"""GPT / ERNIE-style decoder-only transformer.

Capability parity target: the ERNIE/GPT stacks trained on the reference
framework (PaddleNLP GPT-3 / ERNIE 4.5 recipes; framework side:
fleet hybrid parallel + fused attention ops per SURVEY.md §2.3). Differs
from the Llama family: learned absolute position embeddings, pre-LN
LayerNorm (not RMSNorm), GELU MLP with biases, no rotary.

Follows the same TP wiring as models/llama.py: Column/RowParallelLinear
and VocabParallelEmbedding activate when a fleet mesh with mp>1 is live.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from ..framework.core import apply
from .. import nn
from ..nn import functional as F
from .lm_head import head_output, make_lm_head, next_token_loss

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "gpt_tiny",
           "gpt_345m"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 1024
    intermediate_size: int = 4096
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    max_position_embeddings: int = 1024
    layer_norm_epsilon: float = 1e-5
    attention_dropout: float = 0.0
    hidden_dropout: float = 0.0
    tie_word_embeddings: bool = True
    dtype: str = "float32"
    use_recompute: bool = False
    # 'full' | 'full_attn' | 'core_attn' (see LlamaConfig)
    recompute_granularity: str = "full"
    tensor_parallel: bool = False
    # >0: forward() returns hidden states; loss() runs the chunked
    # head-matmul + CE (see nn.functional.chunked_softmax_cross_entropy)
    chunked_ce_tokens: int = 0


def _mp_active() -> bool:
    from ..distributed.fleet import get_hybrid_communicate_group
    hcg = get_hybrid_communicate_group()
    return hcg is not None and hcg.get_model_parallel_world_size() > 1


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__(dtype=cfg.dtype)
        self.num_heads = cfg.num_attention_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.dropout = cfg.attention_dropout
        self._tp = cfg.tensor_parallel and _mp_active()
        if self._tp:
            from ..distributed.fleet import (ColumnParallelLinear,
                                             RowParallelLinear)
            self.qkv_proj = ColumnParallelLinear(
                cfg.hidden_size, 3 * cfg.hidden_size, has_bias=True,
                gather_output=False)
            self.out_proj = RowParallelLinear(
                cfg.hidden_size, cfg.hidden_size, has_bias=True,
                input_is_parallel=True)
        else:
            self.qkv_proj = nn.Linear(cfg.hidden_size, 3 * cfg.hidden_size)
            self.out_proj = nn.Linear(cfg.hidden_size, cfg.hidden_size)

    def forward(self, x):
        b, s = x.shape[0], x.shape[1]
        qkv = self.qkv_proj(x).reshape([b, s, 3, self.num_heads,
                                        self.head_dim])
        if self._tp:
            from ..distributed.fleet.mpu import _constrain, _get_mesh
            qkv = _constrain(qkv, _get_mesh(),
                             [None, None, None, "mp", None])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        if self._tp:
            from ..distributed.fleet.mpu import _constrain, _get_mesh
            out = _constrain(out, _get_mesh(), [None, None, "mp"])
        return self.out_proj(out)


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__(dtype=cfg.dtype)
        if cfg.tensor_parallel and _mp_active():
            from ..distributed.fleet import (ColumnParallelLinear,
                                             RowParallelLinear)
            self.fc_in = ColumnParallelLinear(
                cfg.hidden_size, cfg.intermediate_size, has_bias=True,
                gather_output=False)
            self.fc_out = RowParallelLinear(
                cfg.intermediate_size, cfg.hidden_size, has_bias=True,
                input_is_parallel=True)
        else:
            self.fc_in = nn.Linear(cfg.hidden_size, cfg.intermediate_size)
            self.fc_out = nn.Linear(cfg.intermediate_size, cfg.hidden_size)

    def forward(self, x):
        return self.fc_out(F.gelu(self.fc_in(x)))


class GPTDecoderLayer(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__(dtype=cfg.dtype)
        self.ln_1 = nn.LayerNorm(cfg.hidden_size,
                                 epsilon=cfg.layer_norm_epsilon)
        self.attn = GPTAttention(cfg)
        self.ln_2 = nn.LayerNorm(cfg.hidden_size,
                                 epsilon=cfg.layer_norm_epsilon)
        self.mlp = GPTMLP(cfg)
        self.dropout = cfg.hidden_dropout
        self.use_recompute = cfg.use_recompute
        self.recompute_granularity = cfg.recompute_granularity

    def _block(self, x):
        h = self.attn(self.ln_1(x))
        if self.dropout:
            h = F.dropout(h, p=self.dropout, training=self.training)
        x = x + h
        h = self.mlp(self.ln_2(x))
        if self.dropout:
            h = F.dropout(h, p=self.dropout, training=self.training)
        return x + h

    def _attn_sub(self, x):
        h = self.attn(self.ln_1(x))
        if self.dropout:
            h = F.dropout(h, p=self.dropout, training=self.training)
        return h

    def _mlp_sub(self, x):
        h = self.mlp(self.ln_2(x))
        if self.dropout:
            h = F.dropout(h, p=self.dropout, training=self.training)
        return h

    def forward(self, x):
        if self.use_recompute:
            from ..distributed.fleet import recompute
            from ..distributed.fleet.recompute import _SubFn
            from .llama import _LayerFn
            gran = self.recompute_granularity
            if gran == "full":
                return recompute(_LayerFn(self), x)
            if gran == "full_attn":
                h = x + recompute(
                    _SubFn(self, "_attn_sub",
                           (self.ln_1, self.attn)), x)
                return h + self._mlp_sub(h)
            if gran == "core_attn":
                # flash backward recomputes scores/probs internally
                return self._block(x)
            raise ValueError(
                f"unknown recompute_granularity {gran!r}; expected "
                "'full', 'full_attn' or 'core_attn'")
        return self._block(x)


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        if cfg.tensor_parallel and _mp_active():
            from ..distributed.fleet import VocabParallelEmbedding
            self.embed_tokens = VocabParallelEmbedding(cfg.vocab_size,
                                                       cfg.hidden_size)
        else:
            self.embed_tokens = nn.Embedding(cfg.vocab_size,
                                             cfg.hidden_size)
        self.embed_positions = nn.Embedding(cfg.max_position_embeddings,
                                            cfg.hidden_size)
        self.layers = nn.LayerList(
            [GPTDecoderLayer(cfg) for _ in range(cfg.num_hidden_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size,
                                 epsilon=cfg.layer_norm_epsilon)

    def forward(self, input_ids):
        s = input_ids.shape[1]
        pos = apply("position_ids",
                    lambda ids: jnp.broadcast_to(
                        jnp.arange(ids.shape[1]), ids.shape), input_ids)
        h = self.embed_tokens(input_ids) + self.embed_positions(pos)
        if self.cfg.dtype != "float32":
            h = h.astype(self.cfg.dtype)
        for layer in self.layers:
            h = layer(h)
        return self.ln_f(h)


class GPTForCausalLM(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        self.lm_head = make_lm_head(cfg.hidden_size, cfg.vocab_size,
                                    tied=cfg.tie_word_embeddings)

    def forward(self, input_ids):
        return head_output(self.gpt(input_ids), self.lm_head,
                           self.gpt.embed_tokens, self.cfg.chunked_ce_tokens)

    def loss(self, out, labels):
        return next_token_loss(out, labels, self.lm_head,
                               self.gpt.embed_tokens,
                               self.cfg.chunked_ce_tokens)

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())


def gpt_tiny(**kw) -> GPTConfig:
    return GPTConfig(vocab_size=512, hidden_size=128,
                     intermediate_size=512, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=256,
                     **kw)


def gpt_345m(**kw) -> GPTConfig:
    return GPTConfig(vocab_size=50304, hidden_size=1024,
                     intermediate_size=4096, num_hidden_layers=24,
                     num_attention_heads=16,
                     max_position_embeddings=1024, **kw)

