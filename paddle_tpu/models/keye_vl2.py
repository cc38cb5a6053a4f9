"""Keye-VL-2.0-30B-A3B's language model: a decoder whose attention is
*learned sparse attention* and whose every MLP is a gated-expert layer.

Per layer, on ``x = RMSNorm(h)``:

- attention: q [32 heads], k, v [4 heads] of ``head_dim`` (given, not
  ``hidden_size / heads``), RMSNorm over each head of q and of k, rotary
  embedding, then softmax attention over the keys the indexer selected;
- the indexer, on ``stop_gradient(x)``: ``qI`` [16 heads of 64], one key
  head ``kI = RMSNorm(x WkI)``, head weights ``w = x Ww``, rotary
  embedding on both, score ``sum_j w_j relu(qI_j . kI) / sqrt(64 * 16)``;
  each query keeps its ``index_topk`` best causal keys (exact, ties to
  the lower index). Its loss is the KL divergence from the main
  attention's probabilities over the kept keys (heads summed, normalised,
  under ``stop_gradient``) to the softmax of its scores over them: the
  indexer's three matrices and its norm learn from that term alone, and
  nothing else learns from it
  (``ops.pallas.sparse_attention.learned_sparse_attention``: seven
  kernels a layer and step; the backward pass makes the scores again,
  and the kernel of dk and dv gathers the indexer's target from the
  probabilities it makes anyway and ends in the loss's gradient, so a
  step makes the main attention's q.k^T four times);
- experts: ``nn.MoEShareLayer`` - softmax routing over all
  ``num_experts`` in float32, top ``num_experts_per_tok`` renormalised,
  SwiGLU experts, of which this process holds the ``expert_share``.

``forward`` returns (logits, the layers' summed indexer loss);
``loss`` is the mean next-token loss plus that sum (weight 1). There is
no vision tower here and no load-balancing loss: text tokens only, where
the model's three rotary sections carry one position.
"""
from __future__ import annotations

import weakref
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.core import apply
from .lm_head import head_output, make_lm_head, next_token_loss
from ..ops.pallas import sparse_attention as sa
from ..ops.rms_norm import rms_norm
from ..ops.rope import build_rope_cache, rope_reference
from ..utils import telemetry

__all__ = ["KeyeVL2Config", "KeyeVL2ForCausalLM", "KeyeVL2Model",
           "keye_vl2_tiny"]


@dataclass
class KeyeVL2Config:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128               # the router's width
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    # (index, count): this process holds experts index * num_experts /
    # count onward, num_experts / count of them (nn.MoEShareLayer)
    expert_share: Tuple[int, int] = (0, 1)
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    index_topk: int = 2048
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    dtype: str = "float32"
    use_recompute: bool = False


def _rope(x, theta):
    """Rotary embedding over the whole last dimension of [b, s, h, d],
    in float32."""
    cos, sin = build_rope_cache(x.shape[1], x.shape[-1], theta, jnp.float32)
    return rope_reference(x.astype(jnp.float32), cos, sin).astype(x.dtype)


class KeyeVL2Attention(nn.Layer):
    def __init__(self, cfg: KeyeVL2Config):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
        lin = lambda n_out: nn.Linear(h, n_out, bias_attr=False)
        self.q_proj = lin(cfg.num_attention_heads * d)
        self.k_proj = lin(cfg.num_key_value_heads * d)
        self.v_proj = lin(cfg.num_key_value_heads * d)
        self.o_proj = nn.Linear(cfg.num_attention_heads * d, h,
                                bias_attr=False)
        self.q_norm = nn.RMSNorm(d, cfg.rms_norm_eps, dtype=cfg.dtype)
        self.k_norm = nn.RMSNorm(d, cfg.rms_norm_eps, dtype=cfg.dtype)
        self.indexer_q_proj = lin(hi * di)
        self.indexer_k_proj = lin(di)
        self.indexer_weights_proj = lin(hi)
        self.indexer_k_norm = nn.RMSNorm(di, cfg.rms_norm_eps,
                                         dtype=cfg.dtype)
        self._scope = "layer"

    def indexer_parameters(self):
        return [self.indexer_q_proj.weight, self.indexer_k_proj.weight,
                self.indexer_weights_proj.weight, self.indexer_k_norm.weight]

    def forward(self, x):
        """x [b, s, hidden], already normed -> (attention's output before
        the residual [b, s, hidden], the indexer's loss)."""
        cfg = self.cfg
        eps, theta = cfg.rms_norm_eps, cfg.rope_theta
        nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
        scope = self._scope
        telemetry.default_tracer().metrics.inc("attn.sparse.kernel")

        # the norm and the rotary embedding work in float32: made again
        # in the backward pass from the projection's output, which is
        # kept in its own dtype
        @jax.checkpoint
        def norm_rope(a, gain):
            return jnp.swapaxes(_rope(rms_norm(a, gain, eps), theta), 1, 2)

        def f(xa, wq, wk, wv, wo, qn, kn, iwq, iwk, iww, ikn):
            b, s, _ = xa.shape
            with jax.named_scope(f"{scope}/attn"):
                q = norm_rope((xa @ wq).reshape(b, s, nh, d), qn)
                k = norm_rope((xa @ wk).reshape(b, s, nkv, d), kn)
                v = jnp.swapaxes((xa @ wv).reshape(b, s, nkv, d), 1, 2)
            with jax.named_scope(f"{scope}/indexer"):
                xs = jax.lax.stop_gradient(xa)
                qi = jnp.swapaxes(
                    _rope((xs @ iwq).reshape(b, s, hi, di), theta), 1, 2)
                ki = _rope(rms_norm(xs @ iwk, ikn, eps)[:, :, None],
                           theta)[:, :, 0]
                w = (xs @ iww).astype(jnp.float32) * (di ** -0.5 * hi ** -0.5)
            with jax.named_scope(scope):
                o, li = sa.learned_sparse_attention(
                    q, k, v, qi, ki, w, cfg.index_topk, d ** -0.5)
            with jax.named_scope(f"{scope}/attn"):
                o = jnp.swapaxes(o, 1, 2).reshape(b, s, nh * d)
                return o @ wo, li

        return apply(
            "keye_attention", f, x, self.q_proj.weight, self.k_proj.weight,
            self.v_proj.weight, self.o_proj.weight, self.q_norm.weight,
            self.k_norm.weight, *self.indexer_parameters())


class KeyeVL2DecoderLayer(nn.Layer):
    def __init__(self, cfg: KeyeVL2Config):
        super().__init__(dtype=cfg.dtype)
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                          dtype=cfg.dtype)
        self.self_attn = KeyeVL2Attention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(
            cfg.hidden_size, cfg.rms_norm_eps, dtype=cfg.dtype)
        self.mlp = nn.MoEShareLayer(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, share=cfg.expert_share,
            norm_topk_prob=cfg.norm_topk_prob, dtype=cfg.dtype)
        self.use_recompute = cfg.use_recompute
        self._scope = "layer"

    def _block(self, x):
        """(the layer's output, its indexer loss, the rows its experts
        computed): everything a rematerialised region may hand out."""
        attn, li = self.self_attn(self.input_layernorm(x))
        h = x + attn
        with jax.named_scope(f"{self._scope}/moe"):
            y, seen = self.mlp.compute(self.post_attention_layernorm(h))
            return h + y, li, seen

    def forward(self, x):
        if self.use_recompute:
            from ..distributed.fleet import recompute
            from .llama import _LayerFn
            h, li, seen = recompute(_LayerFn(self), x)
        else:
            h, li, seen = self._block(x)
        self.mlp.count(seen)
        return h, li


class KeyeVL2Model(nn.Layer):
    def __init__(self, cfg: KeyeVL2Config):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [KeyeVL2DecoderLayer(cfg) for _ in range(cfg.num_hidden_layers)])
        for i, layer in enumerate(self.layers):
            layer._scope = layer.self_attn._scope = f"layer{i}"
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                               dtype=cfg.dtype)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
            if self.cfg.dtype != "float32":
                h = h.astype(self.cfg.dtype)
        aux = None
        for layer in self.layers:
            h, li = layer(h)
            aux = li if aux is None else aux + li
        with jax.named_scope("final_norm"):
            return self.norm(h), aux


class KeyeVL2ForCausalLM(nn.Layer):
    def __init__(self, cfg: KeyeVL2Config):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.model = KeyeVL2Model(cfg)
        self.lm_head = make_lm_head(cfg.hidden_size, cfg.vocab_size)
        # the default registry's snapshot() asks for the experts' counters
        # (moe.rows_held, moe.rows_max_expert, moe.rows_walked,
        # moe.rows_routed)
        telemetry.default_tracer().metrics.add_source(
            "moe", weakref.WeakMethod(self.routing_counts))

    def forward(self, input_ids):
        h, aux = self.model(input_ids)
        return head_output(h, self.lm_head, None), aux

    def loss(self, out, labels):
        """Mean next-token cross entropy plus the layers' indexer losses."""
        logits, aux = out
        return next_token_loss(logits, labels, self.lm_head, None) \
            + aux.astype("float32")

    def indexer_parameters(self):
        return [p for layer in self.model.layers
                for p in layer.self_attn.indexer_parameters()]

    def routing_counts(self) -> dict:
        """The layers' expert counters summed (``rows_max_expert``: the
        busiest single expert of any layer)."""
        return nn.MoEShareLayer.summed_counts(
            layer.mlp for layer in self.model.layers)

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())


def keye_vl2_tiny(**kw) -> KeyeVL2Config:
    """Small enough for the CPU, with every mechanism biting: 8 experts
    of which 2 are held, top-2, 16 of 64 keys selected."""
    base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                moe_intermediate_size=48, num_experts=8,
                num_experts_per_tok=2, expert_share=(0, 4),
                indexer_num_heads=4, indexer_head_dim=16, index_topk=16)
    return KeyeVL2Config(**dict(base, **kw))
