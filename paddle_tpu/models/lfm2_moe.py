"""LFM2-8B-A1B (``model_type`` ``lfm2_moe``): a decoder whose layers
are of mixed kinds. Layer ``i`` takes its operator from
``layer_types[i]`` and its feed-forward from ``i < num_dense_layers``:

    h += Op(RMSNorm(h));  h += FFN(RMSNorm(h))

- ``conv``: the gated short convolution (``nn.GatedShortConv``:
  ``[B, C, X] = x W_in``, a depthwise causal convolution of
  ``conv_L_cache`` taps over ``B * X``, gated by ``C``, ``W_out``; no
  biases);
- ``full_attention``: grouped-query attention with heads of
  ``hidden_size / num_attention_heads`` (64), no biases, an RMSNorm with
  a learned gain over each head of q and of k, then the rotary embedding
  over the whole head (half-split pairs), causal, scale ``head ** -0.5``,
  through ``ops.flash_attention.flash_attention`` (the Pallas kernels on
  the TPU);
- the first ``num_dense_layers`` feed-forwards are a SwiGLU MLP of
  ``intermediate_size``, ``w2(silu(w1 x) * w3 x)``; the others
  ``nn.MoEShareLayer`` under the sigmoid rule: ``s = sigmoid(logits)`` in
  float32, the ``num_experts_per_tok`` largest of ``s + expert_bias``
  chosen, weights ``s`` there over their sum + 1e-6
  (``norm_topk_prob``) times ``routed_scaling_factor``; no shared
  expert; this process holds the ``expert_share``.

A last RMSNorm, and the logits are ``h E^T`` with the embedding tied.
``loss`` is the mean next-token loss: the config gives no coefficient
for an auxiliary one. ``expert_bias`` starts at zero and nothing here
writes it: its update rule is no key of the config.
"""
from __future__ import annotations

import weakref
from dataclasses import dataclass
from typing import Tuple

import jax

from .. import nn
from ..framework.core import apply
from ..nn import functional as F
from ..ops.flash_attention import flash_attention
from ..ops.rms_norm import rms_norm
from ..utils import telemetry
from .keye_vl2 import _rope
from .lm_head import head_output, make_lm_head, next_token_loss

__all__ = ["Lfm2MoeConfig", "Lfm2MoeForCausalLM", "Lfm2MoeModel",
           "lfm2_moe_tiny"]

LAYER_KINDS = ("conv", "full_attention")
# LFM2-8B-A1B's 24 layers: 18 convolutions, 6 attentions
_PUBLISHED_LAYERS = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168        # the leading dense MLPs' width
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3                # the convolution's taps
    num_experts: int = 32                # the router's width
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    # (index, count): this process holds experts index * num_experts /
    # count onward, num_experts / count of them (nn.MoEShareLayer)
    expert_share: Tuple[int, int] = (0, 1)
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    dtype: str = "float32"
    use_recompute: bool = False

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.layer_types)} layer_types for "
                f"{self.num_hidden_layers} layers")
        unknown = sorted(set(self.layer_types) - set(LAYER_KINDS))
        if unknown:
            raise ValueError(f"layer kinds {unknown}; there are: "
                             f"{', '.join(LAYER_KINDS)}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size is not a multiple of the heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


class Lfm2MoeAttention(nn.Layer):
    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        lin = lambda n_in, n_out: nn.Linear(n_in, n_out, bias_attr=False)
        self.q_proj = lin(h, cfg.num_attention_heads * d)
        self.k_proj = lin(h, cfg.num_key_value_heads * d)
        self.v_proj = lin(h, cfg.num_key_value_heads * d)
        self.out_proj = lin(cfg.num_attention_heads * d, h)
        self.q_layernorm = nn.RMSNorm(d, cfg.norm_eps, dtype=cfg.dtype)
        self.k_layernorm = nn.RMSNorm(d, cfg.norm_eps, dtype=cfg.dtype)

    def forward(self, x):
        """x [b, s, hidden], already normed -> [b, s, hidden]."""
        cfg = self.cfg
        nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        eps, theta = cfg.norm_eps, cfg.rope_theta
        telemetry.default_tracer().metrics.set_gauge("attn.flash.head_dim", d)

        # the norm and the rotary embedding work in float32: made again
        # in the backward pass from the projection's output, which is
        # kept in its own dtype
        @jax.checkpoint
        def norm_rope(a, gain):
            return _rope(rms_norm(a, gain, eps), theta)

        def f(xa, wq, wk, wv, wo, qn, kn):
            b, s, _ = xa.shape
            q = norm_rope((xa @ wq).reshape(b, s, nh, d), qn)
            k = norm_rope((xa @ wk).reshape(b, s, nkv, d), kn)
            v = (xa @ wv).reshape(b, s, nkv, d)
            o = flash_attention(q, k, v, causal=True, scale=d ** -0.5)
            return o.reshape(b, s, nh * d) @ wo

        return apply(
            "lfm2_attention", f, x, self.q_proj.weight, self.k_proj.weight,
            self.v_proj.weight, self.out_proj.weight,
            self.q_layernorm.weight, self.k_layernorm.weight)


class Lfm2MoeMLP(nn.Layer):
    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__(dtype=cfg.dtype)
        lin = lambda n_in, n_out: nn.Linear(n_in, n_out, bias_attr=False)
        self.w1 = lin(cfg.hidden_size, cfg.intermediate_size)
        self.w3 = lin(cfg.hidden_size, cfg.intermediate_size)
        self.w2 = lin(cfg.intermediate_size, cfg.hidden_size)

    def forward(self, x):
        return self.w2(F.silu(self.w1(x)) * self.w3(x))


class Lfm2MoeDecoderLayer(nn.Layer):
    def __init__(self, cfg: Lfm2MoeConfig, index: int):
        super().__init__(dtype=cfg.dtype)
        self.operator_norm = nn.RMSNorm(cfg.hidden_size, cfg.norm_eps,
                                        dtype=cfg.dtype)
        self.is_attention = cfg.layer_types[index] == "full_attention"
        if self.is_attention:
            self.self_attn = Lfm2MoeAttention(cfg)
        else:
            self.conv = nn.GatedShortConv(cfg.hidden_size, cfg.conv_L_cache,
                                          dtype=cfg.dtype)
        self.ffn_norm = nn.RMSNorm(cfg.hidden_size, cfg.norm_eps,
                                   dtype=cfg.dtype)
        self.is_dense = index < cfg.num_dense_layers
        if self.is_dense:
            self.feed_forward = Lfm2MoeMLP(cfg)
        else:
            self.feed_forward = nn.MoEShareLayer(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, share=cfg.expert_share,
                norm_topk_prob=cfg.norm_topk_prob, dtype=cfg.dtype,
                score_func="sigmoid", expert_bias=cfg.use_expert_bias,
                routed_scaling_factor=cfg.routed_scaling_factor)
        self.use_recompute = cfg.use_recompute
        # the named scopes a device trace groups this layer's time by
        self._op_scope = f"layer{index}/" + (
            "attn" if self.is_attention else "short_conv")
        self._ffn_scope = f"layer{index}/" + (
            "mlp" if self.is_dense else "moe")

    def _block(self, x):
        """The layer's output; with experts, also the rows they computed
        (everything a rematerialised region may hand out)."""
        with jax.named_scope(self._op_scope):
            op = self.self_attn if self.is_attention else self.conv
            h = x + op(self.operator_norm(x))
        with jax.named_scope(self._ffn_scope):
            if self.is_dense:
                return h + self.feed_forward(self.ffn_norm(h))
            y, seen = self.feed_forward.compute(self.ffn_norm(h))
            return h + y, seen

    def forward(self, x):
        if self.use_recompute:
            from ..distributed.fleet import recompute
            from .llama import _LayerFn
            out = recompute(_LayerFn(self), x)
        else:
            out = self._block(x)
        if self.is_dense:
            return out
        h, seen = out
        self.feed_forward.count(seen)
        return h


class Lfm2MoeModel(nn.Layer):
    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [Lfm2MoeDecoderLayer(cfg, i)
             for i in range(cfg.num_hidden_layers)])
        self.embedding_norm = nn.RMSNorm(cfg.hidden_size, cfg.norm_eps,
                                         dtype=cfg.dtype)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
            if self.cfg.dtype != "float32":
                h = h.astype(self.cfg.dtype)
        for layer in self.layers:
            h = layer(h)
        with jax.named_scope("final_norm"):
            return self.embedding_norm(h)


class Lfm2MoeForCausalLM(nn.Layer):
    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.model = Lfm2MoeModel(cfg)
        self.lm_head = make_lm_head(cfg.hidden_size, cfg.vocab_size,
                                    tied=True)
        # the default registry's snapshot() asks for the experts' counters
        # (moe.rows_held, moe.rows_max_expert, moe.rows_walked,
        # moe.rows_routed)
        telemetry.default_tracer().metrics.add_source(
            "moe", weakref.WeakMethod(self.routing_counts))

    def forward(self, input_ids):
        return head_output(self.model(input_ids), self.lm_head,
                           self.model.embed_tokens)

    def loss(self, logits, labels):
        """Mean next-token cross entropy."""
        return next_token_loss(logits, labels, self.lm_head,
                               self.model.embed_tokens)

    def routing_counts(self) -> dict:
        """The expert layers' counters summed (``rows_max_expert``: the
        busiest single expert of any layer)."""
        return nn.MoEShareLayer.summed_counts(
            layer.feed_forward for layer in self.model.layers
            if not layer.is_dense)


def lfm2_moe_tiny(**kw) -> Lfm2MoeConfig:
    """Small enough for the CPU, with all four kinds of layer: a
    convolution and an attention with a dense MLP, then a convolution and
    an attention with 8 experts each, of which 2 are held, top-2."""
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=48, num_hidden_layers=4,
                layer_types=("conv", "full_attention", "conv",
                             "full_attention"),
                num_dense_layers=2, num_attention_heads=4,
                num_key_value_heads=2, num_experts=8,
                num_experts_per_tok=2, expert_share=(0, 4))
    return Lfm2MoeConfig(**dict(base, **kw))
