"""SmallThinker-21BA3B-Instruct: a decoder that mixes full and window
attention, routes before attention and gates its experts with ReLU.

Layer ``i`` on its input ``h`` (the residual stream):

- the router reads ``h`` itself, before the input norm and before
  attention: float32 logits over all ``moe_num_primary_experts``, softmax,
  the ``moe_num_active_primary_experts`` largest, divided by their sum
  (``norm_topk_prob``): the softmax rule of ``nn.MoEShareLayer``, given
  ``h`` as its ``router_input``;
- attention on ``RMSNorm(h)``: grouped-query attention with heads of
  ``head_dim`` (given, not ``hidden_size / heads``), no biases and no
  head norms. Where ``rope_layout[i]`` is 1 the rotary embedding over the
  whole head (half-split pairs), else no position embedding at all;
  where ``sliding_window_layout[i]`` is 1 a query sees the last
  ``sliding_window_size`` keys, its own counted, else every earlier key:
  ``ops.flash_attention.flash_attention(window=...)``, the Pallas
  kernels on the TPU. The layer's window comes from the layout and from
  nothing else;
- experts on ``RMSNorm(h + attention)``: ``w_down(relu(w_gate u) *
  w_up u)`` ("sparse ReGLU"), weighted by the routing made from ``h``;
  this process holds the ``expert_share``.

A last RMSNorm and an untied head. ``loss`` is the mean next-token loss:
the config gives no coefficient for an auxiliary one. The family's
"secondary" experts are no key of the config and nothing here builds
them.
"""
from __future__ import annotations

import weakref
from dataclasses import dataclass
from typing import Tuple

import jax

from .. import nn
from ..framework.core import apply
from ..ops.flash_attention import flash_attention
from ..utils import telemetry
from .keye_vl2 import _rope
from .lm_head import head_output, make_lm_head, next_token_loss

__all__ = ["SmallThinkerConfig", "SmallThinkerForCausalLM",
           "SmallThinkerModel", "smallthinker_tiny"]

# one global layer without position embedding, then three rotary layers
# under the window: 13 periods
_PUBLISHED_LAYOUT = (0, 1, 1, 1) * 13


@dataclass
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64    # the router's width
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    # (index, count): this process holds experts index *
    # moe_num_primary_experts / count onward (nn.MoEShareLayer)
    expert_share: Tuple[int, int] = (0, 1)
    sliding_window_size: int = 4096
    sliding_window_layout: Tuple[int, ...] = _PUBLISHED_LAYOUT
    rope_layout: Tuple[int, ...] = _PUBLISHED_LAYOUT
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    dtype: str = "float32"
    use_recompute: bool = False

    def __post_init__(self):
        self.sliding_window_layout = tuple(self.sliding_window_layout)
        self.rope_layout = tuple(self.rope_layout)
        for name in ("sliding_window_layout", "rope_layout"):
            layout = getattr(self, name)
            if len(layout) != self.num_hidden_layers \
                    or set(layout) - {0, 1}:
                raise ValueError(
                    f"{name} has to give 0 or 1 for each of the "
                    f"{self.num_hidden_layers} layers; got {layout}")
        if not self.moe_primary_router_apply_softmax:
            raise ValueError("the router's rule here is the softmax's")

    def window(self, i: int):
        """Layer ``i``'s window in keys, or None where it sees them all."""
        return self.sliding_window_size if self.sliding_window_layout[i] \
            else None


class SmallThinkerAttention(nn.Layer):
    def __init__(self, cfg: SmallThinkerConfig, index: int):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.window = cfg.window(index)
        self.rotary = bool(cfg.rope_layout[index])
        h, d = cfg.hidden_size, cfg.head_dim
        lin = lambda n_in, n_out: nn.Linear(n_in, n_out, bias_attr=False)
        self.q_proj = lin(h, cfg.num_attention_heads * d)
        self.k_proj = lin(h, cfg.num_key_value_heads * d)
        self.v_proj = lin(h, cfg.num_key_value_heads * d)
        self.o_proj = lin(cfg.num_attention_heads * d, h)

    def forward(self, x):
        """x [b, s, hidden], already normed -> [b, s, hidden]."""
        cfg = self.cfg
        nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        theta, window = cfg.rope_theta, self.window
        # float32 inside; made again in the backward pass from the
        # projection's output, which is kept in its own dtype
        place = jax.checkpoint(lambda a: _rope(a, theta)) if self.rotary \
            else (lambda a: a)

        def f(xa, wq, wk, wv, wo):
            b, s, _ = xa.shape
            q = place((xa @ wq).reshape(b, s, nh, d))
            k = place((xa @ wk).reshape(b, s, nkv, d))
            v = (xa @ wv).reshape(b, s, nkv, d)
            o = flash_attention(q, k, v, causal=True, scale=d ** -0.5,
                                window=window)
            return o.reshape(b, s, nh * d) @ wo

        return apply(
            "smallthinker_attention", f, x, self.q_proj.weight,
            self.k_proj.weight, self.v_proj.weight, self.o_proj.weight)


class SmallThinkerDecoderLayer(nn.Layer):
    def __init__(self, cfg: SmallThinkerConfig, index: int):
        super().__init__(dtype=cfg.dtype)
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                          dtype=cfg.dtype)
        self.self_attn = SmallThinkerAttention(cfg, index)
        self.post_attention_layernorm = nn.RMSNorm(
            cfg.hidden_size, cfg.rms_norm_eps, dtype=cfg.dtype)
        self.block_sparse_moe = nn.MoEShareLayer(
            cfg.hidden_size, cfg.moe_ffn_hidden_size,
            cfg.moe_num_primary_experts, cfg.moe_num_active_primary_experts,
            share=cfg.expert_share, norm_topk_prob=cfg.norm_topk_prob,
            dtype=cfg.dtype, activation="relu")
        self.use_recompute = cfg.use_recompute
        # the named scopes a device trace groups this layer's time by
        kind = "attn_global" if self.self_attn.window is None \
            else "attn_window"
        self._attn_scope = f"layer{index}/{kind}"
        self._moe_scope = f"layer{index}/moe"

    def _block(self, x):
        """(the layer's output, the rows its experts computed):
        everything a rematerialised region may hand out."""
        telemetry.default_tracer().metrics.inc("moe.route.pre_attention")
        with jax.named_scope(self._attn_scope):
            h = x + self.self_attn(self.input_layernorm(x))
        with jax.named_scope(self._moe_scope):
            # the router reads the layer's input; the experts what
            # attention made of it
            y, seen = self.block_sparse_moe.compute(
                self.post_attention_layernorm(h), router_input=x)
            return h + y, seen

    def forward(self, x):
        if self.use_recompute:
            from ..distributed.fleet import recompute
            from ..ops.pallas.flash_attention import FLASH_KEEP
            from .llama import _LayerFn
            # at 16,384 tokens a flash forward kernel costs O(s x band) to
            # make again and O(s) to hold: the region keeps its outputs
            # and makes everything else again
            h, seen = recompute(_LayerFn(self), x, keep=FLASH_KEEP)
        else:
            h, seen = self._block(x)
        self.block_sparse_moe.count(seen)
        return h


class SmallThinkerModel(nn.Layer):
    def __init__(self, cfg: SmallThinkerConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [SmallThinkerDecoderLayer(cfg, i)
             for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                               dtype=cfg.dtype)

    def forward(self, input_ids):
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
            if self.cfg.dtype != "float32":
                h = h.astype(self.cfg.dtype)
        for layer in self.layers:
            h = layer(h)
        with jax.named_scope("final_norm"):
            return self.norm(h)


class SmallThinkerForCausalLM(nn.Layer):
    def __init__(self, cfg: SmallThinkerConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.model = SmallThinkerModel(cfg)
        self.lm_head = make_lm_head(cfg.hidden_size, cfg.vocab_size)
        # the default registry's snapshot() asks for the experts' counters
        # (moe.rows_held, moe.rows_max_expert, moe.rows_walked,
        # moe.rows_routed)
        telemetry.default_tracer().metrics.add_source(
            "moe", weakref.WeakMethod(self.routing_counts))

    def forward(self, input_ids):
        return head_output(self.model(input_ids), self.lm_head, None)

    def loss(self, logits, labels):
        """Mean next-token cross entropy."""
        return next_token_loss(logits, labels, self.lm_head, None)

    def routing_counts(self) -> dict:
        """The layers' expert counters summed (``rows_max_expert``: the
        busiest single expert of any layer)."""
        return nn.MoEShareLayer.summed_counts(
            layer.block_sparse_moe for layer in self.model.layers)


def smallthinker_tiny(**kw) -> SmallThinkerConfig:
    """Small enough for the CPU, with every mechanism biting: one period
    (a global layer without rotary embedding, three rotary layers under a
    window of 16 keys), groups of three query heads a key head, 8 experts
    of which 2 are held, top-2."""
    base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=4,
                num_attention_heads=6, num_key_value_heads=2, head_dim=16,
                moe_ffn_hidden_size=48, moe_num_primary_experts=8,
                moe_num_active_primary_experts=2, expert_share=(0, 4),
                sliding_window_size=16, sliding_window_layout=(0, 1, 1, 1),
                rope_layout=(0, 1, 1, 1))
    return SmallThinkerConfig(**dict(base, **kw))
