"""Laguna-S-2.1: a decoder whose window and full layers have different
numbers of query heads, a sigmoid gate on each head's output, two rotary
embeddings of different kind and width, and a shared expert beside the
routed ones.

Layer ``i`` on its input ``x`` (the residual stream): ``h = x +
Attn_i(RMSNorm(x))``, then ``x' = h + FFN_i(RMSNorm(h))``.

- ``Attn_i`` takes everything from ``layer_types[i]`` and
  ``num_attention_heads_per_layer[i]``: ``H_i`` query heads of
  ``head_dim`` over ``num_key_value_heads`` key heads (grouped-query
  attention), no biases, no head norms, scale ``head_dim ** -0.5``,
  causal. A ``sliding_attention`` layer's query at t sees the keys s with
  ``t - sliding_window < s <= t`` (``ops.flash_attention``'s ``window=``);
  a ``full_attention`` layer's every earlier key. q and k take the rotary
  embedding of ``rope_parameters[layer_types[i]]`` over their first
  ``partial_rotary_factor * head_dim`` dimensions (half-split pairs
  inside them; the rest pass through unscaled): ``default`` at its
  ``rope_theta``, or ``yarn`` (``ops.rope.yarn_inv_freq``) with cos and
  sin times its ``attention_factor``.
- The per-head gate: ``g = sigmoid(RMSNorm(x) W_g)``, ``W_g`` [hidden,
  H_i] without bias, on the input q reads; head h's output is multiplied
  by ``g_h`` before the output projection.
- ``FFN_i`` from ``mlp_layer_types[i]``: ``dense`` is a SwiGLU MLP of
  ``intermediate_size``; ``sparse`` is the routed experts (SwiGLU of
  ``moe_intermediate_size``) plus one shared SwiGLU expert of
  ``shared_expert_intermediate_size`` on the same input, added without a
  gate. The router: float32 logits over all ``num_experts``, softmax, the
  ``num_experts_per_tok`` largest, divided by their sum
  (``norm_topk_prob``), times ``moe_routed_scaling_factor``; weights on
  the experts' outputs. This process holds the ``expert_share``
  (``nn.MoEShareLayer``); the shared expert is whole on every share.

A last RMSNorm and an untied head; ``loss`` is the mean next-token loss.
"""
from __future__ import annotations

import weakref
from dataclasses import dataclass, field
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..framework.core import apply
from ..nn import functional as F
from ..ops.flash_attention import flash_attention
from ..ops.rope import build_rope_cache, rope_reference, yarn_inv_freq
from ..utils import telemetry
from .lm_head import head_output, make_lm_head, next_token_loss

__all__ = ["LagunaConfig", "LagunaForCausalLM", "LagunaModel",
           "laguna_tiny"]

_PERIOD = ("full_attention",) + ("sliding_attention",) * 3
_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 128,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}


@dataclass
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72) * 12
    layer_types: Tuple[str, ...] = _PERIOD * 12
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 47
    sliding_window: int = 512
    rope_parameters: Dict[str, dict] = field(
        default_factory=lambda: {k: dict(v) for k, v in _ROPE.items()})
    num_experts: int = 256              # the router's width
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    # (index, count): this process holds experts index * num_experts /
    # count onward (nn.MoEShareLayer)
    expert_share: Tuple[int, int] = (0, 1)
    rms_norm_eps: float = 1e-6
    dtype: str = "float32"
    use_recompute: bool = False

    def __post_init__(self):
        for name, kinds in (
                ("num_attention_heads_per_layer", None),
                ("layer_types", set(_ROPE)),
                ("mlp_layer_types", {"dense", "sparse"})):
            value = tuple(getattr(self, name))
            setattr(self, name, value)
            if len(value) != self.num_hidden_layers or (
                    kinds is not None and set(value) - kinds):
                raise ValueError(
                    f"{name} has to name each of the "
                    f"{self.num_hidden_layers} layers"
                    + (f" as one of {sorted(kinds)}" if kinds else "")
                    + f"; got {value}")
        for i, heads in enumerate(self.num_attention_heads_per_layer):
            if heads % self.num_key_value_heads:
                raise ValueError(f"layer {i}: {heads} query heads over "
                                 f"{self.num_key_value_heads} key heads")
        for kind in set(self.layer_types):
            rope = self.rope_parameters[kind]
            if rope["rope_type"] not in ("default", "yarn"):
                raise ValueError(f"{kind}: rope_type {rope['rope_type']!r};"
                                 " there are: default, yarn")

    def window(self, i: int):
        """Layer ``i``'s window in keys, or None where it sees them all."""
        return self.sliding_window \
            if self.layer_types[i] == "sliding_attention" else None

    def rotary(self, i: int):
        """(rotated dimensions, inverse frequencies [rotated / 2] or None
        for the plain ones of ``rope_theta``, scale of cos and sin) of
        layer ``i``."""
        rope = self.rope_parameters[self.layer_types[i]]
        dim = int(self.head_dim * rope.get("partial_rotary_factor", 1))
        if rope["rope_type"] == "default":
            return dim, None, 1.0
        return dim, yarn_inv_freq(
            dim, float(rope["rope_theta"]), float(rope["factor"]),
            int(rope["original_max_position_embeddings"]),
            float(rope["beta_fast"]), float(rope["beta_slow"])), \
            float(rope["attention_factor"])


class LagunaAttention(nn.Layer):
    def __init__(self, cfg: LagunaConfig, index: int):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.window = cfg.window(index)
        self.num_heads = cfg.num_attention_heads_per_layer[index]
        self.yarn = cfg.rope_parameters[cfg.layer_types[index]][
            "rope_type"] == "yarn"
        self.rope_theta = float(
            cfg.rope_parameters[cfg.layer_types[index]]["rope_theta"])
        self.rotary_dim, self.inv_freq, self.rope_scale = cfg.rotary(index)
        h, d = cfg.hidden_size, cfg.head_dim
        lin = lambda n_in, n_out: nn.Linear(n_in, n_out, bias_attr=False)
        self.q_proj = lin(h, self.num_heads * d)
        self.k_proj = lin(h, cfg.num_key_value_heads * d)
        self.v_proj = lin(h, cfg.num_key_value_heads * d)
        self.o_proj = lin(self.num_heads * d, h)
        self.g_proj = lin(h, self.num_heads)

    def _rope(self, x):
        """The layer's rotary embedding of x [b, s, heads, d], in
        float32."""
        cos, sin = build_rope_cache(x.shape[1], self.rotary_dim,
                                    self.rope_theta, jnp.float32,
                                    self.inv_freq, self.rope_scale)
        return rope_reference(x.astype(jnp.float32), cos, sin) \
            .astype(x.dtype)

    def forward(self, x):
        """x [b, s, hidden], already normed -> [b, s, hidden]."""
        cfg = self.cfg
        nh, nkv, d = self.num_heads, cfg.num_key_value_heads, cfg.head_dim
        window = self.window
        metrics = telemetry.default_tracer().metrics
        metrics.inc("attn.gate.per_head")
        if self.yarn:
            metrics.inc("rope.yarn")
            metrics.set_gauge("rope.rotary_dim", self.rotary_dim)
        # made again in the backward pass from the projection's output,
        # which is kept in its own dtype
        place = jax.checkpoint(self._rope)

        def f(xa, wq, wk, wv, wo, wg):
            b, s, _ = xa.shape
            q = place((xa @ wq).reshape(b, s, nh, d))
            k = place((xa @ wk).reshape(b, s, nkv, d))
            v = (xa @ wv).reshape(b, s, nkv, d)
            o = flash_attention(q, k, v, causal=True, scale=d ** -0.5,
                                window=window)
            gate = jax.nn.sigmoid((xa @ wg).astype(jnp.float32))
            o = o * gate.astype(o.dtype)[..., None]
            return o.reshape(b, s, nh * d) @ wo

        return apply(
            "laguna_attention", f, x, self.q_proj.weight,
            self.k_proj.weight, self.v_proj.weight, self.o_proj.weight,
            self.g_proj.weight)


class LagunaMLP(nn.Layer):
    """SwiGLU: ``down(silu(gate x) * up x)``, no biases."""

    def __init__(self, hidden: int, width: int):
        super().__init__()
        self.gate_proj = nn.Linear(hidden, width, bias_attr=False)
        self.up_proj = nn.Linear(hidden, width, bias_attr=False)
        self.down_proj = nn.Linear(width, hidden, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LagunaDecoderLayer(nn.Layer):
    def __init__(self, cfg: LagunaConfig, index: int):
        super().__init__(dtype=cfg.dtype)
        h, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(h, eps, dtype=cfg.dtype)
        self.self_attn = LagunaAttention(cfg, index)
        self.post_attention_layernorm = nn.RMSNorm(h, eps, dtype=cfg.dtype)
        self.is_dense = cfg.mlp_layer_types[index] == "dense"
        if self.is_dense:
            self.mlp = LagunaMLP(h, cfg.intermediate_size)
        else:
            self.mlp = nn.MoEShareLayer(
                h, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, share=cfg.expert_share,
                norm_topk_prob=cfg.norm_topk_prob, dtype=cfg.dtype,
                routed_scaling_factor=cfg.moe_routed_scaling_factor)
            self.shared_expert = LagunaMLP(
                h, cfg.shared_expert_intermediate_size)
        self.use_recompute = cfg.use_recompute
        # the named scopes a device trace groups this layer's time by
        self._attn_scope = f"layer{index}/" + (
            "attn_global" if self.self_attn.window is None
            else "attn_window")
        self._ffn_scope = f"layer{index}/" + ("mlp" if self.is_dense
                                              else "moe")

    def _block(self, x):
        """The layer's output; with experts, also the rows they computed
        (everything a rematerialised region may hand out)."""
        with jax.named_scope(self._attn_scope):
            h = x + self.self_attn(self.input_layernorm(x))
        with jax.named_scope(self._ffn_scope):
            u = self.post_attention_layernorm(h)
            if self.is_dense:
                return h + self.mlp(u)
            telemetry.default_tracer().metrics.inc("moe.shared_expert")
            y, seen = self.mlp.compute(u)
            with jax.named_scope("shared"):
                y = y + self.shared_expert(u)
            return h + y, seen

    def forward(self, x):
        if self.use_recompute:
            from ..distributed.fleet import recompute
            from ..ops.pallas.flash_attention import FLASH_KEEP
            from .llama import _LayerFn
            # the region keeps its flash kernel's output and makes the
            # rest of its forward pass again
            out = recompute(_LayerFn(self), x, keep=FLASH_KEEP)
        else:
            out = self._block(x)
        if self.is_dense:
            return out
        h, seen = out
        self.mlp.count(seen)
        return h


class LagunaModel(nn.Layer):
    def __init__(self, cfg: LagunaConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [LagunaDecoderLayer(cfg, i)
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


class LagunaForCausalLM(nn.Layer):
    def __init__(self, cfg: LagunaConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.model = LagunaModel(cfg)
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
        """The expert layers' counters summed (``rows_max_expert``: the
        busiest single expert of any layer)."""
        return nn.MoEShareLayer.summed_counts(
            layer.mlp for layer in self.model.layers if not layer.is_dense)


def laguna_tiny(**kw) -> LagunaConfig:
    """Small enough for the CPU, with every mechanism biting: a dense
    layer, then one period (a full layer, three window layers under a
    window of 16 keys) and a full layer, groups of two query heads a key
    head on full layers and of three on window layers, YaRN over half of
    each head on the full layers, 16 experts of which 4 are held, top-3,
    scaled 2.5, and a shared expert."""
    rope = {k: dict(v) for k, v in _ROPE.items()}
    rope["full_attention"].update(original_max_position_embeddings=16,
                                  factor=8, attention_factor=1.2)
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
                num_attention_heads_per_layer=(4, 6, 6, 6, 4),
                layer_types=_PERIOD + ("full_attention",),
                mlp_layer_types=("dense",) + ("sparse",) * 4,
                sliding_window=16, rope_parameters=rope,
                num_experts=16, num_experts_per_tok=3,
                moe_intermediate_size=32, shared_expert_intermediate_size=24,
                expert_share=(0, 4))
    return LagunaConfig(**dict(base, **kw))
