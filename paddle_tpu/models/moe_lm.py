"""Mixture-of-Experts causal LM (DeepSeekMoE / Qwen2-MoE style).

Capability parity target: the reference's MoE stack
(/root/reference/python/paddle/incubate/distributed/models/moe/
moe_layer.py:263 + global_scatter/gather alltoall comm) as used by
DeepSeek/Qwen MoE recipes.

TPU-native: Llama-style decoder blocks whose MLP is an nn.MoELayer
(top-k gating, capacity-bounded dispatch expressed as one-hot matmuls —
MXU-friendly — with the expert dim sharded over the mesh 'ep'/'mp' axis
under fleet; the all-to-all is GSPMD-inserted). A DeepSeek-style shared
expert runs densely alongside the routed experts.
"""
from __future__ import annotations

from dataclasses import dataclass

from .. import nn
from ..nn import functional as F
from .lm_head import head_output, make_lm_head, next_token_loss
from .llama import LlamaAttention, LlamaConfig, _LayerFn

__all__ = ["MoEConfig", "MoEForCausalLM", "MoEModel", "moe_tiny"]


@dataclass
class MoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632        # shared-expert/dense FFN width
    moe_intermediate_size: int = 1408    # per-expert FFN width
    num_hidden_layers: int = 8
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    num_experts: int = 8
    num_experts_per_tok: int = 2
    num_shared_experts: int = 1          # DeepSeek-style dense experts
    first_k_dense_replace: int = 1       # first k layers use dense MLP
    capacity_factor: float = 1.25
    # 'dense' = GShard one-hot dispatch (EP-shardable); 'ragged' =
    # sort-based dropless grouped-matmul dispatch (the large-E on-chip
    # path — memory O(T*k*D) instead of O(T*E*C))
    moe_dispatch_mode: str = "dense"
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    aux_loss_weight: float = 0.01
    dtype: str = "float32"
    use_recompute: bool = False
    # 'full' | 'full_attn' | 'core_attn' (see LlamaConfig)
    recompute_granularity: str = "full"
    tensor_parallel: bool = False
    # >0: forward() returns hidden states; loss() runs the chunked
    # head-matmul + CE (see nn.functional.chunked_softmax_cross_entropy)
    chunked_ce_tokens: int = 0

    def _attn_cfg(self) -> LlamaConfig:
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
            dtype=self.dtype, tensor_parallel=self.tensor_parallel)


class _DenseMLP(nn.Layer):
    def __init__(self, d_model, d_hidden, dtype):
        super().__init__(dtype=dtype)
        self.gate_proj = nn.Linear(d_model, d_hidden, bias_attr=False)
        self.up_proj = nn.Linear(d_model, d_hidden, bias_attr=False)
        self.down_proj = nn.Linear(d_hidden, d_model, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class MoEBlock(nn.Layer):
    """Routed experts + optional shared (always-on) expert."""

    def __init__(self, cfg: MoEConfig):
        super().__init__(dtype=cfg.dtype)
        self.moe = nn.MoELayer(
            d_model=cfg.hidden_size,
            d_hidden=cfg.moe_intermediate_size,
            num_experts=cfg.num_experts, gate="gshard",
            top_k=cfg.num_experts_per_tok,
            capacity_factor=cfg.capacity_factor,
            dispatch_mode=cfg.moe_dispatch_mode)
        self.shared = _DenseMLP(
            cfg.hidden_size,
            cfg.moe_intermediate_size * cfg.num_shared_experts,
            cfg.dtype) if cfg.num_shared_experts > 0 else None

    def forward(self, x):
        routed = self.moe(x)
        if self.shared is not None:
            routed = routed + self.shared(x)
        return routed

    @property
    def aux_loss(self):
        return self.moe.aux_loss


class MoEDecoderLayer(nn.Layer):
    def __init__(self, cfg: MoEConfig, layer_idx: int):
        super().__init__(dtype=cfg.dtype)
        acfg = cfg._attn_cfg()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          cfg.rms_norm_eps,
                                          dtype=cfg.dtype)
        self.self_attn = LlamaAttention(acfg)
        self.post_attention_layernorm = nn.RMSNorm(
            cfg.hidden_size, cfg.rms_norm_eps, dtype=cfg.dtype)
        self.is_dense = layer_idx < cfg.first_k_dense_replace
        if self.is_dense:
            self.mlp = _DenseMLP(cfg.hidden_size, cfg.intermediate_size,
                                 cfg.dtype)
        else:
            self.mlp = MoEBlock(cfg)
        self.use_recompute = cfg.use_recompute
        self.recompute_granularity = cfg.recompute_granularity

    def _block(self, x):
        h = x + self.self_attn(self.input_layernorm(x))
        return h + self.mlp(self.post_attention_layernorm(h))

    def forward(self, x):
        if self.use_recompute:
            from ..distributed.fleet import recompute
            from .llama import _AttnFn
            gran = self.recompute_granularity
            if gran == "full":
                if isinstance(self.mlp, MoEBlock):
                    # the router aux-loss must cross the checkpoint
                    # boundary as an OUTPUT — a side-channel store from
                    # inside jax.checkpoint leaks an escaped tracer
                    out, aux = recompute(_MoEBlockFn(self), x)
                    self.mlp.moe._aux_loss = aux
                    return out
                return recompute(_LayerFn(self), x)
            if gran == "full_attn":
                h = x + recompute(_AttnFn(self), x)
                return h + self.mlp(self.post_attention_layernorm(h))
            if gran == "core_attn":
                return self._block(x)
            raise ValueError(
                f"unknown recompute_granularity {gran!r}; expected "
                "'full', 'full_attn' or 'core_attn'")
        return self._block(x)


class _MoEBlockFn:
    """recompute() adapter for an MoE decoder layer: returns
    (block_output, router_aux_loss) so the aux-loss is a real
    checkpoint output with a grad path, not an escaped tracer."""

    def __init__(self, layer):
        self.layer = layer

    def parameters(self):
        return self.layer.parameters()

    def __call__(self, x):
        out = self.layer._block(x)
        return out, self.layer.mlp.aux_loss


class MoEModel(nn.Layer):
    def __init__(self, cfg: MoEConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [MoEDecoderLayer(cfg, i)
             for i in range(cfg.num_hidden_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                               dtype=cfg.dtype)

    def forward(self, input_ids):
        h = self.embed_tokens(input_ids)
        if self.cfg.dtype != "float32":
            h = h.astype(self.cfg.dtype)
        for layer in self.layers:
            h = layer(h)
        return self.norm(h)

    def aux_losses(self):
        out = []
        for layer in self.layers:
            if isinstance(layer.mlp, MoEBlock) and \
                    layer.mlp.aux_loss is not None:
                out.append(layer.mlp.aux_loss)
        return out


class MoEForCausalLM(nn.Layer):
    def __init__(self, cfg: MoEConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.model = MoEModel(cfg)
        self.lm_head = make_lm_head(cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids):
        return head_output(self.model(input_ids), self.lm_head, None,
                           self.cfg.chunked_ce_tokens)

    def loss(self, out, labels):
        """Shifted CE + router load-balance auxiliary loss."""
        ce = next_token_loss(out, labels, self.lm_head, None,
                             self.cfg.chunked_ce_tokens)
        aux = self.model.aux_losses()
        if aux and self.cfg.aux_loss_weight:
            total_aux = aux[0]
            for a in aux[1:]:
                total_aux = total_aux + a
            ce = ce + self.cfg.aux_loss_weight * total_aux
        return ce

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def num_activated_params(self) -> int:
        """Per-token activated parameters (MoE efficiency metric)."""
        total = 0
        for name, p in self.named_parameters():
            if ".moe." in name and ("w1" in name or "w2" in name
                                    or "experts" in name):
                total += p.size * self.cfg.num_experts_per_tok \
                    // self.cfg.num_experts
            else:
                total += p.size
        return total


def moe_tiny(**kw) -> MoEConfig:
    base = dict(vocab_size=512, hidden_size=128,
                intermediate_size=256, moe_intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=4, num_experts=4,
                num_experts_per_tok=2, first_k_dense_replace=1,
                max_position_embeddings=256)
    base.update(kw)          # callers may override any default
    return MoEConfig(**base)

