"""Llama-family decoder-only transformer — the framework's flagship model.

Capability parity target: PaddleNLP's Llama stack trained with Fleet 4D
parallel (reference framework side: fleet hybrid topology
/root/reference/python/paddle/distributed/fleet/base/topology.py:174, TP
layers /root/reference/python/paddle/distributed/fleet/layers/mpu/
mp_layers.py, fused rope/rms incubate ops).

TPU-native design:
- RMSNorm + rotary + GQA attention via ops.flash_attention (Pallas on TPU)
- SwiGLU MLP
- tensor parallel via Column/RowParallelLinear + VocabParallelEmbedding
  when a fleet mesh with mp_degree > 1 is active
- FSDP/dp are placement recipes applied by fleet.distributed_model
- bf16 weights with f32 master copies in the optimizer (multi_precision)
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from ..framework.core import Tensor, apply
from .. import nn
from ..nn import functional as F
from .lm_head import head_output, make_lm_head, next_token_loss
from ..ops.rope import build_rope_cache, rope_reference

__all__ = ["LlamaConfig", "LlamaForCausalLM", "LlamaModel", "llama_tiny",
           "llama_small", "llama_mid", "llama_3_8b"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: str = "float32"
    use_recompute: bool = False
    # reference recompute_granularity (PaddleNLP llama configs):
    # "full"      — whole block rematerialized (max memory savings)
    # "full_attn" — only the attention sublayer (ln1 + attn)
    #               rematerialized; MLP activations stored
    # "core_attn" — only the attention inner recomputed: with the Pallas
    #               flash kernel the plain forward (its backward already
    #               recomputes from q/k/v and stores no probabilities)
    # recompute(keep=) can keep kernels' outputs by name; Llama asks for none
    recompute_granularity: str = "full"
    # parallelism knobs (consumed when a fleet mesh is active)
    tensor_parallel: bool = False
    sequence_parallel: bool = False
    # context parallelism (reference hybrid_configs sep_degree,
    # fleet/base/topology.py:497 + meta_parallel/segment_parallel.py):
    # >1 = training attention runs zigzag ring attention over the
    # fleet mesh's 'sep' axis (must match its size); the sequence dim
    # of q/k/v shards across the ring, KV blocks rotate over ICI
    sep_degree: int = 1
    # >0: forward() returns hidden states and loss() computes the head
    # matmul + cross entropy in chunks of this many tokens under
    # jax.checkpoint (training-memory config; generate() still works —
    # the cached decode path keeps the normal head). 0, the dense path,
    # holds the [B, S, V] logits and their gradient in the model's
    # dtype at the end of the forward pass (1 GB each at b4 s4096 v32k
    # bf16) and nothing float32 of that shape
    chunked_ce_tokens: int = 0


def _mp_active() -> bool:
    from ..distributed.fleet import get_hybrid_communicate_group
    hcg = get_hybrid_communicate_group()
    return hcg is not None and hcg.get_model_parallel_world_size() > 1


def _sep_mesh(sep_degree: int):
    """The fleet mesh, when CP is requested and the mesh has a 'sep'
    axis of the configured size (loud on mismatch)."""
    if sep_degree <= 1:
        return None
    from ..distributed.fleet import get_hybrid_communicate_group
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        return None                      # single-device runs/tests
    mesh = hcg.mesh
    if "sep" not in mesh.dim_names or \
            mesh.get_dim_size("sep") != sep_degree:
        raise ValueError(
            f"sep_degree={sep_degree} needs a fleet mesh with a 'sep' "
            f"axis of that size; got {mesh.dim_names} "
            f"{[mesh.get_dim_size(a) for a in mesh.dim_names]} — set "
            "hybrid_configs sep_degree")
    return mesh


class LlamaMLP(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__(dtype=cfg.dtype)
        if cfg.tensor_parallel and _mp_active():
            from ..distributed.fleet import (ColumnParallelLinear,
                                             RowParallelLinear)
            self.gate_proj = ColumnParallelLinear(
                cfg.hidden_size, cfg.intermediate_size, has_bias=False,
                gather_output=False)
            self.up_proj = ColumnParallelLinear(
                cfg.hidden_size, cfg.intermediate_size, has_bias=False,
                gather_output=False)
            self.down_proj = RowParallelLinear(
                cfg.intermediate_size, cfg.hidden_size, has_bias=False,
                input_is_parallel=True)
        else:
            self.gate_proj = nn.Linear(cfg.hidden_size, cfg.intermediate_size,
                                       bias_attr=False)
            self.up_proj = nn.Linear(cfg.hidden_size, cfg.intermediate_size,
                                     bias_attr=False)
            self.down_proj = nn.Linear(cfg.intermediate_size, cfg.hidden_size,
                                       bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__(dtype=cfg.dtype)
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.hidden_size = cfg.hidden_size
        q_out = cfg.hidden_size
        kv_out = self.num_kv_heads * self.head_dim
        self._tp = cfg.tensor_parallel and _mp_active()
        if self._tp:
            # heads shard over mp: q/k/v stay feature-sharded
            # (gather_output=False), attention runs on the local heads, and
            # o_proj's row-parallel matmul reduces — matching the
            # reference's mp_layers head partitioning.
            from ..distributed.fleet import (ColumnParallelLinear,
                                             RowParallelLinear)
            from ..distributed.fleet.mpu import _get_mesh
            mesh = _get_mesh()
            mp = mesh.get_dim_size("mp")
            if self.num_kv_heads % mp or self.num_heads % mp:
                raise ValueError(
                    f"num_heads {self.num_heads} / num_kv_heads "
                    f"{self.num_kv_heads} must divide mp degree {mp}")
            self.q_proj = ColumnParallelLinear(cfg.hidden_size, q_out,
                                               has_bias=False,
                                               gather_output=False)
            self.k_proj = ColumnParallelLinear(cfg.hidden_size, kv_out,
                                               has_bias=False,
                                               gather_output=False)
            self.v_proj = ColumnParallelLinear(cfg.hidden_size, kv_out,
                                               has_bias=False,
                                               gather_output=False)
            self.o_proj = RowParallelLinear(q_out, cfg.hidden_size,
                                            has_bias=False,
                                            input_is_parallel=True)
        else:
            self.q_proj = nn.Linear(cfg.hidden_size, q_out, bias_attr=False)
            self.k_proj = nn.Linear(cfg.hidden_size, kv_out, bias_attr=False)
            self.v_proj = nn.Linear(cfg.hidden_size, kv_out, bias_attr=False)
            self.o_proj = nn.Linear(q_out, cfg.hidden_size, bias_attr=False)
        self.rope_theta = cfg.rope_theta
        self.sep_degree = cfg.sep_degree

    def forward(self, x, rope_cos=None, rope_sin=None, past_kv=None,
                pos=None):
        """past_kv: optional (k_cache, v_cache) Tensors of fixed shape
        [b, max_len, kv_heads, head_dim]; pos: scalar Tensor — number of
        tokens already cached. With a cache, returns (out, new_kv) and
        attends this chunk's queries over cache[:pos]+chunk (the decode
        path; shapes stay static so ONE compiled program serves every
        step)."""
        b, s = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        if self._tp:
            # keep the head dim sharded over mp through the reshape
            from ..distributed.fleet.mpu import _constrain, _get_mesh
            mesh = _get_mesh()
            head_spec = [None, None, "mp", None]
            q = _constrain(q, mesh, head_spec)
            k = _constrain(k, mesh, head_spec)
            v = _constrain(v, mesh, head_spec)

        # rotary embedding (fused-rope parity) applied inside one taped
        # op; with a cache the table is sliced at the running offset
        if past_kv is None:
            def rope_fn(qa, ka):
                cos, sin = build_rope_cache(s, self.head_dim,
                                            self.rope_theta, jnp.float32)
                qo = rope_reference(qa, cos.astype(qa.dtype),
                                    sin.astype(qa.dtype))
                ko = rope_reference(ka, cos.astype(ka.dtype),
                                    sin.astype(ka.dtype))
                return qo, ko
            q, k = apply("fused_rope", rope_fn, q, k)
            sep_mesh = _sep_mesh(self.sep_degree)
            if sep_mesh is not None:
                # context parallelism: zigzag ring attention over the
                # 'sep' axis (sequence sharded, KV rotates the ring);
                # dp/mp compose as GSPMD auto axes around it
                from ..distributed.ring_attention import ring_attention
                out = apply(
                    "ring_attention",
                    lambda qa, ka, va: ring_attention(
                        qa, ka, va, sep_mesh, axis="sep", causal=True),
                    q, k, v)
            else:
                out = F.scaled_dot_product_attention(q, k, v,
                                                     is_causal=True)
            out = out.reshape([b, s, self.num_heads * self.head_dim])
            if self._tp:
                from ..distributed.fleet.mpu import _constrain, _get_mesh
                out = _constrain(out, _get_mesh(), [None, None, "mp"])
            return self.o_proj(out)

        past_k, past_v = past_kv
        max_len = past_k.shape[1]

        def cached_attn(qa, ka, va, pk, pv, p):
            import jax
            cos_f, sin_f = build_rope_cache(max_len, self.head_dim,
                                            self.rope_theta, jnp.float32)
            # cache layout [1, max_len, 1, d] → slice the seq axis
            cos = jax.lax.dynamic_slice_in_dim(cos_f, p, s, axis=1)
            sin = jax.lax.dynamic_slice_in_dim(sin_f, p, s, axis=1)
            qa = rope_reference(qa, cos.astype(qa.dtype),
                                sin.astype(qa.dtype))
            ka = rope_reference(ka, cos.astype(ka.dtype),
                                sin.astype(ka.dtype))
            nk = jax.lax.dynamic_update_slice_in_dim(pk, ka, p, axis=1)
            nv = jax.lax.dynamic_update_slice_in_dim(pv, va, p, axis=1)
            # GQA attention of the s new queries over nk[:, :p+s]
            group = self.num_heads // self.num_kv_heads
            qg = qa.reshape(b, s, self.num_kv_heads, group, self.head_dim)
            scores = jnp.einsum("bqkgd,bskd->bkgqs",
                                qg.astype(jnp.float32),
                                nk.astype(jnp.float32))
            scores = scores / jnp.sqrt(float(self.head_dim))
            kpos = jnp.arange(max_len)[None, None, None, None, :]
            qpos = p + jnp.arange(s)[None, None, None, :, None]
            scores = jnp.where(kpos <= qpos, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            og = jnp.einsum("bkgqs,bskd->bqkgd", probs,
                            nv.astype(jnp.float32))
            o = og.reshape(b, s, self.num_heads * self.head_dim)
            return o.astype(qa.dtype), nk, nv

        out, new_k, new_v = apply("cached_attention", cached_attn,
                                  q, k, v, past_k, past_v, pos)
        return self.o_proj(out), (new_k, new_v)


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__(dtype=cfg.dtype)
        # the named scopes a device trace groups this layer's time by;
        # LlamaModel puts the layer's index in
        self._attn_scope = "layer/attn"
        self._mlp_scope = "layer/mlp"
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                          dtype=cfg.dtype)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps,
                                                   dtype=cfg.dtype)
        self.mlp = LlamaMLP(cfg)
        self.use_recompute = cfg.use_recompute
        self.recompute_granularity = cfg.recompute_granularity

    def _mlp(self, h):
        with jax.named_scope(self._mlp_scope):
            return h + self.mlp(self.post_attention_layernorm(h))

    def _block(self, x):
        with jax.named_scope(self._attn_scope):
            h = x + self.self_attn(self.input_layernorm(x))
        return self._mlp(h)

    def forward(self, x, past_kv=None, pos=None):
        if past_kv is not None:
            with jax.named_scope(self._attn_scope):
                attn, new_kv = self.self_attn(self.input_layernorm(x),
                                              past_kv=past_kv, pos=pos)
                h = x + attn
            return self._mlp(h), new_kv
        if self.use_recompute:
            from ..distributed.fleet import recompute
            gran = self.recompute_granularity
            if gran == "full":
                return recompute(_LayerFn(self), x)
            if gran == "full_attn":
                with jax.named_scope(self._attn_scope):
                    h = x + recompute(_AttnFn(self), x)
                return self._mlp(h)
            if gran == "core_attn":
                # flash backward recomputes scores/probs from q/k/v by
                # construction — the plain forward IS core_attn remat
                return self._block(x)
            raise ValueError(
                f"unknown recompute_granularity {gran!r}; expected "
                "'full', 'full_attn' or 'core_attn'")
        return self._block(x)


class _LayerFn:
    """Adapter giving recompute() access to the layer's parameters."""

    def __init__(self, layer):
        self.layer = layer

    def parameters(self):
        return self.layer.parameters()

    def __call__(self, x):
        return self.layer._block(x)


class _AttnFn:
    """recompute_granularity='full_attn': the rematerialized region is
    ln1 + attention (the residual add and MLP stay stored)."""

    def __init__(self, layer):
        self.layer = layer

    def parameters(self):
        return (list(self.layer.input_layernorm.parameters())
                + list(self.layer.self_attn.parameters()))

    def __call__(self, x):
        return self.layer.self_attn(self.layer.input_layernorm(x))


class LlamaModel(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        if cfg.tensor_parallel and _mp_active():
            from ..distributed.fleet import VocabParallelEmbedding
            self.embed_tokens = VocabParallelEmbedding(cfg.vocab_size,
                                                       cfg.hidden_size)
        else:
            self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(cfg) for _ in range(cfg.num_hidden_layers)])
        for i, layer in enumerate(self.layers):
            layer._attn_scope = f"layer{i}/attn"
            layer._mlp_scope = f"layer{i}/mlp"
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                               dtype=cfg.dtype)

    def forward(self, input_ids, caches=None, pos=None):
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
            if self.cfg.dtype != "float32":
                h = h.astype(self.cfg.dtype)
        if caches is not None:
            new_caches = []
            for layer, kv in zip(self.layers, caches):
                h, nkv = layer(h, past_kv=kv, pos=pos)
                new_caches.append(nkv)
        else:
            for layer in self.layers:
                h = layer(h)
        with jax.named_scope("final_norm"):
            h = self.norm(h)
        return h if caches is None else (h, new_caches)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__(dtype=cfg.dtype)
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        self.lm_head = make_lm_head(
            cfg.hidden_size, cfg.vocab_size, tied=cfg.tie_word_embeddings,
            tensor_parallel=cfg.tensor_parallel and _mp_active())

    def forward(self, input_ids, caches=None, pos=None):
        if caches is None:
            # with cfg.chunked_ce_tokens the hidden states: loss() owns
            # the head's matmul
            return head_output(self.model(input_ids), self.lm_head,
                               self.model.embed_tokens,
                               self.cfg.chunked_ce_tokens)
        h, new_caches = self.model(input_ids, caches=caches, pos=pos)
        return head_output(h, self.lm_head,
                           self.model.embed_tokens), new_caches

    def loss(self, out, labels):
        """Shifted causal-LM cross entropy of ``out = forward(ids)``
        (``lm_head.next_token_loss``)."""
        return next_token_loss(out, labels, self.lm_head,
                               self.model.embed_tokens,
                               self.cfg.chunked_ce_tokens)

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def generate(self, input_ids, max_new_tokens: int = 32,
                 max_length: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 top_p: float = 1.0, repetition_penalty: float = 1.0,
                 num_beams: int = 1, length_penalty: float = 0.0):
        """KV-cached autoregressive generation (the serving decode loop —
        reference analog: the generation path over
        block_multihead_attention). Prefill compiles once, the
        single-token decode step compiles once (static cache shapes,
        traced position), then every step is a fast replay.
        num_beams > 1 switches to deterministic beam search (per-beam
        GNMT length penalty, eos early-stop) — sampling knobs don't
        combine with it and are rejected. (New kwargs append after the
        r2 signature so positional callers keep their meaning.)
        """
        if num_beams > 1:
            if temperature > 0 or top_k > 0 or top_p < 1.0 \
                    or repetition_penalty != 1.0:
                raise ValueError(
                    "num_beams > 1 is deterministic beam search; "
                    "temperature/top_k/top_p/repetition_penalty do not "
                    "apply — drop them or use num_beams=1 sampling")
            from .generation import beam_search as _beam
            return _beam(self, input_ids, num_beams=num_beams,
                         max_new_tokens=max_new_tokens,
                         length_penalty=length_penalty,
                         eos_token_id=eos_token_id,
                         max_length=max_length)
        from .generation import generate as _generate
        return _generate(self, input_ids, max_new_tokens=max_new_tokens,
                         max_length=max_length, temperature=temperature,
                         top_k=top_k, top_p=top_p,
                         repetition_penalty=repetition_penalty,
                         eos_token_id=eos_token_id, seed=seed)


def llama_tiny(**kw) -> LlamaConfig:
    base = dict(vocab_size=512, hidden_size=128, intermediate_size=352,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=256)
    base.update(kw)          # callers may override any default
    return LlamaConfig(**base)


def llama_small(**kw) -> LlamaConfig:
    """~0.5B: 8 layers at llama_mid's width."""
    base = dict(vocab_size=32000, hidden_size=2048,
                intermediate_size=5632, num_hidden_layers=8,
                num_attention_heads=16, num_key_value_heads=8,
                max_position_embeddings=2048)
    base.update(kw)
    return LlamaConfig(**base)


def llama_mid(**kw) -> LlamaConfig:
    """~0.65B: 11 layers of 2048 x 5632, which with
    AdamW(multi_precision) and the activations of batch 4 x 2048 fits
    one 16 GB v5e chip (``chip_smoke.py`` trains it)."""
    base = dict(vocab_size=32000, hidden_size=2048,
                intermediate_size=5632, num_hidden_layers=11,
                num_attention_heads=16, num_key_value_heads=8,
                max_position_embeddings=2048)
    base.update(kw)
    return LlamaConfig(**base)


def llama_3_8b(**kw) -> LlamaConfig:
    base = dict(vocab_size=128256, hidden_size=4096,
                intermediate_size=14336, num_hidden_layers=32,
                num_attention_heads=32, num_key_value_heads=8,
                max_position_embeddings=8192, rope_theta=500000.0)
    base.update(kw)
    return LlamaConfig(**base)
